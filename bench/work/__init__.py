"""Logical work per kernel: one module per kernel, found by its name.

Each module's ``cost(ctx)`` returns ``(flops, bytes)`` of the work the
algorithm asked for over the traced stretch of a run, counted from the
configuration's logical shapes and the program's counters, never from a
kernel's tiles, capacities or padding.
"""
