"""Shares of the chip's roofline from logical work and measured time."""

from __future__ import annotations

import importlib


def least_seconds(flops, nbytes, peaks):
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])


def work(name):
    return importlib.import_module(f"bench.work.{name}")


def kernel_share(ctx, kernel):
    """% of the roofline reached by ``kernel`` over the traced stretch:
    least time for its logical work over the summed device time of its
    events. None when the trace holds none of its events."""
    if ctx.trace is None:
        return None
    spent = ctx.trace["kernel_ns"].get(kernel, 0.0) * 1e-9
    if spent <= 0:
        return None
    flops, nbytes = work(kernel).cost(ctx)
    return 100.0 * least_seconds(flops, nbytes, ctx.peaks) / spent
