"""% of the traced window in which the device idled between the end of one
chunk program and the start of the next: the driver's per-chunk host
sync, fold and dispatch."""


def read(ctx):
    if ctx.trace is None or ctx.trace["chunk_programs"] < 2:
        return None
    return 100.0 * ctx.trace["boundary_idle_ns"] / ctx.trace["window_ns"]
