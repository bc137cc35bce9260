"""% of the window that the driver's host work took: dispatch, regrow,
fold and the on_chunk hook, from the difference of the driver's counters
over the window (``ctx.window["driver"]``; the overflow wait is the
device's). The seconds that starting and stopping the profiler took
inside the window (``profiler_s``) leave both sides."""

from bench.harness import HOST_PARTS


def read(ctx):
    delta = ctx.window.get("driver")
    if delta is None:
        return None
    p = delta["profiler_s"]
    return 100.0 * (sum(delta[k] for k in HOST_PARTS) - p) / (
        ctx.window["seconds"] - p)
