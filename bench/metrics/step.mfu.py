"""% of the chip's roofline reached by the whole step over the traced
stretch: the least time for the step's logical work (``bench/work/step.py``)
over the traced window's length."""

from bench.roofline import least_seconds, work


def read(ctx):
    if ctx.trace is None or ctx.traced["chain_iters"] <= 0:
        return None
    flops, nbytes = work("step").cost(ctx)
    return 100.0 * least_seconds(flops, nbytes, ctx.peaks) / (
        ctx.trace["window_ns"] * 1e-9)
