"""% of the traced window in which no operation ran on the device."""


def read(ctx):
    if ctx.trace is None or ctx.trace["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_ns"] / ctx.trace["window_ns"])
