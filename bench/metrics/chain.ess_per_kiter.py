"""Minimum ESS over θ's coordinates (summed over chains) per 1,000
chain-iterations of the window."""


def read(ctx):
    iters = ctx.window["chain_iters"]
    return 1000.0 * ctx.window["min_ess"] / iters if iters > 0 else None
