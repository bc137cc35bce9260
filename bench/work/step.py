"""The whole step: the likelihood rows it asked for, plus, for FlyMC, one
pass over the partition state.

Each of the ``lik_queries`` rows reads D·4 + 8 bytes (features, target,
ξ) and costs 2·D·K flops; a FlyMC chain-iteration also reads the (N,)
int32 partition once (4·N bytes). Regular MCMC has no partition.
"""


def cost(ctx):
    d, k, n = ctx.cfg["d"], ctx.cfg.get("classes", 1), ctx.cfg["n"]
    rows = ctx.traced["queries"]
    partition = 4 * n * ctx.traced["chain_iters"] if ctx.flymc else 0
    return rows * 2 * d * k, rows * (4 * d + 8) + partition
