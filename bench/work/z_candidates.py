"""z_candidates: one pass over the (N,) partition array per chain-iteration.

Reads the int32 partition once (4·N bytes) and writes the dark→bright
candidates, q_db·N expected (4 bytes each). No floating-point work.
"""


def cost(ctx):
    n, q = ctx.cfg["n"], ctx.cfg["q_db"]
    return 0, ctx.traced["chain_iters"] * (4 * n + 4 * q * n)
