"""bright_glm: δ and its total over the rows the step asked for.

Every likelihood query (``StepStats.lik_queries``, θ-update and z-update
candidates alike) is one row read through this kernel: D·4 bytes of
features, 4 of target and 4 of ξ in, 4 of δ out, and a K-column dot
product, 2·D·K flops.
"""


def cost(ctx):
    d, k = ctx.cfg["d"], ctx.cfg.get("classes", 1)
    rows = ctx.traced["queries"]
    return rows * 2 * d * k, rows * (4 * d + 8 + 4)
