"""% of its roofline reached by the bright_glm kernel (bench/work/bright_glm.py)."""

from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "bright_glm")
