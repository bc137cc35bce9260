"""% of its roofline reached by the z_candidates kernel
(bench/work/z_candidates.py)."""

from bench.roofline import kernel_share


def read(ctx):
    return kernel_share(ctx, "z_candidates")
