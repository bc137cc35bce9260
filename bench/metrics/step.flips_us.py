"""Device µs per traced chain-iteration in the z-update's partition swaps
and δ-cache writes: the named scope ``flymc.z.flips``."""

from bench.scopes import phase_us


def read(ctx):
    return phase_us(ctx, ("flymc.z.flips",))
