"""Device µs per traced chain-iteration in FlyMC's z-update: the named
scope ``flymc.z`` and those under it (candidates, their δ, the flips)."""

from bench.scopes import phase_us


def read(ctx):
    return phase_us(ctx, ("flymc.z",))
