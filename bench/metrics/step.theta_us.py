"""Device µs per traced chain-iteration in the θ-update: the named scopes
``flymc.theta`` or ``regular.theta`` and any the program names under them
(``bench/scopes.py``)."""

from bench.scopes import phase_us


def read(ctx):
    return phase_us(ctx, ("flymc.theta", "regular.theta"))
