"""Device µs per traced chain-iteration in the backward of the fused
likelihood's gradient: the named scopes ``flymc.theta.grad`` (the
θ-kernel's gradient) and ``flymc.refresh.grad`` (the refresh after the
z-update), which gradient θ-kernels such as MALA run and others do not."""

from bench.scopes import phase_us


def read(ctx):
    return phase_us(ctx, ("flymc.theta.grad", "flymc.refresh.grad"))
