"""Pure-jnp oracle for the bright-GLM kernel.

Computes, for a padded buffer of bright indices, the per-datum
δ_n = log L_n - log B_n (by the kernel's formulas in
:mod:`repro.core.numerics`, so only the θᵀx reduction differs) and the masked pseudo-log-likelihood contribution
log(exp(δ)-1) — the inner loop of every FlyMC θ-update (paper §2, Alg. 1
line 19). Families: logistic (Jaakkola–Jordan bound), student_t (tangent
bound) and softmax (Böhning bound); each reduces to a (batched) inner
product plus scalar math per row. Doubles as the backward pass of the
fused kernel's custom VJP (:mod:`repro.kernels.bright_glm.ops`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bounds import GLMData, LogisticBound, SoftmaxBound, StudentTBound
from repro.core.numerics import log_expm1


def bright_glm_ref(
    x: jax.Array,  # (N, D) features
    t: jax.Array,  # (N,) labels / responses / class ids
    xi: jax.Array,  # (N,) per-datum bound tightness ((N, K) for softmax)
    idx: jax.Array,  # (C,) bright indices (padded; entries clamped to [0, N))
    mask: jax.Array,  # (C,) validity
    theta: jax.Array,  # (D,)  ((K, D) for softmax)
    family: str = "logistic",
    nu: float = 4.0,
    sigma: float = 1.0,
):
    """Returns (delta (C,), masked log-pseudo-likelihood contributions (C,))."""
    idx = jnp.clip(idx, 0, x.shape[0] - 1)
    rows = GLMData(
        x=jnp.take(x, idx, axis=0),
        t=jnp.take(t, idx, axis=0),
        xi=jnp.take(xi, idx, axis=0),
    )
    return bright_rows_ref(rows, mask, theta, family=family, nu=nu,
                           sigma=sigma)


def bright_rows_ref(
    rows: GLMData,  # the C gathered rows
    mask: jax.Array,  # (C,) validity
    theta: jax.Array,
    family: str = "logistic",
    nu: float = 4.0,
    sigma: float = 1.0,
):
    """:func:`bright_glm_ref` on rows already gathered."""
    if family == "logistic":
        delta = LogisticBound.fused_delta(theta, rows)
    elif family == "student_t":
        delta = StudentTBound(nu=nu, sigma=sigma).fused_delta(theta, rows)
    elif family == "softmax":
        delta = SoftmaxBound.fused_delta(theta, rows)
    else:
        raise ValueError(family)
    contrib = jnp.where(mask, log_expm1(delta), 0.0)
    return delta, contrib
