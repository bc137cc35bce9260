"""Shared FlyMC numerics — the single source of truth for δ and log L̃ math.

Everything here is consumed by *both* the pure-jnp reference path
(:mod:`repro.core.bounds`, :mod:`repro.core.flymc`,
:mod:`repro.kernels.bright_glm.ref`) and the fused Pallas kernel
(:mod:`repro.kernels.bright_glm.kernel`). Keeping one copy is a correctness
requirement, not a style choice: the two paths feed the same MH accept
decisions, so a guard present on one side and missing on the other (as
happened with the ``min(d, 80)`` clamp in ``log_expm1``) silently changes
the realized chain for extreme δ.

All functions are plain jnp element-wise math — safe to trace inside a
Pallas kernel body and under jit/vmap/shard_map alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_DELTA_FLOOR = 1e-10  # δ = logL - logB ≥ 0 in exact math; clamp FP noise.


def _expm1(d: jax.Array) -> jax.Array:
    """exp(d) - 1 to a few ulp, from exp and log only (Kahan's method).

    Pallas TPU has no ``expm1`` lowering, and ``exp(d) - 1`` alone loses
    every significant bit as d → 0. With u = exp(d) rounded, the factor
    d / log(u) cancels the rounding of u, so (u - 1)·d/log(u) is accurate
    wherever u ≠ 1; where u rounds to 1, expm1(d) = d to working precision.
    The u = 1 slots get a guarded log argument (double-where) so the
    unselected branch's gradient stays finite.
    """
    u = jnp.exp(d)
    one = u == 1.0
    u_safe = jnp.where(one, 2.0, u)
    return jnp.where(one, d, (u_safe - 1.0) * (d / jnp.log(u_safe)))


def _poly(x: jax.Array, coeffs) -> jax.Array:
    """Σ_k coeffs[k]·x^k by Horner's rule (coeffs lowest order first)."""
    acc = jnp.full_like(x, coeffs[-1])
    for c in coeffs[-2::-1]:
        acc = acc * x + c
    return acc


_LOG1PMX_SERIES = 0.25  # |w| below: series for log1p(w) - w
_LOG1PMX_TERMS = 16


def _log1pmx(w: jax.Array) -> jax.Array:
    """log1p(w) - w, accurate relative to its own size (≈ -w²/2 near 0).

    The direct difference cancels to nothing as w → 0; below
    ``_LOG1PMX_SERIES`` the alternating series Σ_{k≥2} (-1)^{k+1} w^k/k
    is summed instead (16 terms reach f32 precision at |w| = 1/4).
    Double-where guarded so neither branch's gradient turns NaN.
    """
    small = jnp.abs(w) < _LOG1PMX_SERIES
    ws = jnp.where(small, w, 0.0)
    wb = jnp.where(small, 1.0, w)
    coeffs = [(-1.0) ** (k + 1) / k for k in range(2, _LOG1PMX_TERMS + 2)]
    series = ws * ws * _poly(ws, coeffs)
    return jnp.where(small, series, jnp.log1p(wb) - wb)


def _sinhmx(x: jax.Array) -> jax.Array:
    """sinh(x) - x for |x| ≤ 1: the odd series x³/3! + … + x¹³/13!."""
    x2 = x * x
    fact = [6.0, 120.0, 5040.0, 362880.0, 39916800.0, 6227020800.0]
    return x * x2 * _poly(x2, [1.0 / f for f in fact])


def log_expm1(delta: jax.Array) -> jax.Array:
    """Stable log(exp(δ) - 1) = log L̃ for δ ≥ 0.

    Both branches receive guarded inputs (double-where): in f32,
    exp(-δ) rounds to 1.0 for δ ≲ 1e-8 and log1p(-1.0) = -inf would poison
    the gradient of the *unselected* branch (0 · inf = NaN). The inner
    ``min(d, 80)`` keeps exp(-δ) from flushing to a denormal-zero whose
    log1p gradient is garbage for extreme δ.
    """
    d = jnp.maximum(delta, _DELTA_FLOOR)
    small = d < 15.0
    d_small = jnp.where(small, d, 1.0)
    d_big = jnp.where(small, 20.0, d)
    return jnp.where(
        small,
        jnp.log(_expm1(d_small)),
        d_big + jnp.log1p(-jnp.exp(-jnp.minimum(d_big, 80.0))),
    )


def fixed_order_sum(v: jax.Array) -> jax.Array:
    """Σ v over a 1-D buffer, in an order fixed by slot position alone.

    A pairwise tree over the buffer zero-padded to a power of two: slot i
    always meets the same partners, and zero padding only adds exact
    zeros. So a masked sum over a capacity-C bright buffer is bitwise the
    same at every C that holds the bright prefix — which the overflow
    re-run protocol needs of the joint log density, and which ``jnp.sum``
    does not give (XLA picks its reduction order by length).
    """
    n = v.shape[0]
    v = jnp.pad(v, (0, (1 << max(0, (n - 1).bit_length())) - n))
    while v.shape[0] > 1:
        v = v[0::2] + v[1::2]
    return v[0]


# ---------------------------------------------------------------------------
# Counter-based per-datum RNG (shared by the fused z-update kernel & its ref)
# ---------------------------------------------------------------------------
#
# The z-kernel's exactness story needs per-*datum* randomness (flymc.py's
# capacity/chunk-invariance contract), but materializing three (N,) uniform
# arrays per step is exactly the O(N) work the fused engine exists to kill.
# Instead each uniform is a pure function  u = f(step_key, draw_id, datum):
# one Threefry-2x32 block (Salmon et al. 2011, the same cipher behind jax's
# PRNG) whose counter words are (draw_id, datum_index). The Pallas kernel
# evaluates it on streamed (block, 128) tiles, the jnp side on whatever
# small buffer it holds (bright slots, compacted candidates) — same bits
# either way, never a length-N intermediate.
#
# Everything is carried in int32 lanes (Mosaic's native integer width):
# adds wrap mod 2^32 identically to uint32, and right shifts go through
# lax.shift_right_logical so sign bits never smear.

# Draw-id words: one independent stream per Algorithm-2 decision.
DRAW_DARKEN = 0  # bright → dark accept uniform (u1)
DRAW_CAND = 1  # dark → bright candidate selection (u2)
DRAW_BRIGHT = 2  # candidate brighten accept uniform (u3)

_UNIFORM_BITS = 24  # bits24 ∈ [0, 2^24): exact in f32, u = bits24 · 2⁻²⁴


def _rotl32(x: jax.Array, d: int) -> jax.Array:
    return (x << d) | jax.lax.shift_right_logical(x, 32 - d)


def threefry2x32(
    k0: jax.Array, k1: jax.Array, x0: jax.Array, x1: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Threefry-2x32, 20 rounds, on int32 lanes (bit-compatible with uint32).

    Safe to trace inside a Pallas kernel body (adds/xors/shifts only) and in
    plain jnp — the fused z-update kernel and its pure-jnp reference import
    this one definition, so their bit streams cannot drift.
    """
    rotations = ((13, 15, 26, 6), (17, 29, 16, 24))
    k0 = k0.astype(jnp.int32)
    k1 = k1.astype(jnp.int32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.int32(0x1BD11BDA))
    x0 = (x0.astype(jnp.int32) + k0).astype(jnp.int32)
    x1 = (x1.astype(jnp.int32) + k1).astype(jnp.int32)
    for r in range(5):
        for d in rotations[r % 2]:
            x0 = x0 + x1
            x1 = _rotl32(x1, d) ^ x0
        x0 = x0 + ks[(r + 1) % 3]
        x1 = x1 + ks[(r + 2) % 3] + jnp.int32(r + 1)
    return x0, x1


def counter_bits24(
    key_words: jax.Array, draw_id: int, datum: jax.Array
) -> jax.Array:
    """24-bit random integers keyed on (step key, draw stream, datum index).

    ``key_words`` is a (2,) int32 array (bitcast PRNG key data); ``datum``
    any int32 array of datum indices. Returns int32 in [0, 2^24) with the
    same shape as ``datum``.
    """
    x0 = jnp.full(datum.shape, draw_id, jnp.int32)
    b0, _ = threefry2x32(key_words[0], key_words[1], x0, datum.astype(jnp.int32))
    return jax.lax.shift_right_logical(b0, 32 - _UNIFORM_BITS)


def counter_uniform(
    key_words: jax.Array, draw_id: int, datum: jax.Array
) -> jax.Array:
    """Per-datum U[0, 1) floats (24-bit grid) from :func:`counter_bits24`."""
    return counter_bits24(key_words, draw_id, datum).astype(jnp.float32) * (
        1.0 / (1 << _UNIFORM_BITS)
    )


def key_words_of(key: jax.Array) -> jax.Array:
    """(2,) int32 counter-RNG key words from a jax PRNG key (typed or raw)."""
    data = key
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    return jax.lax.bitcast_convert_type(data.reshape(-1)[:2], jnp.int32)


# ---------------------------------------------------------------------------
# Jaakkola–Jordan (logistic) bound pieces
# ---------------------------------------------------------------------------


def jj_a(xi: jax.Array) -> jax.Array:
    """a(ξ) = -tanh(ξ/2)/(4ξ), with the ξ→0 limit -1/8 handled exactly."""
    safe = jnp.where(jnp.abs(xi) < 1e-4, 1.0, xi)
    a = -jnp.tanh(safe / 2.0) / (4.0 * safe)
    # Taylor: -1/8 + ξ²/96 + O(ξ⁴)
    return jnp.where(jnp.abs(xi) < 1e-4, -0.125 + xi * xi / 96.0, a)


def jj_c(xi: jax.Array) -> jax.Array:
    """c(ξ) = -a·ξ² + ξ/2 - log(eᶻ+1); tightness: log B(±ξ) = log σ(±ξ)."""
    return -jj_a(xi) * xi * xi + xi / 2.0 - jax.nn.softplus(xi)


def _logistic_delta_direct(s: jax.Array, xi: jax.Array) -> jax.Array:
    log_l = -jax.nn.softplus(-s)
    log_b = jj_a(xi) * s * s + 0.5 * s + jj_c(xi)
    return log_l - log_b


def logistic_delta(s: jax.Array, xi: jax.Array) -> jax.Array:
    """δ = log L - log B for the Jaakkola–Jordan bound, s = t·θᵀx.

    δ has a double root at the tangency |s| = |ξ|, where log L and log B
    agree to every bit f32 holds: subtracting them leaves rounding noise
    of ~1e-7, and log L̃ = log(expm1 δ) ≈ log δ turns that noise into O(1)
    errors. Near tangency (|Δ| < 1, Δ = (|s| - |ξ|)/2) δ is therefore
    summed from terms that are each O(Δ²) and computed without
    cancellation. With v₀ = |ξ|/2, g(s) = -log(2 cosh(s/2)) and
    e = cosh Δ - 1 + tanh(v₀)·sinh Δ = cosh(v₀+Δ)/cosh(v₀) - 1:

        δ = g(s) - g(ξ) - a(ξ)(s² - ξ²)
          = -4a(ξ)Δ² - (cosh Δ - 1) - tanh(v₀)(sinh Δ - Δ) - (log1p(e) - e)
    """
    v0 = 0.5 * jnp.abs(xi)
    dl = 0.5 * jnp.abs(s) - v0
    near = jnp.abs(dl) < 1.0
    dn = jnp.where(near, dl, 0.0)
    half = 0.5 * dn + _sinhmx(0.5 * dn)  # sinh(Δ/2)
    cosh_m1 = 2.0 * half * half
    sinh_mx = _sinhmx(dn)
    th = jnp.tanh(v0)
    e = cosh_m1 + th * (dn + sinh_mx)
    near_delta = (
        -4.0 * jj_a(xi) * dn * dn - cosh_m1 - th * sinh_mx - _log1pmx(e)
    )
    s_far = jnp.where(near, jnp.abs(xi) + 2.0, s)
    return jnp.where(near, near_delta, _logistic_delta_direct(s_far, xi))


# ---------------------------------------------------------------------------
# Student-t tangent bound
# ---------------------------------------------------------------------------


def student_t_delta(
    r: jax.Array, xi: jax.Array, nu: float, sigma: float
) -> jax.Array:
    """δ for the tangent-in-r² Gaussian bound on the Student-t density.

    ``r`` is the residual t - θᵀx. With f(u) = -((ν+1)/2)·log1p(u/ν) the
    log density in u = (r/σ)², the tangent at u₀ = (ξ/σ)² gives exactly

        δ = f(u) - f(u₀) - f'(u₀)(u - u₀) = -((ν+1)/2)·(log1p(w) - w),
        w = (u - u₀)/(ν + u₀),

    and u - u₀ = (|r| - |ξ|)(|r| + |ξ|)/σ². Written this way no two
    nearly equal terms are subtracted, so δ keeps its relative accuracy
    down to the tangency (see :func:`logistic_delta` for why that matters).
    """
    u0 = (xi / sigma) ** 2
    du = (jnp.abs(r) - jnp.abs(xi)) * (jnp.abs(r) + jnp.abs(xi)) / sigma**2
    return -((nu + 1.0) / 2.0) * _log1pmx(du / (nu + u0))


# ---------------------------------------------------------------------------
# Böhning (softmax) bound — lane-padded variant for the Pallas kernel
# ---------------------------------------------------------------------------


def softmax_delta_padded(
    eta: jax.Array,  # (B, Kp) logits θx, columns ≥ n_classes are padding
    eta0: jax.Array,  # (B, Kp) tangency logits (data.xi), same padding
    n_classes: int,
) -> jax.Array:
    """δ = log L - log B for the Böhning bound on lane-padded (B, Kp) logits.

    Padding columns (k ≥ n_classes) are excluded from every reduction, so
    the result is the Böhning δ of the unpadded (B, K) arrays (columns
    ≥ n_classes = K are then absent). The label terms of log L and log B
    cancel exactly, so with d = η - η₀ and p₀ = softmax(η₀):

        δ = ½ dᵀA d - [lse(η) - lse(η₀) - p₀·d],   A = ½(I - 𝟙𝟙ᵀ/K)

    Both terms vanish to second order at the tangency, where subtracting
    log L and log B would leave only rounding noise (see
    :func:`logistic_delta`). So ½dᵀAd is summed as ¼Σ(d - d̄)², and with
    y = d - p₀·d the bracket is log1p(Σ p₀·(expm1(y) - y)), each term
    O(y²); far from the tangency (max |y| ≥ 1) the bracket is a plain
    masked logsumexp.
    """
    valid = (
        jax.lax.broadcasted_iota(jnp.int32, eta.shape, eta.ndim - 1) < n_classes
    )
    neg = jnp.asarray(-1e30, eta.dtype)
    rowsum = lambda v: jnp.sum(jnp.where(valid, v, 0.0), axis=-1, keepdims=True)

    e0 = jnp.where(valid, eta0, neg)
    m0 = jnp.max(e0, axis=-1, keepdims=True)
    w0 = jnp.where(valid, jnp.exp(e0 - m0), 0.0)
    p0 = w0 / jnp.sum(w0, axis=-1, keepdims=True)  # softmax(η₀), 0 on padding
    d = jnp.where(valid, eta - eta0, 0.0)
    dc = jnp.where(valid, d - rowsum(d) / n_classes, 0.0)
    half_quad = 0.25 * jnp.sum(dc * dc, axis=-1)  # ½ dᵀAd
    y = jnp.where(valid, d - rowsum(p0 * d), 0.0)
    near = jnp.max(jnp.abs(y), axis=-1) < 1.0

    yn = jnp.where(near[..., None], y, 0.0)
    # expm1(y) - y = y²/2! + y³/3! + …, 11 terms for |y| < 1.
    fact, coeffs = 1.0, []
    for k in range(2, 13):
        fact *= k
        coeffs.append(1.0 / fact)
    expm1mx = yn * yn * _poly(yn, coeffs)
    bracket_near = jnp.log1p(jnp.sum(p0 * expm1mx, axis=-1))

    # Far: lse(log p₀ + y) over the valid columns, max-shifted.
    z = jnp.where(valid & (p0 > 0.0), jnp.log(jnp.where(p0 > 0.0, p0, 1.0)) + y, neg)
    zm = jnp.max(z, axis=-1, keepdims=True)
    bracket_far = zm[..., 0] + jnp.log(
        jnp.sum(jnp.where(valid, jnp.exp(z - zm), 0.0), axis=-1)
    )
    return half_quad - jnp.where(near, bracket_near, bracket_far)
