"""The shared δ numerics against references that do not share their code.

``repro.core.numerics`` computes δ = log L - log B by cancellation-free
formulas that the fused kernel and the jnp engines both use, so the kernel
parity tests cannot judge them. Here each formula is held to references
that compute δ the plain way, as log L - log B from the bounds' defining
expressions: at 40 significant digits (mpmath), and through the bounds'
own ``log_lik``/``log_bound`` in float64. Points cover the tangency, its
neighbourhood down to relative offsets of 1e-7, and points far from it.

Tolerance: |δ₃₂ - δ| ≤ RTOL·δ + SCALE_TOL·h², where h² is the size of
the second-order terms δ is summed from (Δ² for logistic and Student-t,
the squared logit offset for softmax). Where the bound's curvature
matches the likelihood's (logistic with |ξ| → 0, softmax along a
two-class direction) those terms still cancel at leading order, so δ is
accurate to f32 relative to h², not to itself. The plain f32 difference
log L - log B fails this bound near the tangency: its error is f32
rounding of log L itself (see the discrimination checks below).
"""

import math

import jax
import jax.numpy as jnp
import mpmath
import numpy as np
import pytest

from repro.core import bounds, numerics
from repro.core.bounds import (
    GLMData,
    LogisticBound,
    SoftmaxBound,
    StudentTBound,
)

jax.config.update("jax_platform_name", "cpu")

mpmath.mp.dps = 40
RTOL, SCALE_TOL = 1e-5, 1e-6
FLOOR = numerics._DELTA_FLOOR
NU, SIGMA, K = 4.0, 1.0, 3
# Relative offsets from the tangency: exact, near, and far.
OFFSETS = np.concatenate([[0.0], 10.0 ** -np.arange(7, 0, -1), [0.5, 2.0]])


def _mp(v):
    return mpmath.mpf(float(v))


def _mp_logistic(s, xi):
    s, xi = _mp(s), _mp(xi)
    a = -mpmath.mpf(1) / 8 if xi == 0 else -mpmath.tanh(xi / 2) / (4 * xi)
    c = -a * xi * xi + xi / 2 - mpmath.log(mpmath.exp(xi) + 1)
    log_l = -mpmath.log(1 + mpmath.exp(-s))
    return float(log_l - (a * s * s + s / 2 + c))


def _mp_student_t(r, xi):
    half = (mpmath.mpf(NU) + 1) / 2
    u, u0 = (_mp(r) / SIGMA) ** 2, (_mp(xi) / SIGMA) ** 2
    f = lambda v: -half * mpmath.log1p(v / NU)
    return float(f(u) - f(u0) + half / (NU + u0) * (u - u0))


def _mp_softmax(eta, eta0):
    eta, eta0 = [_mp(v) for v in eta], [_mp(v) for v in eta0]
    lse = lambda v: mpmath.log(mpmath.fsum(mpmath.exp(e) for e in v))
    # Label 0; δ does not depend on it (the label terms cancel).
    log_l = eta[0] - lse(eta)
    p0 = [mpmath.exp(e - lse(eta0)) for e in eta0]
    d = [a - b for a, b in zip(eta, eta0)]
    dbar = mpmath.fsum(d) / K
    quad = mpmath.fsum(di * (di - dbar) for di in d) / 2  # dᵀAd
    g = [(1 if k == 0 else 0) - p0[k] for k in range(K)]
    log_b = (eta0[0] - lse(eta0)) + mpmath.fsum(gk * dk for gk, dk in
                                                 zip(g, d)) - quad / 2
    return float(log_l - log_b)


def _within(got, truth, h2):
    """Rows where |got - truth| ≤ RTOL·|truth| + SCALE_TOL·h², beyond the
    reference's own rounding (1e-30: 40 digits of a log of order one)."""
    got = np.asarray(got, np.float64)
    return (np.abs(got - truth)
            <= RTOL * np.abs(truth) + SCALE_TOL * h2 + 1e-30)


def _logistic_points(rng):
    xi = np.concatenate([10.0 ** rng.uniform(-4, 0.8, 60), [0.0]])
    off = rng.choice(OFFSETS, xi.size) * rng.choice([-1, 1], xi.size)
    s = xi * (1 + off) * rng.choice([-1, 1], xi.size)
    xi = (xi * rng.choice([-1, 1], xi.size)).astype(np.float32)
    return s.astype(np.float32), xi


def _tangency_grid(rng, n=40):
    """(base, offset) pairs: every OFFSETS entry for n random bases."""
    base = rng.normal(0, 2, n)
    off = np.repeat(OFFSETS[None], n, 0) * rng.choice([-1, 1], (n, 1))
    return np.repeat(base[:, None], OFFSETS.size, 1), off


@pytest.mark.parametrize("family", ["logistic", "student_t", "softmax"])
def test_delta_matches_40_digit_reference(family):
    rng = np.random.default_rng(0)
    if family == "logistic":
        s, xi = _logistic_points(rng)
        s = np.concatenate([s, rng.normal(0, 4, 40).astype(np.float32)])
        xi = np.concatenate([xi, rng.normal(0, 2, 40).astype(np.float32)])
        truth = np.array([_mp_logistic(a, b) for a, b in zip(s, xi)])
        got = jax.jit(numerics.logistic_delta)(s, xi)
        h2 = (0.5 * (np.abs(s) - np.abs(xi)).astype(np.float64)) ** 2
    elif family == "student_t":
        base, off = _tangency_grid(rng)
        xi = base.ravel().astype(np.float32)
        r = (base * (1 + off)).ravel().astype(np.float32)
        truth = np.array([_mp_student_t(a, b) for a, b in zip(r, xi)])
        got = jax.jit(lambda r, x: numerics.student_t_delta(r, x, NU, SIGMA))(
            r, xi)
        h2 = (0.5 * (np.abs(r) - np.abs(xi)).astype(np.float64)) ** 2
    else:
        eta0 = rng.normal(0, 2, (60, K))
        direction = rng.normal(0, 1, (60, K))
        scale = rng.choice(OFFSETS, (60, 1)) * 3.0
        eta = (eta0 + scale * direction).astype(np.float32)
        eta0 = eta0.astype(np.float32)
        truth = np.array([_mp_softmax(a, b) for a, b in zip(eta, eta0)])
        pad = lambda a: np.pad(a, ((0, 0), (0, 128 - K)))
        got = jax.jit(lambda e, e0: numerics.softmax_delta_padded(e, e0, K))(
            pad(eta), pad(eta0))
        h2 = np.sum((eta.astype(np.float64) - eta0) ** 2, axis=1)
    assert np.all(truth >= -1e-30)  # B ≤ L: δ ≥ 0 in exact arithmetic
    ok = _within(got, truth, h2)
    assert ok.all(), (np.asarray(got)[~ok], truth[~ok])
    # log L̃ = log expm1(δ) inherits the error as an absolute one (≈ log δ),
    # and δ below the floor is clamped on both sides.
    lt = np.asarray(numerics.log_expm1(jnp.asarray(got)), np.float64)
    lt_true = np.array([float(mpmath.log(mpmath.expm1(max(v, FLOOR))))
                        for v in truth])
    big = truth >= FLOOR
    rel_h = SCALE_TOL * h2[big] / truth[big]
    assert np.all(np.abs(lt[big] - lt_true[big])
                  <= 2 * RTOL + rel_h + 1e-6 * np.abs(lt_true[big]))


def _exact_case(family, rng, n=48, d=5):
    """f32 data whose products θᵀx are exact in f32 (small integers times
    dyadic θ), and tightness at the given offsets from the tangency, so
    the f32 and float64 evaluations see the same inner products."""
    x = rng.integers(-3, 4, (n, d)).astype(np.float32)
    off = rng.choice(OFFSETS[OFFSETS >= 1e-3], n) * rng.choice([-1, 1], n)
    if family == "softmax":
        theta = (rng.integers(-8, 9, (K, d)) / 8).astype(np.float32)
        eta = x @ theta.T
        t = rng.integers(0, K, n).astype(np.int32)
        xi = (eta * (1 + off[:, None] * rng.normal(0, 1, (n, K))))
        h2 = np.sum((eta - xi.astype(np.float32)) ** 2, 1)
        return GLMData(x, t, xi.astype(np.float32)), theta, h2
    theta = (rng.integers(-8, 9, d) / 8).astype(np.float32)
    s = x @ theta
    if family == "logistic":
        t = rng.choice([-1.0, 1.0], n).astype(np.float32)
        xi = (np.abs(s) * (1 + off)).astype(np.float32)
        h2 = (0.5 * (np.abs(s) - np.abs(xi))) ** 2
    else:
        t = (s + rng.integers(-16, 17, n) / 4).astype(np.float32)
        xi = ((t - s) * (1 + off)).astype(np.float32)
        h2 = (0.5 * (np.abs(t - s) - np.abs(xi))) ** 2
    return GLMData(x, t, xi), theta, h2.astype(np.float64)


_BOUNDS = {"logistic": LogisticBound(), "student_t": StudentTBound(NU, SIGMA),
           "softmax": SoftmaxBound()}


@pytest.mark.parametrize("family", ["logistic", "student_t", "softmax"])
def test_fused_delta_matches_float64_log_lik_minus_log_bound(family):
    bound = _BOUNDS[family]
    data, theta, h2 = _exact_case(family, np.random.default_rng(1))
    with jax.enable_x64(True):
        d64 = GLMData(*(jnp.asarray(np.asarray(a, np.int64 if a.dtype.kind
                                               == "i" else np.float64))
                        for a in data[:3]))
        th64 = jnp.asarray(theta, np.float64)
        truth = np.asarray(bound.log_lik(th64, d64)
                           - bound.log_bound(th64, d64))
        assert truth.dtype == np.float64
    d32 = GLMData(*map(jnp.asarray, data[:3]))
    got = np.asarray(bound.fused_delta(jnp.asarray(theta), d32))
    # bounds.delta is the engines' route to the same numbers.
    assert np.array_equal(got, np.asarray(bounds.delta(bound, theta, d32)))
    assert got.dtype == np.float32
    # float64 itself subtracts log L and log B: allow its own rounding.
    ok = _within(got, truth, h2 + 1e-9)
    assert ok.all(), (got[~ok], truth[~ok])
    # Discrimination: the plain f32 difference misses the same bound near
    # the tangency, so this test would catch a return to it.
    plain = np.asarray(bound.log_lik(theta, d32) - bound.log_bound(theta, d32))
    assert not _within(plain, truth, h2 + 1e-9).all()


def test_expm1_matches_jnp_expm1():
    """Kahan's expm1 from exp and log, against jnp.expm1 and float64."""
    mag = 10.0 ** np.linspace(-12, math.log10(80.0), 400)
    d = np.concatenate([[0.0], mag, -mag]).astype(np.float32)
    got = np.asarray(jax.jit(numerics._expm1)(d), np.float64)
    want = np.asarray(jnp.expm1(d), np.float64)
    exact = np.expm1(d.astype(np.float64))
    scale = np.maximum(np.abs(exact), np.finfo(np.float32).tiny)
    assert np.max(np.abs(got - exact) / scale) <= 4 * np.finfo(np.float32).eps
    # jnp.expm1 has its own rounding: two 4-ulp errors apart at most.
    np.testing.assert_allclose(got, want, rtol=8 * np.finfo(np.float32).eps,
                               atol=0)
    # The gradient of the unselected branch stays finite at u = 1.
    g = jax.grad(lambda v: numerics._expm1(v))(jnp.float32(1e-9))
    assert np.isfinite(g) and abs(float(g) - 1.0) < 1e-6


def test_fixed_order_sum_is_capacity_invariant_and_accurate():
    rng = np.random.default_rng(2)
    v = rng.normal(0, 1, 777).astype(np.float32) * 10.0 ** rng.uniform(
        -3, 3, 777).astype(np.float32)
    total = np.asarray(numerics.fixed_order_sum(jnp.asarray(v)))
    # Accurate: a pairwise tree errs at most ⌈log₂ n⌉ roundings of Σ|v|.
    exact = math.fsum(v.astype(np.float64))
    bound = math.ceil(math.log2(v.size)) * np.finfo(np.float32).eps
    assert abs(float(total) - exact) <= bound * np.sum(np.abs(v), dtype=float)
    # Bitwise the same for every zero-padded capacity holding the prefix.
    for cap in (777, 778, 1024, 1500, 4096):
        padded = jnp.asarray(np.pad(v, (0, cap - v.size)))
        assert np.asarray(jax.jit(numerics.fixed_order_sum)(padded)) == total


@pytest.mark.parametrize("family", ["logistic", "student_t"])
def test_recenter_preserves_collapsed_quadratic(family):
    """recenter re-expands the same quadratic about its maximum: in float64
    both forms agree at any θ; in f32 the re-centred form keeps the
    θ-dependent differences the raw form's cancellation loses."""
    from repro.data import logistic_data, robust_data

    key = jax.random.key(3)
    n, d = 200_000, 16
    bound = _BOUNDS[family]
    if family == "logistic":
        data = logistic_data(key, n=n, d=d)
        theta0 = jnp.asarray(np.random.default_rng(4).normal(0, 0.3, d),
                             jnp.float32)
    else:
        data, theta0 = robust_data(key, n=n, d=d)
    data = bound.tighten(theta0, data)
    # θ over a posterior's width (N^-1/2) about the quadratic's maximum.
    ref = bounds.recenter(bound, bound.suffstats(data)).ref
    thetas = ref + 3e-3 * jax.random.normal(jax.random.key(5), (8, d))

    with jax.enable_x64(True):
        d64 = GLMData(*(jnp.asarray(np.asarray(a, np.float64))
                        for a in tuple(data)[:3]))
        raw = bound.suffstats(d64)
        cen = bounds.recenter(bound, raw)
        th64 = jnp.asarray(np.asarray(thetas, np.float64))
        v_raw = np.array([float(bound.collapsed(t, raw)) for t in th64])
        v_cen = np.array([float(bound.collapsed(t, cen)) for t in th64])
        direct = np.array([float(jnp.sum(bound.log_bound(t, d64)))
                           for t in th64])
        grad_at_ref = np.asarray(jax.grad(bound.collapsed)(cen.ref, cen))
    np.testing.assert_allclose(v_cen, v_raw, rtol=1e-12)
    np.testing.assert_allclose(v_cen, direct, rtol=1e-10)
    assert np.max(np.abs(grad_at_ref)) <= 1e-6 * np.max(np.abs(raw.q))

    raw32 = bound.suffstats(data)
    cen32 = bounds.recenter(bound, raw32)
    f32 = lambda st: np.array([float(bound.collapsed(t, st)) for t in thetas])
    err = lambda v: np.max(np.abs((v - v[0]) - (direct - direct[0])))
    # Re-centred, what is left is the rounding of the value itself (c is
    # of order N nats): a difference of two values errs by ≤ 2 ulp.
    two_ulp = 2 * float(np.spacing(np.float32(np.max(np.abs(direct)))))
    assert err(f32(cen32)) <= two_ulp < err(f32(raw32))
