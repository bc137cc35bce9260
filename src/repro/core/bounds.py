"""Collapsible likelihood lower bounds (paper §3.1).

A FlyMC bound ``B_n(θ)`` must satisfy two properties:

  1. ``0 < B_n(θ) <= L_n(θ)`` for all θ (exactness requirement);
  2. the *product* ``∏_n B_n(θ)`` must collapse to an O(D²) quadratic form
     computed from sufficient statistics that are built once (and psum-able
     across data shards).

All three of the paper's bounds are scaled exponential-family functions of a
GLM inner product, so their log-products collapse to

    log ∏_n B_n(θ) = θᵀ Q θ + qᵀ θ + c            (vector θ, logistic/robust)
    log ∏_n B_n(θ) = -½ tr(A θ S θᵀ) + tr(θ R) + c (matrix θ, softmax/Böhning)

Implemented bounds:
  * :class:`LogisticBound`  — Jaakkola–Jordan (1997) scaled-Gaussian bound on
    the logistic likelihood, per-datum tightness parameter ξ_n.
  * :class:`SoftmaxBound`   — Böhning (1992) fixed-curvature quadratic bound
    on the softmax log-likelihood, per-datum tangency logits η₀_n.
  * :class:`StudentTBound`  — tangent-in-r² Gaussian bound on the Student-t
    density (log t_ν is convex in r², so the tangent is a global lower bound),
    per-datum tangency residual ξ_n.

Every bound exposes the same surface:

    log_lik(theta, data)          -> per-datum log L_n(θ)
    log_bound(theta, data)        -> per-datum log B_n(θ)
    suffstats(data)               -> CollapsedStats  (one-time, O(N·D²))
    collapsed(theta, stats)       -> Σ_n log B_n(θ)  (O(D²) per θ)
    tighten(theta_map, data)      -> data with per-datum tightness at θ_MAP
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import numerics
from repro.core.numerics import jj_a as _jj_a
from repro.core.numerics import jj_c as _jj_c


# Every product of data and parameters runs at f32: XLA's TPU default
# rounds f32 matmul operands to bf16, which at N = 1.8M quantizes θ to
# about the posterior's own width. (On CPU the precision is f32 either way.)
_HIGHEST = jax.lax.Precision.HIGHEST
_mm = partial(jnp.matmul, precision=_HIGHEST)
_einsum = partial(jnp.einsum, precision=_HIGHEST)


class GLMData(NamedTuple):
    """A batch of GLM data rows.

    x  : (N, D) features
    t  : (N,)   targets — labels in {-1,+1} (logistic), class id (softmax),
                or real-valued response (robust regression)
    xi : per-datum bound-tightness parameter. Shape (N,) for logistic/robust,
         (N, K) tangency logits for softmax.
    x_rows : x in the fused bright-GLM kernel's gather layout, (N, 1, Dp)
         (:func:`repro.kernels.common.gather_layout`), or None. Built once
         per dataset by :func:`with_gather_layout` for ``backend="pallas"``
         so no evaluation re-lays the dataset out.
    """

    x: jax.Array
    t: jax.Array
    xi: jax.Array
    x_rows: jax.Array | None = None


def with_gather_layout(data: GLMData) -> GLMData:
    """``data`` carrying ``x_rows``, built from ``x`` unless already there."""
    if data.x_rows is not None:
        return data
    from repro.kernels.common import gather_layout

    return data._replace(x_rows=gather_layout(data.x))


class CollapsedStats(NamedTuple):
    """Sufficient statistics of a product of quadratic log-bounds.

    For vector-parameter bounds: ``Σ log B = θᵀ·Q·θ + q·θ + c``, or, once
    :func:`recenter` has set ``ref``, ``Δᵀ·Q·Δ + q·Δ + c`` with
    Δ = θ - ref (the same quadratic, expanded about ``ref``).
    For the softmax (matrix θ of shape (K, D)): ``Q`` holds S=Σxxᵀ (D,D),
    ``q`` holds R=Σ x rᵀ (D,K) and the quadratic is -½tr(AθSθᵀ)+tr(θR)+c.
    """

    Q: jax.Array
    q: jax.Array
    c: jax.Array
    ref: jax.Array | None = None


def psum_stats(stats: CollapsedStats, axis_name) -> CollapsedStats:
    """All-reduce suff-stats across data shards (one-time setup collective)."""
    assert stats.ref is None, "psum the raw stats, then recenter"
    psum = lambda s: jax.lax.psum(s, axis_name)
    return CollapsedStats(psum(stats.Q), psum(stats.q), psum(stats.c))


def recenter(bound, stats: CollapsedStats) -> CollapsedStats:
    """``stats`` of a vector-θ quadratic bound, expanded about its maximum.

    At N = 1.8M the terms θᵀQθ, q·θ and c are each ~10⁶ nats and cancel
    to the few nats that vary over the posterior, so f32 evaluation left
    ~8 nats of θ-dependent rounding noise in every FlyMC log density. About
    ref ≈ argmax, Δᵀ·Q·Δ and q·Δ are small and accurate; the expansion is
    exact for any ref (q and c absorb it), so ref need only be close.
    Other bounds' stats are returned unchanged.
    """
    if not isinstance(bound, (LogisticBound, StudentTBound)) or (
        stats.ref is not None
    ):
        return stats
    Q, q, c = stats.Q, stats.q, stats.c
    ref = -0.5 * jnp.linalg.lstsq(Q, q)[0]
    Qr = _mm(Q, ref)
    return CollapsedStats(Q, q + 2.0 * Qr, c + _mm(ref, Qr) + _mm(q, ref), ref)


def _vector_quadratic(theta: jax.Array, stats: CollapsedStats) -> jax.Array:
    """θᵀQθ + q·θ + c, about ``stats.ref`` when set (see :func:`recenter`)."""
    Q, q, c, ref = stats
    d = theta if ref is None else theta - ref
    return _mm(_mm(d, Q), d) + _mm(q, d) + c


# ---------------------------------------------------------------------------
# Bound protocol + registry
# ---------------------------------------------------------------------------


@runtime_checkable
class Bound(Protocol):
    """The surface every collapsible FlyMC bound must implement (§3.1).

    Exactness contract: ``0 < exp(log_bound) <= exp(log_lik)`` everywhere, and
    ``collapsed(θ, suffstats(data)) == Σ_n log_bound(θ, data_n)``.
    """

    name: str

    def log_lik(self, theta: jax.Array, data: GLMData) -> jax.Array: ...

    def log_bound(self, theta: jax.Array, data: GLMData) -> jax.Array: ...

    def suffstats(self, data: GLMData) -> CollapsedStats: ...

    def collapsed(self, theta: jax.Array, stats: CollapsedStats) -> jax.Array: ...

    def tighten(self, theta_map: jax.Array, data: GLMData) -> GLMData: ...

    # Optional fused-delta hook (see FusedBound): bounds that additionally
    # expose ``fused_family``/``fused_kernel_kwargs`` can route θ-updates
    # through the fused Pallas kernel (FlyMCSpec.backend = "pallas").


@runtime_checkable
class FusedBound(Bound, Protocol):
    """A Bound with a fused Pallas δ-kernel (the backend="pallas" hot path).

    ``fused_family`` names the family implemented by
    :mod:`repro.kernels.bright_glm` ("logistic" | "student_t" | "softmax");
    ``fused_kernel_kwargs()`` returns the static scalar parameters the kernel
    needs beyond (x, t, ξ, θ) — e.g. (ν, σ) for the Student-t bound. The hook
    is optional: plain Bounds keep working on the jnp backend, and
    ``FlyMCSpec.backend = "pallas"`` is rejected up front for bounds that
    don't implement it.

    One hook, both hot paths: ``backend="pallas"`` routes the θ-update's
    bright-buffer evaluation AND the z-update's candidate-δ evaluation
    (:func:`repro.core.flymc._candidate_delta`) through the same fused
    kernel, so a bound that declares a family covers every per-datum
    likelihood query a FlyMC step makes.
    """

    fused_family: str

    def fused_kernel_kwargs(self) -> dict: ...

    def fused_delta(self, theta: jax.Array, data: GLMData) -> jax.Array:
        """δ = log L - log B by the kernel's own numerics, in plain jnp."""
        ...


def fused_family_of(bound) -> str | None:
    """The bound's fused-kernel family, or None if it must use the jnp path.

    Guards against an inheritance accident: a subclass that overrides
    ``log_lik``/``log_bound`` but merely *inherits* ``fused_family`` would
    dispatch θ-updates to a fused kernel hard-coding the parent's math while
    z-updates use the overridden jnp math — silently sampling the wrong
    posterior. The hook therefore only counts if no likelihood method is
    overridden below the class that declared it; a subclass that changes the
    math opts back in by re-declaring ``fused_family`` (asserting its
    overrides are kernel-compatible).
    """
    cls = type(bound)
    declarer = next(
        (k for k in cls.__mro__ if "fused_family" in vars(k)), None
    )
    if declarer is None or getattr(cls, "fused_family", None) is None:
        return None
    for meth in ("log_lik", "log_bound"):
        effective = next((k for k in cls.__mro__ if meth in vars(k)), None)
        # The method only counts as vouched-for if the fused_family
        # declaration sits at or below it in the MRO (declarer is a
        # subclass of the provider). Anything else — an override below the
        # declaration OR a sibling mixin ahead of it in the MRO — changes
        # the math without re-asserting kernel compatibility.
        if effective is not None and not issubclass(declarer, effective):
            return None
    return cls.fused_family


def delta(bound, theta: jax.Array, data: GLMData) -> jax.Array:
    """Per-datum δ_n = log L_n(θ) - log B_n(θ), the jnp engines' one route.

    Fused bounds compute it with the fused kernel's formulas
    (:mod:`repro.core.numerics`), which keep δ accurate down to the
    tangency instead of subtracting two nearly equal logs; any other
    bound falls back to ``log_lik - log_bound``.
    """
    if fused_family_of(bound) is not None:
        return bound.fused_delta(theta, data)
    return bound.log_lik(theta, data) - bound.log_bound(theta, data)


BOUND_REGISTRY: dict[str, type] = {}


def register_bound(cls: type, *aliases: str) -> type:
    """Register a Bound class under its ``name`` attribute plus aliases."""
    for key in (cls.name, *aliases):
        BOUND_REGISTRY[key] = cls
    return cls


def get_bound(bound) -> Bound:
    """Resolve a bound: pass through instances, instantiate registered names."""
    if isinstance(bound, str):
        try:
            cls = BOUND_REGISTRY[bound]
        except KeyError:
            raise KeyError(
                f"unknown bound {bound!r}; registered: {sorted(BOUND_REGISTRY)}"
            ) from None
        return cls()
    if not isinstance(bound, Bound):
        raise TypeError(
            f"{type(bound).__name__} does not implement the Bound protocol "
            "(log_lik/log_bound/suffstats/collapsed/tighten)"
        )
    return bound


# ---------------------------------------------------------------------------
# Jaakkola–Jordan bound for logistic regression
# ---------------------------------------------------------------------------


# _jj_a/_jj_c live in repro.core.numerics (shared with the Pallas kernel so
# the two likelihood paths cannot drift); re-imported above under the old
# names for backward compatibility.


class LogisticBound:
    """Jaakkola–Jordan scaled-Gaussian lower bound on logit⁻¹(t·θᵀx).

    log B_n(s) = a(ξ_n)·s² + s/2 + c(ξ_n)   with  s = t_n·θᵀx_n.

    Tight at s = ±ξ_n, so MAP-tuning uses ξ_n = |θ_MAPᵀ x_n|.
    """

    name = "jaakkola-jordan"
    fused_family = "logistic"

    @staticmethod
    def fused_kernel_kwargs() -> dict:
        return {}

    @staticmethod
    def fused_delta(theta: jax.Array, data: GLMData) -> jax.Array:
        return numerics.logistic_delta(data.t * _mm(data.x, theta), data.xi)

    @staticmethod
    def log_lik(theta: jax.Array, data: GLMData) -> jax.Array:
        s = data.t * _mm(data.x, theta)
        return -jax.nn.softplus(-s)

    @staticmethod
    def log_bound(theta: jax.Array, data: GLMData) -> jax.Array:
        s = data.t * _mm(data.x, theta)
        return _jj_a(data.xi) * s * s + 0.5 * s + _jj_c(data.xi)

    @staticmethod
    def suffstats(data: GLMData) -> CollapsedStats:
        a = _jj_a(data.xi)
        # s² = (θᵀx)² (t²=1), so Q = Σ a_n x xᵀ; the linear term keeps t.
        Q = _einsum("n,nd,ne->de", a, data.x, data.x)
        q = 0.5 * _einsum("n,nd->d", data.t.astype(data.x.dtype), data.x)
        c = jnp.sum(_jj_c(data.xi))
        return CollapsedStats(Q, q, c)

    @staticmethod
    def collapsed(theta: jax.Array, stats: CollapsedStats) -> jax.Array:
        return _vector_quadratic(theta, stats)

    @staticmethod
    def tighten(theta_map: jax.Array, data: GLMData) -> GLMData:
        return data._replace(xi=jnp.abs(_mm(data.x, theta_map)))

    @staticmethod
    def default_xi(data: GLMData, xi: float = 1.5) -> GLMData:
        return data._replace(xi=jnp.full(data.x.shape[0], xi, data.x.dtype))


# ---------------------------------------------------------------------------
# Böhning bound for softmax classification
# ---------------------------------------------------------------------------


def _a_mul(v: jax.Array) -> jax.Array:
    """Apply Böhning curvature A = ½(I - 𝟙𝟙ᵀ/K) along the last axis."""
    return 0.5 * (v - jnp.mean(v, axis=-1, keepdims=True))


def _softmax_log_lik_eta(eta: jax.Array, t: jax.Array) -> jax.Array:
    """log softmax(η)[t] for per-row class ids t."""
    return jnp.take_along_axis(
        jax.nn.log_softmax(eta, axis=-1), t[..., None], axis=-1
    )[..., 0]


class SoftmaxBound:
    """Böhning (1992) quadratic lower bound for the softmax likelihood.

    θ is (K, D); per-datum logits η_n = θ x_n. With tangency logits η₀_n
    (= data.xi, shape (N, K)):

        log B_n = log L_n(η₀) + g_nᵀ(η-η₀) - ½(η-η₀)ᵀ A (η-η₀)
        g_n = e_{t_n} - softmax(η₀_n),   A = ½(I - 𝟙𝟙ᵀ/K)

    A ⪰ H(η) for every η (Böhning), so B_n ≤ L_n globally, and A is constant,
    which makes the product collapse: S = Σ x xᵀ and R = Σ x r_nᵀ with
    r_n = g_n + A η₀_n.
    """

    name = "bohning"
    fused_family = "softmax"

    @staticmethod
    def fused_kernel_kwargs() -> dict:
        return {}

    @staticmethod
    def fused_delta(theta: jax.Array, data: GLMData) -> jax.Array:
        eta = _mm(data.x, theta.T)  # (N, K)
        return numerics.softmax_delta_padded(eta, data.xi, eta.shape[-1])

    @staticmethod
    def log_lik(theta: jax.Array, data: GLMData) -> jax.Array:
        eta = _mm(data.x, theta.T)  # (N, K)
        return _softmax_log_lik_eta(eta, data.t)

    @staticmethod
    def log_bound(theta: jax.Array, data: GLMData) -> jax.Array:
        eta = _mm(data.x, theta.T)
        eta0 = data.xi
        K = eta.shape[-1]
        g = jax.nn.one_hot(data.t, K, dtype=eta.dtype) - jax.nn.softmax(eta0)
        d = eta - eta0
        quad = jnp.sum(d * _a_mul(d), axis=-1)
        return (
            _softmax_log_lik_eta(eta0, data.t)
            + jnp.sum(g * d, axis=-1)
            - 0.5 * quad
        )

    @staticmethod
    def suffstats(data: GLMData) -> CollapsedStats:
        x, t, eta0 = data.x, data.t, data.xi
        K = eta0.shape[-1]
        g = jax.nn.one_hot(t, K, dtype=x.dtype) - jax.nn.softmax(eta0)
        r = g + _a_mul(eta0)  # (N, K)
        S = _einsum("nd,ne->de", x, x)  # (D, D)
        R = _einsum("nd,nk->dk", x, r)  # (D, K)
        c = jnp.sum(
            _softmax_log_lik_eta(eta0, t)
            - jnp.sum(g * eta0, axis=-1)
            - 0.5 * jnp.sum(eta0 * _a_mul(eta0), axis=-1)
        )
        return CollapsedStats(S, R, c)

    @staticmethod
    def collapsed(theta: jax.Array, stats: CollapsedStats) -> jax.Array:
        S, R, c, _ = stats
        quad = jnp.sum(_mm(_a_mul(theta.T).T, S) * theta)  # tr(AθSθᵀ)
        lin = jnp.sum(theta.T * R)  # tr(θR)
        return -0.5 * quad + lin + c

    @staticmethod
    def tighten(theta_map: jax.Array, data: GLMData) -> GLMData:
        return data._replace(xi=_mm(data.x, theta_map.T))

    @staticmethod
    def default_xi(data: GLMData, n_classes: int) -> GLMData:
        return data._replace(
            xi=jnp.zeros((data.x.shape[0], n_classes), data.x.dtype)
        )


# ---------------------------------------------------------------------------
# Gaussian bound for Student-t robust regression
# ---------------------------------------------------------------------------


class StudentTBound:
    """Tangent-in-r² Gaussian lower bound on the Student-t likelihood.

    With z = (t_n - θᵀx_n)/σ and u = z², the log-density
    f(u) = const - ((ν+1)/2)·log(1 + u/ν) is convex in u, so its tangent at
    u₀ = (ξ/σ)² is a global lower bound — a scaled Gaussian in the residual:

        log B_n(z) = f(u₀) + f'(u₀)·(z² - u₀),  f'(u₀) = -((ν+1)/2)/(ν+u₀).

    Tight at z = ±ξ/σ; MAP-tuning: ξ_n = t_n - θ_MAPᵀ x_n.
    """

    name = "student-t-tangent"
    fused_family = "student_t"

    def __init__(self, nu: float = 4.0, sigma: float = 1.0):
        self.nu = float(nu)
        self.sigma = float(sigma)

    def fused_kernel_kwargs(self) -> dict:
        return {"nu": self.nu, "sigma": self.sigma}

    def fused_delta(self, theta: jax.Array, data: GLMData) -> jax.Array:
        return numerics.student_t_delta(
            data.t - _mm(data.x, theta), data.xi, self.nu, self.sigma
        )

    def _log_t_const(self, dtype) -> jax.Array:
        nu = self.nu
        return jnp.asarray(
            jax.scipy.special.gammaln((nu + 1.0) / 2.0)
            - jax.scipy.special.gammaln(nu / 2.0)
            - 0.5 * jnp.log(nu * jnp.pi)
            - jnp.log(self.sigma),
            dtype,
        )

    def _f(self, u: jax.Array) -> jax.Array:
        return self._log_t_const(u.dtype) - ((self.nu + 1.0) / 2.0) * jnp.log1p(
            u / self.nu
        )

    def _fprime(self, u: jax.Array) -> jax.Array:
        return -((self.nu + 1.0) / 2.0) / (self.nu + u)

    def log_lik(self, theta: jax.Array, data: GLMData) -> jax.Array:
        z = (data.t - _mm(data.x, theta)) / self.sigma
        return self._f(z * z)

    def log_bound(self, theta: jax.Array, data: GLMData) -> jax.Array:
        z = (data.t - _mm(data.x, theta)) / self.sigma
        u0 = (data.xi / self.sigma) ** 2
        return self._f(u0) + self._fprime(u0) * (z * z - u0)

    def suffstats(self, data: GLMData) -> CollapsedStats:
        x, y = data.x, data.t
        u0 = (data.xi / self.sigma) ** 2
        A = self._fprime(u0) / (self.sigma**2)  # coefficient of r² (negative)
        Q = _einsum("n,nd,ne->de", A, x, x)
        q = -2.0 * _einsum("n,n,nd->d", A, y, x)
        c = jnp.sum(A * y * y) + jnp.sum(self._f(u0) - self._fprime(u0) * u0)
        return CollapsedStats(Q, q, c)

    @staticmethod
    def collapsed(theta: jax.Array, stats: CollapsedStats) -> jax.Array:
        return _vector_quadratic(theta, stats)

    def tighten(self, theta_map: jax.Array, data: GLMData) -> GLMData:
        return data._replace(xi=data.t - _mm(data.x, theta_map))

    @staticmethod
    def default_xi(data: GLMData) -> GLMData:
        return data._replace(xi=jnp.zeros(data.x.shape[0], data.x.dtype))


# ---------------------------------------------------------------------------
# Priors
# ---------------------------------------------------------------------------


register_bound(LogisticBound, "logistic")
register_bound(SoftmaxBound, "softmax")
register_bound(StudentTBound, "student-t", "robust")


def gaussian_log_prior(theta: jax.Array, scale: float) -> jax.Array:
    """Isotropic Gaussian prior (normalization constant dropped)."""
    return -0.5 * jnp.sum(jnp.square(theta)) / (scale**2)


def laplace_log_prior(theta: jax.Array, scale: float) -> jax.Array:
    """Sparsity-inducing Laplace prior (paper §4.3)."""
    return -jnp.sum(jnp.abs(theta)) / scale
