"""Bayesian GLMs for the paper's three experiments (§4.1–§4.3).

Bundles a collapsible bound, a prior, data and suff-stats into one object,
provides the full-data posterior (the "Regular MCMC" baseline of Table 1),
MAP estimation (for MAP-tuned bounds), and FlyMC spec construction.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import bounds as bounds_lib
from repro.core import flymc, samplers
from repro.core.bounds import GLMData


@dataclasses.dataclass
class GLMModel:
    bound: Any
    log_prior: Callable[[jax.Array], jax.Array]
    data: GLMData
    stats: bounds_lib.CollapsedStats
    theta_shape: tuple

    # ---- construction ------------------------------------------------------

    @classmethod
    def logistic(cls, data: GLMData, prior_scale: float = 1.0, xi: float = 1.5):
        """§4.1: logistic regression, Jaakkola–Jordan bound, Gaussian prior."""
        bound = bounds_lib.LogisticBound()
        data = bound.default_xi(data, xi)
        return cls(
            bound=bound,
            log_prior=partial(bounds_lib.gaussian_log_prior, scale=prior_scale),
            data=data,
            stats=bound.suffstats(data),
            theta_shape=(data.x.shape[1],),
        )

    @classmethod
    def softmax(cls, data: GLMData, n_classes: int, prior_scale: float = 1.0):
        """§4.2: softmax classification, Böhning bound, Gaussian prior."""
        bound = bounds_lib.SoftmaxBound()
        data = bound.default_xi(data, n_classes)
        return cls(
            bound=bound,
            log_prior=partial(bounds_lib.gaussian_log_prior, scale=prior_scale),
            data=data,
            stats=bound.suffstats(data),
            theta_shape=(n_classes, data.x.shape[1]),
        )

    @classmethod
    def robust(
        cls,
        data: GLMData,
        nu: float = 4.0,
        sigma: float = 1.0,
        prior_scale: float = 1.0,
    ):
        """§4.3: robust Student-t regression, tangent bound, Laplace prior."""
        bound = bounds_lib.StudentTBound(nu=nu, sigma=sigma)
        data = bound.default_xi(data)
        return cls(
            bound=bound,
            log_prior=partial(bounds_lib.laplace_log_prior, scale=prior_scale),
            data=data,
            stats=bound.suffstats(data),
            theta_shape=(data.x.shape[1],),
        )

    # ---- densities -----------------------------------------------------------

    def full_log_posterior(
        self, theta: jax.Array, data: GLMData | None = None
    ) -> jax.Array:
        """Exact full-data log posterior (the Regular-MCMC target).

        ``data`` defaults to the model's own rows; passing them lets a jitted
        caller take the dataset as an operand instead of a baked-in constant.
        """
        data = self.data if data is None else data
        return self.log_prior(theta) + jnp.sum(self.bound.log_lik(theta, data))

    def full_logpdf_fn(self, data: GLMData | None = None) -> samplers.LogDensityFn:
        """(lp, aux) wrapper for core.samplers; aux is a dummy scalar."""

        def f(theta):
            lp = self.full_log_posterior(theta, data)
            return lp, jnp.zeros((), theta.dtype)

        return f

    # ---- MAP + bound tuning (paper §3.1 "tight in the right places") --------

    def map_estimate(
        self,
        key: jax.Array,
        steps: int = 500,
        lr: float = 0.05,
        theta0: jax.Array | None = None,
    ) -> jax.Array:
        """Adam ascent on the full-data log posterior (≈ the paper's SGD)."""
        if theta0 is None:
            theta0 = 0.01 * jax.random.normal(key, self.theta_shape)

        def solve(data, theta0):
            grad_fn = jax.grad(lambda th: -self.full_log_posterior(th, data))

            def body(carry, _):
                th, m, v, t = carry
                g = grad_fn(th)
                t = t + 1
                m = 0.9 * m + 0.1 * g
                v = 0.999 * v + 0.001 * g * g
                mh = m / (1.0 - 0.9**t)
                vh = v / (1.0 - 0.999**t)
                th = th - lr * mh / (jnp.sqrt(vh) + 1e-8)
                return (th, m, v, t), None

            zeros = jnp.zeros_like(theta0)
            (theta, _, _, _), _ = jax.lax.scan(
                body, (theta0, zeros, zeros, 0.0), None, length=steps
            )
            return theta

        # The rows go in as an operand: closed over, they would be baked
        # into the executable as a constant (a GB at the paper's N = 1.8M).
        return jax.jit(solve)(self.data, theta0)

    def map_tuned(self, theta_map: jax.Array) -> "GLMModel":
        """Retighten bounds at θ_MAP and rebuild suff-stats (one-time cost)."""
        data = self.bound.tighten(theta_map, self.data)
        return dataclasses.replace(
            self, data=data, stats=self.bound.suffstats(data)
        )

    # ---- repro.api glue ------------------------------------------------------

    def algorithm(self, **kw):
        """FlyMC SamplingAlgorithm over this model (see repro.api.firefly)."""
        from repro import api

        return api.firefly(self, **kw)

    def baseline(self, **kw):
        """Full-data MCMC SamplingAlgorithm (see repro.api.regular_mcmc)."""
        from repro import api

        return api.regular_mcmc(self, **kw)

    # ---- deprecated FlyMC glue (thin wrappers over repro.api) ----------------

    def flymc_spec(
        self,
        kernel: str = "rwmh",
        capacity: int = 1024,
        cand_capacity: int = 1024,
        q_db: float = 0.01,
        mode: str = "implicit",
        **kw,
    ) -> flymc.FlyMCSpec:
        """Deprecated: use ``model.algorithm(...)`` / ``repro.api.firefly``."""
        n = self.data.x.shape[0]
        return flymc.FlyMCSpec(
            bound=self.bound,
            log_prior=self.log_prior,
            kernel=kernel,
            capacity=min(capacity, n),
            cand_capacity=min(cand_capacity, n),
            q_db=q_db,
            mode=mode,
            **kw,
        )

    def init_chain(self, spec, theta0, key, **kw):
        """Deprecated: use ``repro.api.sample`` (it initializes internally)."""
        return flymc.init_chain(spec, self.data, self.stats, theta0, key, **kw)

    def run_chain(self, spec, state, num_iters, **kw):
        """Deprecated: delegates to the repro.api device-resident driver."""
        return flymc.run_chain(
            spec, self.data, self.stats, state, num_iters, **kw
        )


def run_regular_mcmc(
    model: GLMModel,
    theta0: jax.Array,
    key: jax.Array,
    num_iters: int,
    kernel: str = "rwmh",
    step_size: float = 0.05,
    **kernel_kwargs,
):
    """Full-data MCMC baseline (deprecated shim over repro.api.regular_mcmc).

    Returns (samples, lik_queries_per_iter list) like the original host loop,
    but runs on device through the chunked-scan driver.
    """
    from repro import api

    alg = api.regular_mcmc(
        model, kernel=kernel, step_size=step_size,
        kernel_params=tuple(kernel_kwargs.items()),
    )
    trace = api.sample(alg, key, num_iters, init_position=theta0)
    samples = list(jax.device_get(trace.theta[0]))
    queries = [int(q) for q in jax.device_get(trace.stats.lik_queries[0])]
    return samples, queries
