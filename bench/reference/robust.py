"""Plain reference of robust Student-t regression under the tangent
Gaussian bound (paper §3.1, §4.3), in NumPy.

    r = t_n - θᵀx_n,  u = (r/σ)²,  f(u) = C - (ν+1)/2 · log(1 + u/ν)
    C = lgamma((ν+1)/2) - lgamma(ν/2) - ½ log(νπ) - log σ
    log L_n = f(u)
    log B_n = f(u₀) + f'(u₀)(u - u₀),  u₀ = (ξ_n/σ)²   (f is convex in u)
    δ_n     = -(ν+1)/2 · (log1p(w) - w),  w = (u - u₀)/(ν + u₀)
    prior     Laplace(0, scale) per coordinate, normalizing constant dropped
    MAP tuning ξ_n = t_n - θ*ᵀx_n
"""

from __future__ import annotations

import math

import numpy as np

from bench.reference.common import Arith, gaussian_posterior_is


def tune(x, t, theta_star, cfg):
    return (np.asarray(t, np.float64)
            - np.asarray(x, np.float64) @ np.asarray(theta_star, np.float64))


def log_prior(theta, cfg, ar: Arith):
    return -ar.total(np.abs(ar.q(theta)), axis=-1) / cfg["prior_scale"]


def _const(cfg):
    nu, sigma = cfg["nu"], cfg["sigma"]
    return (math.lgamma((nu + 1) / 2) - math.lgamma(nu / 2)
            - 0.5 * math.log(nu * math.pi) - math.log(sigma))


def rows(theta, x, t, xi, cfg, ar: Arith):
    """(log L_n, log B_n, δ_n) for the given rows at one θ."""
    nu, sigma = cfg["nu"], cfg["sigma"]
    k = (nu + 1.0) / 2.0
    r = ar.q(ar.q(t) - ar.dot(x, theta))
    u = ar.q(ar.q(r / sigma) ** 2)
    u0 = ar.q(ar.q(ar.q(xi) / sigma) ** 2)
    c = ar.q(_const(cfg))
    log_l = ar.q(c - ar.q(k * ar.q(np.log1p(ar.q(u / nu)))))
    f0 = ar.q(c - ar.q(k * ar.q(np.log1p(ar.q(u0 / nu)))))
    fp0 = ar.q(-k / ar.q(nu + u0))
    log_b = ar.q(f0 + ar.q(fp0 * ar.q(u - u0)))
    w = ar.q(ar.q(u - u0) / ar.q(nu + u0))
    delta = ar.q(-k * ar.q(ar.q(np.log1p(w)) - w))
    return log_l, log_b, delta


def posterior(x, t, cfg, rng):
    """(mean, sd, se of mean, IS ESS) of the posterior by its Laplace
    approximation: least squares, three EM (IRLS) steps for the Student-t
    likelihood, then Newton steps on the exact float64 log posterior until
    a step moves θ by less than 1e-9 (about 1e-6 posterior sd); the
    covariance is the inverse of the observed information there. At
    N = 1.8M the mode lies within about 1e-3 posterior sd of the mean, so
    no importance correction is made (``reference_draws`` 0)."""
    nu, sigma, scale = cfg["nu"], cfg["sigma"], cfg["prior_scale"]
    x = np.asarray(x, np.float64)
    t = np.asarray(t, np.float64)
    buf = np.empty_like(x)

    def weighted_gram(w):
        np.multiply(x, w[:, None], out=buf)
        return buf.T @ x, buf.T

    theta = np.linalg.solve(x.T @ x, x.T @ t)
    for _ in range(3):
        r = t - x @ theta
        gram, xtw = weighted_gram((nu + 1.0) / (nu * sigma**2 + r * r))
        theta = np.linalg.solve(gram, xtw @ t)

    def grad_info(theta):
        r = t - x @ theta
        den = nu * sigma**2 + r * r
        g = x.T @ ((nu + 1.0) * r / den) - np.sign(theta) / scale
        info, _ = weighted_gram((nu + 1.0) * (nu * sigma**2 - r * r) / den**2)
        return g, info

    for _ in range(20):
        g, info = grad_info(theta)
        step = np.linalg.solve(info, g)
        theta = theta + step
        if np.max(np.abs(step)) < 1e-9:
            break
    _, info = grad_info(theta)
    cov = np.linalg.inv(info)
    return gaussian_posterior_is(theta, cov, None, cfg["reference_draws"], rng)
