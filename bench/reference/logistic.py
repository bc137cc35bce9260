"""Plain reference of logistic regression under the Jaakkola–Jordan bound
(paper §3.1, §4.1), in NumPy.

    log L_n = log σ(s),  s = t_n θᵀx_n
    log B_n = a(ξ_n) s² + s/2 + c(ξ_n),  a(ξ) = -tanh(ξ/2)/(4ξ),
              c(ξ) = -a ξ² + ξ/2 - log(1 + e^ξ)      (tight at s = ±ξ)
    prior     N(0, scale² I), normalizing constant dropped
    MAP tuning ξ_n = |θ*ᵀx_n|
"""

from __future__ import annotations

import numpy as np

from bench.reference.common import Arith, gaussian_posterior_is


def tune(x, t, theta_star, cfg):
    return np.abs(np.asarray(x, np.float64) @ np.asarray(theta_star, np.float64))


def log_prior(theta, cfg, ar: Arith):
    return -0.5 * ar.total(ar.q(ar.q(theta) ** 2), axis=-1) / cfg["prior_scale"] ** 2


def _softplus(ar, v):
    # log(1 + e^v), stable
    return ar.q(np.maximum(v, 0.0) + ar.q(np.log1p(ar.q(np.exp(-np.abs(v))))))


def rows(theta, x, t, xi, cfg, ar: Arith):
    """(log L_n, log B_n, δ_n) for the given rows at one θ."""
    s = ar.q(ar.q(t) * ar.dot(x, theta))
    xi = ar.q(xi)
    safe = np.where(np.abs(xi) < 1e-4, 1.0, xi)
    a = np.where(np.abs(xi) < 1e-4, -0.125,
                 ar.q(-ar.q(np.tanh(ar.q(safe / 2.0))) / ar.q(4.0 * safe)))
    a = ar.q(a)
    c = ar.q(ar.q(-a * ar.q(xi * xi)) + ar.q(xi / 2.0) - _softplus(ar, xi))
    log_l = -_softplus(ar, -s)
    log_b = ar.q(ar.q(a * ar.q(s * s)) + ar.q(s / 2.0) + c)
    return log_l, log_b, ar.q(log_l - log_b)


def full_log_post(thetas, x, t, cfg, block=256):
    """Exact log posterior at each row of thetas (M, D), float64."""
    out = []
    for i in range(0, thetas.shape[0], block):
        th = thetas[i:i + block]
        s = (x @ th.T) * t[:, None]
        out.append(-np.logaddexp(0.0, -s).sum(axis=0)
                   - 0.5 * np.sum(th * th, axis=1) / cfg["prior_scale"] ** 2)
    return np.concatenate(out)


def posterior(x, t, cfg, rng):
    """(mean, sd, se of mean, IS ESS) of the exact posterior: Newton to the
    mode in float64, the Laplace Gaussian there, corrected by importance
    sampling with ``cfg["reference_draws"]`` draws."""
    x = np.asarray(x, np.float64)
    t = np.asarray(t, np.float64)
    d = x.shape[1]
    prec0 = 1.0 / cfg["prior_scale"] ** 2
    theta = np.zeros(d)
    for _ in range(100):
        s = t * (x @ theta)
        p = 1.0 / (1.0 + np.exp(s))  # σ(-s)
        grad = x.T @ (t * p) - prec0 * theta
        w = p * (1.0 - p)
        hess = (x * w[:, None]).T @ x + prec0 * np.eye(d)
        step = np.linalg.solve(hess, grad)
        theta = theta + step
        if np.max(np.abs(step)) < 1e-12:
            break
    s = t * (x @ theta)
    p = 1.0 / (1.0 + np.exp(s))
    hess = (x * (p * (1.0 - p))[:, None]).T @ x + prec0 * np.eye(d)
    cov = np.linalg.inv(hess)
    return gaussian_posterior_is(
        theta, cov, lambda th: full_log_post(th, x, t, cfg),
        cfg["reference_draws"], rng)
