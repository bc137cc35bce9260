"""Plain reference of softmax classification under the Böhning bound
(paper §3.1, §4.2), in NumPy.

θ is (K, D), K = ``cfg["classes"]``; it arrives flat, class by class.

    η_n = θ x_n                                   (K logits)
    log L_n = log softmax(η_n)[t_n]
    log B_n = log L_n(η₀_n) + g_nᵀ(η_n - η₀_n) - ½(η_n - η₀_n)ᵀA(η_n - η₀_n)
              g_n = e_{t_n} - softmax(η₀_n),  A = ½(I - 𝟙𝟙ᵀ/K)
    δ_n     = log L_n - log B_n
    prior     N(0, scale² I), normalizing constant dropped
    MAP tuning η₀_n = θ* x_n                      (tight at θ = θ*)

Adding one vector to every class's θ changes no η_n's softmax, so the
likelihood cannot see θ̄ = mean_k θ_k and its posterior is the prior's:
``coords`` are the class contrasts θ_k - θ̄, which the data determine.
"""

from __future__ import annotations

import numpy as np

from bench.reference.common import Arith


def _classes(theta, cfg):
    """θ flat (..., P) -> (..., K, D)."""
    theta = np.asarray(theta)
    k = cfg["classes"]
    return theta.reshape(*theta.shape[:-1], k, theta.shape[-1] // k)


def coords(theta, cfg):
    th = _classes(np.asarray(theta, np.float64), cfg)
    c = th - th.mean(axis=-2, keepdims=True)
    return c.reshape(*c.shape[:-2], -1)


def tune(x, t, theta_star, cfg):
    th = _classes(np.asarray(theta_star, np.float64), cfg)
    return np.asarray(x, np.float64) @ th.T  # (N, K)


def log_prior(theta, cfg, ar: Arith):
    return -0.5 * ar.total(ar.q(ar.q(theta) ** 2), axis=-1) / cfg["prior_scale"] ** 2


def _lse(ar, v, axis):
    m = np.max(v, axis=axis, keepdims=True)
    s = ar.total(ar.q(np.exp(ar.q(v - m))), axis=axis)
    return ar.q(np.squeeze(m, axis) + ar.q(np.log(s)))


def rows(theta, x, t, xi, cfg, ar: Arith):
    """(log L_n, log B_n, δ_n) for the given rows at one θ (P,), or at M
    θs (P, M): each (N,) or (N, M). ``t`` holds N class ids and ``xi`` N
    tangency logit vectors, in any shape of N or N·K values ((N,), (N, 1);
    (N, K), (N, 1, K))."""
    k = cfg["classes"]
    x = np.asarray(x)
    n, d = x.shape
    theta = np.asarray(theta)
    one = theta.ndim == 1
    th = theta.reshape(k, d, -1)  # (K, D, M)
    m = th.shape[-1]
    eta = ar.dot(x, th.transpose(1, 0, 2).reshape(d, k * m)).reshape(n, k, m)
    t = np.asarray(t).reshape(n).astype(np.int64)
    eta0 = ar.q(np.asarray(xi).reshape(n, k))[:, :, None]  # (N, K, 1)
    pick = lambda v: np.take_along_axis(v, t[:, None, None], axis=1)[:, 0]

    log_l = ar.q(pick(eta) - _lse(ar, eta, 1))  # (N, M)
    log_l0 = ar.q(pick(eta0) - _lse(ar, eta0, 1))  # (N, 1)
    p0 = ar.q(np.exp(ar.q(eta0 - _lse(ar, eta0, 1)[:, None])))  # (N, K, 1)
    dd = ar.q(eta - eta0)  # (N, K, M)
    lin = ar.q(pick(dd) - ar.total(ar.q(p0 * dd), axis=1))  # g_nᵀd
    dc = ar.q(dd - ar.q(ar.total(dd, axis=1) / k)[:, None])
    half_quad = ar.q(0.25 * ar.total(ar.q(dc * dc), axis=1))  # ½ dᵀAd
    log_b = ar.q(ar.q(log_l0 + lin) - half_quad)
    delta = ar.q(log_l - log_b)
    if one:
        return log_l[:, 0], log_b[:, 0], delta[:, 0]
    return log_l, log_b, delta


def _probs(x, theta):
    eta = x @ theta.T
    eta -= eta.max(axis=1, keepdims=True)
    p = np.exp(eta)
    return p / p.sum(axis=1, keepdims=True)


def full_log_post(thetas, x, t, cfg, block=256):
    """Exact log posterior at each row of thetas (M, P), float64."""
    k, (n, d) = cfg["classes"], x.shape
    out = []
    for i in range(0, thetas.shape[0], block):
        th = thetas[i:i + block]
        eta = (x @ th.reshape(-1, d).T).reshape(n, -1, k)  # (N, M, K)
        m = eta.max(axis=2, keepdims=True)
        lse = m[..., 0] + np.log(np.exp(eta - m).sum(axis=2))
        log_l = np.take_along_axis(eta, t[:, None, None], axis=2)[..., 0] - lse
        out.append(log_l.sum(axis=0)
                   - 0.5 * np.sum(th * th, axis=1) / cfg["prior_scale"] ** 2)
    return np.concatenate(out)


def posterior(x, t, cfg, rng):
    """(mean, sd, se of mean, IS ESS) in ``coords`` of the exact posterior:
    Newton in float64 to the mode, with the full (K·D)² Hessian from the
    K² blocks XᵀW_kl X (W_kl = diag(p_k (1[k = l] - p_l))), and the
    Laplace Gaussian there, corrected by importance sampling with
    ``cfg["reference_draws"]`` draws (0: none)."""
    x = np.asarray(x, np.float64)
    t = np.asarray(t).astype(np.int64)
    k, (n, d) = cfg["classes"], x.shape
    prec0 = 1.0 / cfg["prior_scale"] ** 2
    onehot = np.eye(k)[t]
    buf = np.empty_like(x)

    def hessian(p):
        h = np.empty((k, d, k, d))
        for a in range(k):
            for b in range(a, k):
                w = p[:, a] * ((a == b) - p[:, b])
                np.multiply(x, w[:, None], out=buf)
                h[a, :, b, :] = buf.T @ x
                h[b, :, a, :] = h[a, :, b, :].T
        h = h.reshape(k * d, k * d)
        h[np.diag_indices_from(h)] += prec0
        return h

    theta = np.zeros((k, d))
    for _ in range(50):
        p = _probs(x, theta)
        grad = (onehot - p).T @ x - prec0 * theta
        step = np.linalg.solve(hessian(p), grad.reshape(-1)).reshape(k, d)
        theta = theta + step
        if np.max(np.abs(step)) < 1e-10:
            break
    cov = np.linalg.inv(hessian(_probs(x, theta)))
    # The contrasts are linear in θ: C = (I - 𝟙𝟙ᵀ/K) ⊗ I_D.
    c = np.kron(np.eye(k) - 1.0 / k, np.eye(d))
    mode = theta.reshape(-1)
    draws = cfg["reference_draws"]
    if draws == 0:
        return (c @ mode, np.sqrt(np.diag(c @ cov @ c.T)), np.zeros(k * d),
                float("inf"))
    z = rng.standard_normal((draws, k * d))
    th = mode + z @ np.linalg.cholesky(cov).T
    log_w = full_log_post(th, x, t, cfg) + 0.5 * np.sum(z * z, axis=1)
    w = np.exp(log_w - log_w.max())
    w /= w.sum()
    tc = th @ c.T
    mean = w @ tc
    var = w @ (tc - mean) ** 2
    ess = 1.0 / np.sum(w * w)
    return mean, np.sqrt(var), np.sqrt(var / ess), float(ess)
