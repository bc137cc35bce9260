"""Arithmetic shared by the plain references (NumPy only)."""

from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


class Arith:
    """Elementwise arithmetic at one precision.

    ``q`` rounds a result to the precision: the identity in float64, a
    round trip through bfloat16 otherwise. ``dot`` is x @ θ with both
    rounded first and the products summed at the accumulation precision;
    ``total`` sums over rows at that precision.
    """

    def __init__(self, prec: str):
        if prec not in ("f64", "bf16"):
            raise ValueError(f"unknown precision {prec!r}")
        self.prec = prec
        self.acc = np.float64 if prec == "f64" else np.float32

    def q(self, a):
        a = np.asarray(a, self.acc)
        if self.prec == "f64":
            return a
        return a.astype(BF16).astype(np.float32)

    def dot(self, x, theta):
        return self.q(self.q(x) @ self.q(theta))

    def total(self, a, axis=None):
        return np.sum(np.asarray(a, self.acc), axis=axis, dtype=self.acc)


def log_expm1(d):
    """log(e^d - 1) for d > 0, accurate for small and large d."""
    d = np.asarray(d, np.float64)
    big = d > 30.0
    return np.where(big, d, np.log(np.expm1(np.where(big, 1.0, d))))


def gaussian_posterior_is(mode, cov, log_post, draws, rng):
    """Mean, sd and the standard error of the mean of the posterior by
    self-normalized importance sampling from N(mode, cov).

    ``log_post(thetas (M, D)) -> (M,)`` is the unnormalized log posterior.
    With ``draws == 0`` the Gaussian itself is returned (se 0).
    """
    sd = np.sqrt(np.diag(cov))
    if draws == 0:
        return mode, sd, np.zeros_like(mode), float("inf")
    chol = np.linalg.cholesky(cov)
    z = rng.standard_normal((draws, mode.size))
    th = mode + z @ chol.T
    log_q = -0.5 * np.sum(z * z, axis=1)
    log_w = log_post(th) - log_q
    w = np.exp(log_w - log_w.max())
    w /= w.sum()
    mean = w @ th
    var = w @ (th - mean) ** 2
    ess = 1.0 / np.sum(w * w)
    return mean, np.sqrt(var), np.sqrt(var / ess), float(ess)
