"""Effective sample size: Geyer's initial monotone positive sequence.

A copy of the estimator in ``repro.core.diagnostics`` kept with the
benchmark, so that a change to the program cannot change how its output
is scored. Vectorized over coordinates: ``(n, D)`` in, ``(D,)`` out.
"""

from __future__ import annotations

import numpy as np


def _autocovariance(x):
    n = x.shape[0]
    xc = x - x.mean(axis=0)
    size = 1 << (2 * n - 1).bit_length()
    f = np.fft.rfft(xc, size, axis=0)
    return np.fft.irfft(f * np.conj(f), size, axis=0)[:n].real / n


def taus(x):
    """Integrated autocorrelation time per coordinate of an (n, D) chain.

    A coordinate that never moves (or n < 4) reports τ = n, one effective
    sample.
    """
    x = np.asarray(x, np.float64)
    n, d = x.shape
    fallback = np.full(d, float(n))
    if n < 4:
        return fallback
    degenerate = np.all(np.abs(x - x[0]) <= 1e-8 + 1e-5 * np.abs(x[0]), axis=0)
    acov = _autocovariance(x)
    ok = ~degenerate & (acov[0] > 0)
    if not ok.any():
        return fallback
    rho = acov / np.where(acov[0] > 0, acov[0], 1.0)
    tau = np.zeros(d)
    prev = np.full(d, np.inf)
    active = ok.copy()
    for k in range((rho.shape[0] - 1) // 2):
        if not active.any():
            break
        gamma = rho[2 * k] + rho[2 * k + 1]
        active &= gamma > 0
        gamma = np.minimum(gamma, prev)  # monotone decrease
        prev = np.where(active, gamma, prev)
        tau = np.where(active, tau + 2.0 * gamma, tau)
    return np.where(ok, np.maximum(tau - 1.0, 1.0), fallback)


def ess_per_coord(draws):
    """ESS of each coordinate, summed over chains, of (chains, n, D) draws."""
    draws = np.asarray(draws, np.float64)
    draws = draws.reshape(draws.shape[0], draws.shape[1], -1)
    n = draws.shape[1]
    return sum(n / taus(chain) for chain in draws)
