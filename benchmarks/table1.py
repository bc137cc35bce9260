"""Reproduction of paper Table 1 (three experiments × three algorithms).

For each experiment — logistic regression (MNIST-like, RWMH), softmax
classification (CIFAR-like, MALA), robust regression (OPV-like, slice) — we
run Regular MCMC, untuned FlyMC and MAP-tuned FlyMC on synthetic data with
the paper's (N, D, K) shapes, and report the paper's three columns:

    average likelihood queries per iteration  (implementation-independent cost)
    effective samples per 1000 iterations     (min-ESS over θ coordinates)
    speedup relative to regular MCMC          ((ESS/query) ratio)

``--scale`` shrinks N for CPU-budget runs (default 1.0 = paper size for
MNIST/CIFAR; OPV defaults to N=200k — 1.8M with --full).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core import diagnostics
from repro.data import logistic_data, robust_data, softmax_data
from repro.models.bayes_glm import GLMModel


@dataclasses.dataclass
class AlgoResult:
    name: str
    queries_per_iter: float
    ess_per_1000: float
    speedup: float
    us_per_iter: float


def _finish(trace, burn):
    """Common post-processing: burn, flatten, ESS, queries/iter, µs/iter."""
    s = np.asarray(trace.theta[0])[burn:]
    if s.ndim == 3:  # softmax: flatten classes
        s = s.reshape(s.shape[0], -1)
    ess = diagnostics.ess_per_1000_iters(s[:, : min(10, s.shape[1])])
    q_per_iter = float(np.asarray(trace.stats.lik_queries[0])[burn:].mean())
    return s, ess, q_per_iter


def _run_flymc(model, kernel, theta0, key, iters, burn, q_db, step0):
    cap = capacity_for(model.data.x.shape[0])
    alg = api.firefly(
        model, kernel=kernel, capacity=cap, cand_capacity=cap, q_db=q_db,
        step_size=step0, adapt_target="auto",
    )
    t0 = time.time()
    trace = api.sample(alg, key, iters, init_position=theta0)
    jax.block_until_ready(trace.theta)
    wall = time.time() - t0
    s, ess, q_per_iter = _finish(trace, burn)
    return s, ess, q_per_iter, wall * 1e6 / iters


def _run_regular(model, kernel, theta0, key, iters, burn, step0):
    alg = api.regular_mcmc(
        model, kernel=kernel, step_size=step0, adapt_target="auto"
    )
    t0 = time.time()
    trace = api.sample(alg, key, iters, init_position=theta0)
    jax.block_until_ready(trace.theta)
    wall = time.time() - t0
    s, ess, q_per_iter = _finish(trace, burn)
    return s, ess, q_per_iter, wall * 1e6 / iters


def run_experiment(
    name: str, model: GLMModel, kernel: str, key, iters: int, burn: int,
    step0: float, q_untuned: float, q_tuned: float, map_steps: int = 400,
) -> list[AlgoResult]:
    d_theta = model.theta_shape
    theta0 = jnp.zeros(d_theta)
    k1, k2, k3, k4 = jax.random.split(key, 4)

    _, ess_r, q_r, us_r = _run_regular(model, kernel, theta0, k1, iters, burn, step0)
    base_eff = ess_r / max(q_r, 1.0)
    results = [AlgoResult(f"{name}/regular", q_r, ess_r, 1.0, us_r)]

    _, ess_u, q_u, us_u = _run_flymc(
        model, kernel, theta0, k2, iters, burn, q_untuned, step0
    )
    results.append(
        AlgoResult(
            f"{name}/flymc-untuned", q_u, ess_u,
            (ess_u / max(q_u, 1.0)) / base_eff, us_u,
        )
    )

    theta_map = model.map_estimate(k3, steps=map_steps)
    tuned = model.map_tuned(theta_map)
    _, ess_t, q_t, us_t = _run_flymc(
        tuned, kernel, theta0, k4, iters, burn, q_tuned, step0
    )
    results.append(
        AlgoResult(
            f"{name}/flymc-MAP-tuned", q_t, ess_t,
            (ess_t / max(q_t, 1.0)) / base_eff, us_t,
        )
    )
    return results


@dataclasses.dataclass(frozen=True)
class Problem:
    """One of the paper's three experiments at its published shape."""

    name: str
    n: int  # rows (the paper's N)
    d: int  # features
    kernel: str  # θ-kernel
    step0: float  # initial step size
    q_untuned: float  # q_db for untuned FlyMC
    q_tuned: float  # q_db for MAP-tuned FlyMC
    build: Callable  # (key, n) -> GLMModel on synthetic rows of this shape


def _mnist(key, n):
    data = logistic_data(key, n=n, d=51, separation=2.0)
    return GLMModel.logistic(data, prior_scale=1.0, xi=1.5)


def _cifar(key, n):
    data = softmax_data(key, n=n, d=256, k=3)
    return GLMModel.softmax(data, n_classes=3, prior_scale=1.0)


def _opv(key, n):
    data, _ = robust_data(key, n=n, d=57, nu=4.0)
    return GLMModel.robust(data, nu=4.0, sigma=1.0, prior_scale=1.0)


PROBLEMS = (
    # §4.1 — MNIST 7v9 logistic regression, random-walk MH
    Problem("mnist-logistic-rwmh", 12_214, 51, "rwmh", 0.02, 0.1, 0.01,
            _mnist),
    # §4.2 — CIFAR-3 softmax classification, MALA
    Problem("cifar-softmax-mala", 18_000, 256, "mala", 0.002, 0.1, 0.01,
            _cifar),
    # §4.3 — OPV robust regression, slice sampling
    Problem("opv-robust-slice", 1_800_000, 57, "slice", 0.05, 0.1, 0.01,
            _opv),
)


def capacity_for(n: int) -> int:
    """Bright and candidate buffer capacity the Table-1 runs start from."""
    return max(256, int(0.05 * n))


def table1(scale: float = 1.0, iters: int = 3000, burn: int = 750,
           opv_n: int = 200_000, seed: int = 0) -> list[AlgoResult]:
    key = jax.random.key(seed)
    out: list[AlgoResult] = []
    sizes = (PROBLEMS[0].n, PROBLEMS[1].n, opv_n)
    for p, k, n in zip(PROBLEMS, jax.random.split(key, 3), sizes):
        out += run_experiment(
            p.name, p.build(k, int(n * scale)), p.kernel, k, iters, burn,
            step0=p.step0, q_untuned=p.q_untuned, q_tuned=p.q_tuned,
        )
    return out


def format_results(results: list[AlgoResult]) -> str:
    lines = [
        "| experiment / algorithm | lik. queries/iter | ESS per 1000 iters |"
        " speedup vs regular |",
        "|---|---|---|---|",
    ]
    for r in results:
        lines.append(
            f"| {r.name} | {r.queries_per_iter:,.0f} | {r.ess_per_1000:.2f} |"
            f" {r.speedup:.1f}× |"
        )
    return "\n".join(lines)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--iters", type=int, default=3000)
    ap.add_argument("--opv-n", type=int, default=200_000)
    ap.add_argument("--full", action="store_true", help="OPV at paper 1.8M")
    args = ap.parse_args()
    res = table1(
        scale=args.scale, iters=args.iters,
        opv_n=1_800_000 if args.full else args.opv_n,
    )
    print(format_results(res))
    for r in res:
        print(f"{r.name},{r.us_per_iter:.1f},"
              f"q={r.queries_per_iter:.0f};ess={r.ess_per_1000:.2f};"
              f"speedup={r.speedup:.2f}")
