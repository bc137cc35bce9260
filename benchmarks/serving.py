"""Continuous batching vs sequential per-job sampling (serve headline).

Workload: the shared 8-job heterogeneous mix (``benchmarks._util.job_mix``
— logistic / 2-chain logistic / softmax / robust / ESS-auto-terminated).
Two ways to drain it:

  * **sequential** — one ``api.sample`` call per job, back to back, each
    running its full ``max_samples`` (the pre-serve workflow);
  * **service** — everything submitted to one ``repro.serve.Service``,
    which packs compatible jobs onto shared lane axes and retires the
    converged ones (batch-means ESS past the policy target) early.

Reported into ``BENCH_flymc.json`` under ``"serving"``: total wall-clock
and jobs/sec for both paths (the speedup ratio is the headline), per-job
latency p50/p95 under the service (all jobs submitted at t=0), mean
chain-slot occupancy, and the chain-steps saved by auto-termination
relative to fixed-length runs. Both paths get one untimed warmup pass so
the comparison measures steady-state sampling, not first-compile.

    PYTHONPATH=src python -m benchmarks.serving [--quick]
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks._util import job_mix, merge_write

from repro import api
from repro.serve import Service
from repro.serve import job as job_lib


def _sequential(jobs, chunk_size):
    t0 = time.perf_counter()
    out = {}
    for job in jobs:
        alg = job_lib.build_algorithm(job)
        tr = api.sample(
            alg, jax.random.key(job.seed), job.policy.max_samples,
            num_chains=job.num_chains, chunk_size=chunk_size,
            collectors=job.collectors,
        )
        out[job.job_id] = tr.results
    jax.block_until_ready([jax.tree.leaves(r) for r in out.values()])
    return time.perf_counter() - t0, out


def _service(jobs, chunk_size, slot_budget):
    svc = Service(slot_budget=slot_budget, chunk_size=chunk_size)
    done_at: dict[str, float] = {}
    t0 = time.perf_counter()
    for job in jobs:
        svc.submit(job)
    occupancy = []
    while svc.active():
        for u in svc.step():
            if u.done:
                done_at[u.job_id] = time.perf_counter() - t0
        occupancy.append(svc.scheduler.slots_used / svc.scheduler.slot_budget)
    wall = time.perf_counter() - t0
    return wall, svc, done_at, occupancy


def main(quick: bool = False, seed: int = 0) -> dict:
    if quick:
        kw = dict(n=512, d=8, max_samples=96, num_warmup=20)
        chunk_size, budget = 32, 16
    else:
        kw = dict(n=4096, d=16, max_samples=512, num_warmup=100)
        chunk_size, budget = 64, 16
    n_jobs = 8

    # Warmup both paths on the identical shapes (compile), then time.
    _sequential(job_mix(seed, n_jobs, **kw), chunk_size)
    _service(job_mix(seed, n_jobs, **kw), chunk_size, budget)

    seq_jobs = job_mix(seed, n_jobs, **kw)
    seq_wall, seq_results = _sequential(seq_jobs, chunk_size)

    srv_jobs = job_mix(seed, n_jobs, **kw)
    srv_wall, svc, done_at, occupancy = _service(srv_jobs, chunk_size, budget)

    lat = np.array([done_at[j.job_id] for j in srv_jobs])
    fixed_steps = sum(j.policy.max_samples * j.num_chains for j in srv_jobs)
    actual_steps = sum(
        svc.result(j.job_id).committed * j.num_chains for j in srv_jobs
    )

    # Exactness spot check: a fixed-length job's service results are bitwise
    # the sequential run's (auto-terminated jobs stop earlier by design).
    exact = True
    for j in srv_jobs:
        if j.policy.target_rhat is not None or j.policy.min_ess is not None:
            continue
        a = jax.tree.leaves(svc.result(j.job_id).results)
        b = jax.tree.leaves(seq_results[j.job_id])
        exact &= all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(a, b)
        )

    record = {
        "n_jobs": n_jobs,
        "chunk_size": chunk_size,
        "slot_budget": budget,
        "max_samples": kw["max_samples"],
        "quick": quick,
        "sequential": {
            "wall_s": round(seq_wall, 3),
            "jobs_per_s": round(n_jobs / seq_wall, 3),
        },
        "service": {
            "wall_s": round(srv_wall, 3),
            "jobs_per_s": round(n_jobs / srv_wall, 3),
            "latency_p50_s": round(float(np.percentile(lat, 50)), 3),
            "latency_p95_s": round(float(np.percentile(lat, 95)), 3),
            "occupancy_mean": round(float(np.mean(occupancy)), 3),
        },
        "speedup": round(seq_wall / srv_wall, 3),
        "auto_termination": {
            "fixed_chain_steps": fixed_steps,
            "actual_chain_steps": actual_steps,
            "steps_saved_frac": round(1 - actual_steps / fixed_steps, 3),
        },
        "fixed_length_results_bitwise_equal": bool(exact),
    }
    merge_write({"serving": record})
    return record


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    rec = main(quick=args.quick)
    print(
        f"serving: sequential {rec['sequential']['wall_s']}s vs service "
        f"{rec['service']['wall_s']}s (speedup {rec['speedup']}x), "
        f"p50 {rec['service']['latency_p50_s']}s "
        f"p95 {rec['service']['latency_p95_s']}s, "
        f"occupancy {rec['service']['occupancy_mean']}, "
        f"auto-termination saved "
        f"{rec['auto_termination']['steps_saved_frac']:.0%} chain-steps, "
        f"bitwise={rec['fixed_length_results_bitwise_equal']}"
    )
