"""Cost of the fault-tolerance machinery (PR 10 hardening).

Three prices are worth knowing, none worth guessing:

  * **Durability** — ``Checkpointer.save`` now fsyncs every leaf file, the
    manifest, the tmp dir and the parent, and records per-file CRC-32s.
    Timed per save on a serve-sized lane tree, alongside ``verify`` (the
    full integrity re-read) and a verified ``restore``.
  * **Sentinel** — every ``GroupEngine.run_chunk`` reduces an all-finite
    flag across the lane trees inside the jitted chunk. Measured as the
    wall-clock delta between two identical service drains (the sentinel is
    always on, so this is service wall time vs the solo-path equivalent —
    reported as supervised-vs-plain service wall ratio with retry/straggler
    machinery active vs default).
  * **Recovery** — one full chaos schedule (seeded faults, cold restarts,
    verified restores) vs the fault-free drain of the same workload: the
    end-to-end overhead of surviving.

Writes ``BENCH_flymc.json`` under ``"faults"``.

    PYTHONPATH=src python -m benchmarks.faults [--quick]
"""

from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from benchmarks._util import job_mix, merge_write

from repro.checkpoint import Checkpointer
from repro.serve import RetryPolicy, Service
from repro.testing import chaos


def _drain(jobs, *, chunk_size, budget, supervised: bool,
           checkpointer=None, checkpoint_every=None):
    kw = {}
    if supervised:
        kw = dict(retry=RetryPolicy(max_retries=2, backoff_s=0.0),
                  straggler_threshold=4.0)
    svc = Service(slot_budget=budget, chunk_size=chunk_size,
                  checkpointer=checkpointer,
                  checkpoint_every=checkpoint_every, **kw)
    t0 = time.perf_counter()
    for j in jobs:
        svc.submit(j)
    svc.run()
    return time.perf_counter() - t0, svc


def _time_checkpoint_cycle(svc: Service, reps: int):
    """Per-op seconds for (durable save, verify, verified restore) on the
    live service's lane tree."""
    ck = svc.checkpointer
    saves, verifies, restores = [], [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        svc.checkpoint(blocking=True)
        saves.append(time.perf_counter() - t0)
        step = ck.latest_step()
        t0 = time.perf_counter()
        problems = ck.verify(step)
        verifies.append(time.perf_counter() - t0)
        assert problems == []
        t0 = time.perf_counter()
        Service.restore(ck, verify=True)
        restores.append(time.perf_counter() - t0)
    return (float(np.median(saves)), float(np.median(verifies)),
            float(np.median(restores)))


def main(quick: bool = False, seed: int = 0) -> dict:
    if quick:
        kw = dict(n=512, d=8, max_samples=64, num_warmup=16)
        chunk_size, budget, reps = 16, 16, 3
        chaos_kw = dict(n=256, max_samples=48, chunk_size=8,
                        checkpoint_every=1)
    else:
        kw = dict(n=2048, d=16, max_samples=256, num_warmup=64)
        chunk_size, budget, reps = 32, 16, 5
        chaos_kw = dict(n=1024, max_samples=128, chunk_size=16,
                        checkpoint_every=1)
    jobs = job_mix(seed, 8, auto_terminate=False, **kw)

    # Warmup compile on identical shapes, then time both drains.
    _drain(job_mix(seed, 8, auto_terminate=False, **kw),
           chunk_size=chunk_size, budget=budget, supervised=False)
    plain_s, _ = _drain(job_mix(seed, 8, auto_terminate=False, **kw),
                        chunk_size=chunk_size, budget=budget,
                        supervised=False)
    sup_s, _ = _drain(jobs, chunk_size=chunk_size, budget=budget,
                      supervised=True)

    # Checkpoint cycle timings on a mid-flight service (live lane trees).
    with tempfile.TemporaryDirectory(prefix="bench_faults_") as d:
        svc = Service(slot_budget=budget, chunk_size=chunk_size,
                      checkpointer=Checkpointer(d))
        for j in job_mix(seed, 8, auto_terminate=False, **kw):
            svc.submit(j)
        svc.step()
        svc.step()
        n_bytes = sum(
            np.asarray(jax.device_get(l)).nbytes
            for eng in svc.scheduler.engines.values()
            for jid in eng.job_ids
            for l in jax.tree.leaves(eng.lane_of(jid))
        )
        save_s, verify_s, restore_s = _time_checkpoint_cycle(svc, reps)

    # End-to-end chaos schedule vs its own fault-free reference.
    with tempfile.TemporaryDirectory(prefix="bench_chaos_") as d:
        t0 = time.perf_counter()
        report = chaos.run_schedule(seed, directory=d, n_faults=4,
                                    **chaos_kw)
        chaos_s = time.perf_counter() - t0

    record = {
        "quick": quick,
        "supervision": {
            "plain_wall_s": round(plain_s, 3),
            "supervised_wall_s": round(sup_s, 3),
            "overhead_frac": round(sup_s / plain_s - 1, 4),
        },
        "checkpoint": {
            "tree_mbytes": round(n_bytes / 1e6, 3),
            "durable_save_s": round(save_s, 4),
            "verify_s": round(verify_s, 4),
            "verified_restore_s": round(restore_s, 4),
        },
        "chaos": {
            "schedule_wall_s": round(chaos_s, 3),
            "fired": [f.kind for f in report.fired],
            "restarts": report.restarts,
            "fallbacks": report.fallbacks,
            "survivors": len(report.survivors),
            "clean_prefixes": len(report.prefix_ok),
        },
    }
    merge_write({"faults": record})
    return record


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    rec = main(quick=args.quick)
    sup = rec["supervision"]
    ck = rec["checkpoint"]
    print(f"supervision overhead: {sup['overhead_frac'] * 100:.2f}% "
          f"({sup['plain_wall_s']}s -> {sup['supervised_wall_s']}s)")
    print(f"checkpoint ({ck['tree_mbytes']} MB): save {ck['durable_save_s']}s"
          f" verify {ck['verify_s']}s restore {ck['verified_restore_s']}s")
    print(f"chaos: {rec['chaos']}")
