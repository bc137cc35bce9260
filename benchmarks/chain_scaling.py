"""Chain-scaling benchmark: vmap-of-kernels vs chain-batched megakernels.

FlyMC's per-step work is O(touched), but the per-step *fixed* cost (launch
overhead, pipeline fill on ≤capacity workloads) is paid per kernel launch —
and `jax.vmap` over chains launches per chain. With the chain axis as a
leading kernel-grid dimension (``repro.kernels.common.chain_batching``),
all chains coalesce into ONE launch per kernel per step, so the marginal
cost of an extra chain is its compute only, not another fixed cost.

Measures the fused FlyMC step (``backend="pallas"`` + ``z_backend="fused"``)
through ``api.sample`` at ``num_chains ∈ {1, 8, 64}`` under both dispatches
and records, per chain count:

  * ``us_per_step``        — wall µs per iteration (all chains together);
  * ``us_per_step_chain``  — ``us_per_step / num_chains``;
  * ``marginal_us_per_chain`` — ``(us(K) − us(1)) / (K − 1)``: what one
    more chain costs. Sublinear scaling ⇔ this sits strictly below the
    1-chain cost.

Off-TPU both paths run the kernels in Pallas interpret mode — relative
scaling shape, not kernel speed — and the record is flagged
(``interpret: true``), same policy as the other kernel benchmarks.
Results merge into ``BENCH_flymc.json`` under ``chain_scaling``.
"""

from __future__ import annotations

import jax

from benchmarks._util import BENCH_PATH, best_of, merge_write, quickstart_problem
from repro import api
from repro.kernels import common

CHAIN_COUNTS = (1, 8, 64)


def bench(n=512, d=21, capacity=64, iters=20, q_db=0.01, reps=3,
          chain_counts=CHAIN_COUNTS):
    interpret = common.default_interpret()
    tuned, positions = quickstart_problem(
        n, d, num_chains=max(chain_counts)
    )
    key = jax.random.key(3)

    record = {
        "problem": {"name": "quickstart-logistic", "n": n, "d": d,
                    "capacity": capacity, "iters": iters, "q_db": q_db,
                    "backend": "pallas", "z_backend": "fused"},
        "interpret": interpret,
    }
    for mode, batched in (("batched", True), ("vmap", False)):
        per_mode = {}
        with common.chain_batching(batched):
            for k in chain_counts:
                # Fresh algorithm per (mode, K): the dispatch flag is read
                # at trace time and the driver's jit cache keys on it, so a
                # new trace per configuration is what makes the comparison
                # honest.
                alg = api.firefly(
                    tuned, kernel="rwmh", capacity=capacity,
                    cand_capacity=capacity, q_db=q_db, step_size=0.03,
                    backend="pallas", z_backend="fused",
                )
                pos = positions[:k] if k > 1 else positions[0]
                run = lambda: api.sample(
                    alg, key, iters, num_chains=k, chunk_size=iters,
                    init_position=pos,
                )
                # Warm up with the timed call itself: the driver's jit
                # cache keys on chunk_size, so only a same-shape run
                # compiles the executable best_of will measure.
                run()
                wall, out = best_of(run, reps=reps)
                assert out.algorithm.spec.capacity == capacity, (
                    "capacity overflow mid-benchmark: both dispatches would "
                    "time a re-run, not a step"
                )
                us_step = wall * 1e6 / iters
                per_mode[str(k)] = {
                    "us_per_step": us_step,
                    "us_per_step_chain": us_step / k,
                }
        base = per_mode[str(chain_counts[0])]["us_per_step"]
        for k in chain_counts[1:]:
            r = per_mode[str(k)]
            r["marginal_us_per_chain"] = (r["us_per_step"] - base) / (k - 1)
            r["sublinear"] = bool(r["marginal_us_per_chain"] < base)
        record[mode] = per_mode
    return record


def main(quick=False):
    record = bench(
        n=512,
        capacity=64,
        iters=8 if quick else 20,
        reps=2 if quick else 3,
    )
    merge_write({"chain_scaling": record})
    tag = " (interpret)" if record["interpret"] else ""
    print(f"chain scaling{tag}: us/step by num_chains")
    print(f"{'chains':>8} {'batched':>12} {'vmap':>12} "
          f"{'batched marg/chain':>20}")
    for k in CHAIN_COUNTS:
        b = record["batched"][str(k)]
        v = record["vmap"][str(k)]
        marg = b.get("marginal_us_per_chain")
        marg_s = "-" if marg is None else f"{marg:.1f}"
        print(f"{k:>8} {b['us_per_step']:>12.1f} {v['us_per_step']:>12.1f} "
              f"{marg_s:>20}")
    print(f"(wrote {BENCH_PATH.name})")
    return record


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(quick=ap.parse_args().quick)
