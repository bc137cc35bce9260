"""Streaming-observable benchmark: collectors-only vs dense FullTrace.

Measures the quickstart problem through ``api.sample`` two ways on the
identical chain (same keys, same algorithm):

  * ``full_trace`` — the default path: dense θ trajectory + per-step stats
    materialized (memory O(iterations));
  * ``streaming`` — OnlineMoments + RHat + BatchMeansESS + QueryBudget
    collectors only: constant memory regardless of iteration count.

Records ``bytes_materialized`` (trace buffers vs collector carries) and the
µs/step collector overhead under the ``collectors`` key of
``BENCH_flymc.json`` (merge-write: other benchmarks own sibling keys).
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks._util import BENCH_PATH, best_of, merge_write, quickstart_problem
from repro import api


def _tree_bytes(tree) -> int:
    return int(sum(np.asarray(l).nbytes for l in jax.tree.leaves(tree)))


def bench(n=5000, d=21, iters=2000, chunk_size=256, q_db=0.01):
    tuned = quickstart_problem(n, d)
    # Capacity sized so the bright set never overflows mid-run: both paths
    # then execute the identical chain and the deltas are pure output-path
    # cost, not capacity-growth recompiles.
    alg = api.firefly(
        tuned, kernel="rwmh", capacity=1024, cand_capacity=1024, q_db=q_db,
        step_size=0.03, adapt_target="auto",
    )
    key = jax.random.key(3)
    stream_colls = {
        "moments": api.OnlineMoments(),
        "rhat": api.RHat(),
        "ess": api.BatchMeansESS(),
        "queries": api.QueryBudget(),
    }

    run_full = lambda: api.sample(alg, key, iters, chunk_size=chunk_size)
    run_stream = lambda: api.sample(
        alg, key, iters, chunk_size=chunk_size, collectors=stream_colls
    )
    trace_full = run_full()   # warm-up / compile (and the bytes sample)
    trace_stream = run_stream()

    def us_per_step(fn):
        wall, _ = best_of(fn)
        return wall * 1e6 / iters

    us_full = us_per_step(lambda: run_full().final_state)
    us_stream = us_per_step(lambda: run_stream().final_state)

    # Bytes the output path materializes: dense buffers vs collector carries.
    bytes_full = _tree_bytes(trace_full.theta) + _tree_bytes(trace_full.stats)
    state = trace_full.final_state
    pos_struct, stats_struct = alg.output_structs(state)
    carries = {
        name: col.init(iters, pos_struct, stats_struct)
        for name, col in stream_colls.items()
    }
    bytes_stream = _tree_bytes(carries)

    record = {
        "collectors": {
            "problem": {"name": "quickstart-logistic", "n": n, "d": d,
                        "kernel": "rwmh", "iters": iters, "q_db": q_db},
            "full_trace": {
                "us_per_step": us_full,
                "bytes_materialized": bytes_full,
            },
            "streaming": {
                "collectors": sorted(stream_colls),
                "us_per_step": us_stream,
                "bytes_materialized": bytes_stream,
            },
            # per-step cost of streaming the reductions instead of storing
            # the trajectory (negative: collectors are cheaper than the
            # dense buffer writes + host concat)
            "overhead_us_per_step": us_stream - us_full,
            "bytes_ratio": bytes_full / max(bytes_stream, 1),
            "rhat_streamed": float(trace_stream.results["rhat"]["r_hat"]),
        }
    }
    return record


def main(quick=False):
    record = bench(
        n=1000 if quick else 5000, iters=400 if quick else 2000
    )
    merge_write(record)
    rec = record["collectors"]
    full, stream = rec["full_trace"], rec["streaming"]
    print(f"full trace:  {full['us_per_step']:8.1f} us/step  "
          f"{full['bytes_materialized']:>12,} bytes materialized")
    print(f"streaming:   {stream['us_per_step']:8.1f} us/step  "
          f"{stream['bytes_materialized']:>12,} bytes materialized "
          f"({', '.join(stream['collectors'])})")
    print(f"collector overhead: {rec['overhead_us_per_step']:+.1f} us/step; "
          f"bytes ratio {rec['bytes_ratio']:,.0f}x "
          f"(wrote {BENCH_PATH.name})")
    return record


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(quick=ap.parse_args().quick)
