"""Record the static-analysis sweep in BENCH_flymc.json.

Not a timing benchmark: the recorded quantities are the COST FINGERPRINTS
of every registered hot-path jit — per-entry-point eqn counts, worst
RNG/cumsum/gather/scatter sizes, closure-constant bytes, and each rule's
pass/xfail status. A cost-discipline regression (an O(N) primitive
sneaking back into a fused step, a dataset baked in as a const) then shows
up in the perf trajectory next to the timing numbers it would eventually
poison. The sharded entry points additionally record their collective
census (kind@axis -> per-step count) and the derived per-device wire-bytes
model, so communication regressions land in the same trajectory.

    PYTHONPATH=src python -m benchmarks.static_analysis
"""

from __future__ import annotations

from benchmarks._util import merge_write


def main(quick: bool = False) -> dict:
    # The sweep only traces (and lowers, for the donation rule); it is
    # already CI-sized, so quick/full record the same thing.
    del quick
    from repro.analysis import registry

    summary = registry.run_registry()
    record = {
        "problem": {"n": registry.N, "d": registry.D,
                    "capacity": registry.CAPACITY,
                    # the forced mesh the sharded entry points trace under
                    # (AbstractMesh: axis names + sizes, no devices)
                    "data_shards": registry._DATA_SHARDS},
        **summary.to_record(),
    }
    merge_write({"static_analysis": record})
    return record


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    rec = main()
    status = "OK" if rec["ok"] else "FAIL"
    print(f"static_analysis: {status} "
          f"({len(rec['entry_points'])} entry points) -> BENCH_flymc.json")
