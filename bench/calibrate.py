"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 [--control-seeds 1,2] [--trace-seeds 3] \
        [--faults frozen,half,shift,z_frozen --fault-seeds 4,5,6] \
        [--fault-seconds <s>] --out <readings.jsonl>

In one process (the chip belongs to one), each seed is a whole run of the
cell through ``harness.run`` at the cell's own size: the program's numbers,
on the control seeds also the control's at the same states (the reference
one precision down in the program's place), and on the trace seeds with
the window traced as ``--trace 1`` traces it. Each fault of
``harness.Fault`` is then planted under the timed path on each fault seed.
One JSON line per run goes to ``--out`` and to standard output. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--fault-seconds", type=float, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness
    from repro.api import driver
    from repro.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    use_compile_cache()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    log = lambda msg: print(f"[calibrate] {msg}", file=sys.stderr,
                            flush=True)
    ints = lambda text: [int(s) for s in text.split(",") if s]
    controls, traces = set(ints(args.control_seeds)), set(ints(args.trace_seeds))
    plan = [(s, None) for s in ints(args.seeds)] + [
        (s, f) for f in args.faults.split(",") if f
        for s in ints(args.fault_seeds)]
    with open(out, "a") as f:
        for seed, fault in plan:
            t0 = time.perf_counter()
            seconds = (args.fault_seconds or args.seconds) if fault else (
                args.seconds)
            res = harness.run(
                args.workload, seed, seconds, t_start=t0,
                trace=fault is None and seed in traces,
                control=fault is None and seed in controls,
                fault=harness.Fault(fault) if fault else None, log=log)
            row = {"workload": args.workload, "seed": seed, "fault": fault,
                   "run_s": time.perf_counter() - t0, **res}
            line = json.dumps(row)
            f.write(line + "\n")
            f.flush()
            print(line, flush=True)
            # Each run's algorithm holds its rows through the driver's jit
            # cache; drop them before the next run builds its own.
            driver._JIT_CACHE.clear()
            jax.clear_caches()
            gc.collect()
    log(f"total {time.perf_counter() - T_START:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
