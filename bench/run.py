"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it checks that JAX sees a TPU with the chips the cell asks
for (it never falls back to the CPU), turns on the persistent compilation
cache, builds the cell from its files (``bench/harness.py``), warms up,
measures for ``--seconds``, checks the window's output against the plain
reference and prints one JSON object as the last line of standard
output. With ``--trace 1`` the window is profiled and the per-layer
metrics are printed instead of the end-to-end ones. Each number compared
is printed with its limit as the last lines of standard error and under
``checks``, the last key of the result. A rehearsal on the CPU calls
``bench.harness.run`` at smaller sizes, as ``bench/tests`` do.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness

    entry = harness.find_cell(args.workload)[0]
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < entry["chips"]:
        print(f"bench: {args.workload} needs {entry['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache = use_compile_cache()
    log = lambda msg: print(f"[bench] {msg}", file=sys.stderr, flush=True)
    log(f"{args.workload} seed={args.seed} device={devices[0].device_kind} "
        f"compile_cache={cache}")

    result = harness.run(args.workload, args.seed, args.seconds,
                         trace=bool(args.trace), t_start=T_START, log=log)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
