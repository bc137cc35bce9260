"""Run cells through the harness and print where the time went, by phase.

    python3 bench/phases.py --workload <cell> --seeds <n>[,<n>...] --seconds <s> --trace <0|1>

One process on the chip runs each seed in turn through
``bench.harness.run``, as ``bench/run.py`` does. It prints each run's
result line with what the program's own instrumentation gives
(``bench/scopes.py``):

- ``window.driver``: the driver's counters over the window (the difference
  of ``ChunkEvent.driver`` between the window's first and last boundary),
  the seconds the profiler's start and stop took inside the window
  (``profiler_s``, 0 untraced) and its longest boundary-to-boundary
  stretch, split into the overflow wait and the host's work; and
  ``driver.host_share``, the driver's host work as a share of the window,
  both without ``profiler_s``;
- with ``--trace 1``, ``breakdown.scopes`` (device seconds per named scope
  of the traced window, ``(none)`` for ops under no scope),
  ``breakdown.scopes_inherited`` (the part of each whose scope was
  inferred, not named by the op's own metadata), ``device.own_none_share``
  (% of busy time that no op's own metadata names: ``(none)`` plus the
  inferred part), ``breakdown.idle_by_span`` (idle seconds per innermost
  ``repro.*`` or ``bench.*`` host span), ``breakdown.programs_without_hlo``
  (programs whose ops the profile cannot place, counted under
  ``(none)``), the per-layer numbers
  ``step.theta_us``, ``step.z_us`` and ``step.flips_us``, and the traced
  stretch's ``traced_chain_iters_per_s``.

The harness is used unchanged: the trace is reduced by a wrapper around
``bench.trace.reduce_trace`` before the harness deletes it, and the
boundaries are read by a wrapper around the window's ``on_chunk`` hook.
This runner goes once ``bench/harness.py`` reads the scopes and counters
itself.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class Boundaries:
    """Wraps the window's ``on_chunk`` hook and keeps, at each boundary,
    the host clock on arrival, the driver's counters and the seconds the
    hook took if it started or stopped the profiler."""

    def __init__(self, hook):
        self.hook = hook
        self.marks = []  # [(perf_counter, DriverCounters snapshot)]
        self.profiler = []  # [seconds of the hook] per mark, or 0.0

    def __call__(self, event):
        now = time.perf_counter()
        tracing = getattr(self.hook, "tracing", None)
        stop = self.hook(event)
        toggled = getattr(self.hook, "tracing", None) is not tracing
        driver = getattr(event, "driver", None)
        if driver is not None:
            self.marks.append((now, driver))
            self.profiler.append(time.perf_counter() - now if toggled
                                 else 0.0)
        return stop

    def window(self):
        """The window's counter differences, the seconds of the hooks in
        it that started or stopped the profiler (``profiler_s``), and its
        longest stretch between two boundaries, split into wait and host
        parts; None where the program gives no counters.

        The counters of a boundary are taken before its hook runs, so the
        window holds the hooks of every boundary but its last.
        """
        from bench import scopes

        if len(self.marks) < 2:
            return None
        out = scopes.counter_delta(self.marks[0][1], self.marks[-1][1])
        out["profiler_s"] = sum(self.profiler[:-1])
        steps = [(t1 - t0, scopes.counter_delta(a, b))
                 for (t0, a), (t1, b) in zip(self.marks, self.marks[1:])]
        longest, d = max(steps, key=lambda s: s[0])
        out["longest_boundary_s"] = longest
        out["longest_wait_s"] = d["wait_s"]
        out["longest_host_s"] = sum(d[k] for k in scopes.HOST_PARTS)
        return out


@contextlib.contextmanager
def instrumented(trace):
    """Wrap the harness's trace reduction and window hook for one run;
    yields a dict that receives the scope reduction and the hook, and
    raises where a wrapper was never reached (a renamed harness call)."""
    from bench import harness, scopes
    from bench import trace as trace_lib
    from repro import api

    got = {}
    reduce_trace, sample = trace_lib.reduce_trace, api.sample

    def reduce_with_scopes(trace_dir, kernels=()):
        per, first = reduce_trace(trace_dir, kernels)
        got["scopes"] = scopes.reduce_trace(trace_dir)
        return per, first

    def sample_with_boundaries(*args, on_chunk=None, **kw):
        if isinstance(on_chunk, harness.Window):
            on_chunk = got["boundaries"] = Boundaries(on_chunk)
        return sample(*args, on_chunk=on_chunk, **kw)

    trace_lib.reduce_trace, api.sample = reduce_with_scopes, sample_with_boundaries
    try:
        yield got
    finally:
        trace_lib.reduce_trace, api.sample = reduce_trace, sample
    missing = [k for k in ("boundaries",) + (("scopes",) if trace else ())
               if k not in got]
    if missing:
        raise RuntimeError(f"phases: the harness never reached the wrapper "
                           f"of {missing}: bench/harness.py changed")


def run(cell, seed, seconds, trace=False, **kw):
    """``bench.harness.run`` with the additions the module docstring
    lists; ``kw`` goes to the harness (a CPU rehearsal shrinks the cell)."""
    from bench import harness, scopes

    with instrumented(trace) as got:
        result = harness.run(cell, seed, seconds, trace=trace, **kw)
    window = result["window"]
    driver = window["driver"] = got["boundaries"].window()
    if driver is not None:
        # The profiler's start and stop run inside the hook of a traced
        # window: they are neither the driver's work nor the window's.
        result["metrics"]["driver.host_share"] = {
            "value": scopes.host_share(driver, window["seconds"]),
            "unit": "%"}
    if trace:
        red = got["scopes"]
        iters = window["traced_chain_iters"]
        traced_s = result["device"]["window_s"]
        busy = sum(red["scope_ns"].values())
        result["breakdown"]["scopes"] = {
            k: v * 1e-9 for k, v in sorted(red["scope_ns"].items())}
        result["breakdown"]["scopes_inherited"] = {
            k: v * 1e-9 for k, v in sorted(red["inherited_ns"].items())}
        result["breakdown"]["idle_by_span"] = {
            k: v * 1e-9 for k, v in sorted(red["idle_by_span"].items(),
                                           key=lambda kv: -kv[1])}
        result["breakdown"]["programs_without_hlo"] = red["no_proto"]
        result["device"]["scoped_busy_s"] = busy * 1e-9
        result["device"]["own_none_share"] = 100.0 * (
            red["scope_ns"].get(scopes.NONE, 0.0)
            + sum(red["inherited_ns"].values())) / busy if busy else None
        window["traced_chain_iters_per_s"] = iters / traced_s
        for name, value in scopes.step_metrics(red["scope_ns"], iters).items():
            result["metrics"][name] = {"value": value, "unit": "us/iter"}
    result["checks"] = result.pop("checks")  # stays the last key
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated non-negative seeds, run in turn")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if min(seeds) < 0:
        ap.error("--seeds must be non-negative whole numbers")

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("phases: needs a TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    use_compile_cache()
    log = lambda msg: print(f"[phases] {msg}", file=sys.stderr, flush=True)
    t_start = T_START
    for seed in seeds:
        log(f"{args.workload} seed={seed} trace={args.trace}")
        result = run(args.workload, seed, args.seconds, bool(args.trace),
                     t_start=t_start, log=log)
        result["seed"] = seed
        print(json.dumps(result), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
