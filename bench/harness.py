"""One run of one cell: set-up, measured window, optional trace, checks.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything the
harness needs about it is found by name:

    bench/configs/<config>.json     the deployment (problem and sizes)
    bench/traffic/<traffic>.json    the chain law the cell runs
    bench/workloads/<cell>.json     the limits of the numbers compared
    bench/families/<family>.py      rows from the seed, model via the program
    bench/reference/<family>.py     the plain reference
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/work/<kernel>.py          logical work per kernel

The program is used through its normal path only: ``GLMModel`` (MAP fit
and tuned bound), ``repro.api.firefly``/``regular_mcmc`` and
``repro.api.sample`` with an ``on_chunk`` hook.

θ may have any shape. The harness flattens every θ it reads once, in the
program's row-major order, to P = prod(θ's shape): the window's draws to
(chains, iterations, P), a kept state's θ to (P,) per chain, the θs of
``bright_gap`` to (M, P). The reference (``bench/reference/__init__.py``)
takes θ flat and may name the coordinates that are compared
(``coords``); ESS, ``mean_z`` and ``sd_gap`` are taken over those.

A traced run reads the device time of every named scope the program puts
on its ops (``bench/scopes.py``) and, on every run, the window's
difference of the driver's counters (``ChunkEvent.driver``): the metric
readers find them as ``ctx.scopes`` and ``ctx.window["driver"]``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from bench.ess import ess_per_coord

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

# Numbers compared that come out infinite or NaN (a chain that never
# moved has sd 0) are reported as this, which no limit admits.
NOT_FINITE = 1e30
# Window boundary states kept per run for lp_gap and delta_gap (drawn from
# the seed), besides the last.
CHECK_STATES = 2
# Window iterations per chain at which bright_gap compares the bright
# count with the reference's expectation: one drawn from the seed in each
# of this many equal stretches of the window.
BRIGHT_DRAWS = 16
# The window's output buffer holds this many times the iterations that
# the warm-up's rate would reach in the window, in a power of two of
# chunks, so that runs of a cell share one fold program.
BUFFER_MARGIN = 1.5
# What a traffic file's "bound" and "start" may say.
BOUNDS = ("map", "regular")
STARTS = ("map", "dark")
# The driver's counters that are host work (the overflow wait, wait_s, is
# the device's).
HOST_PARTS = ("dispatch_s", "regrow_s", "fold_s", "hook_s")
# Entries of each list of a traced line's breakdown.
BREAKDOWN_TOP = 10


def load_json(path):
    with open(path) as f:
        return json.load(f)


def benchmark_spec():
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(name, spec=None):
    """(workload entry, config, traffic, limits) of one cell, by name."""
    spec = spec or benchmark_spec()
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = load_json(BENCH / "configs" / f"{entry['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    limits = load_json(BENCH / "workloads" / f"{name}.json")["limits"]
    return entry, cfg, traffic, limits


def _choice(traffic, key, allowed):
    value = traffic[key]
    if value not in allowed:
        raise ValueError(f"traffic {key}={value!r}: expected one of "
                         f"{', '.join(allowed)}")
    return value


def _module(kind, name):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed):
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def _buffers(cfg, traffic):
    """Bright and candidate capacities: given, or "expected" (the initial
    bright set 2·q_db·N and a step's candidates q_db·N, plus a tenth, in
    whole 1024-row blocks, as chip_smoke.py sizes them)."""
    pad = lambda rows: ((int(1.1 * rows) + 1 + 1023) // 1024) * 1024
    expected = {"capacity": pad(2 * cfg["q_db"] * cfg["n"]),
                "cand_capacity": pad(cfg["q_db"] * cfg["n"])}
    return {k: expected[k] if traffic[k] == "expected" else int(traffic[k])
            for k in ("capacity", "cand_capacity")}


class CompileCounter:
    """Counts JAX tracings and executable builds while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.traces = 0
        self.builds = 0

        def on_duration(event, duration, **kw):
            if not self.active:
                return
            if event == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                self.builds += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


class Rate:
    """The warm-up's ``on_chunk`` hook: iterations per second per chain
    between its first and last chunk boundaries (the first chunk, which
    may compile, is left out)."""

    def __init__(self):
        self.marks = []

    def __call__(self, event):
        import jax

        jax.block_until_ready(event.state)
        self.marks.append((time.perf_counter(), event.committed))
        return False

    def per_chain(self):
        if len(self.marks) < 2:
            raise ValueError("the warm-up must span at least two chunks")
        (t0, n0), (t1, n1) = self.marks[0], self.marks[-1]
        return (n1 - n0) / (t1 - t0)


def window_buffer(rate, seconds, chunk):
    """Iterations per chain the window's buffer holds: BUFFER_MARGIN times
    what ``rate`` reaches in ``seconds``, plus the call's first chunk, in
    a power of two of chunks."""
    chunks = math.ceil(BUFFER_MARGIN * rate * seconds / chunk) + 1
    return chunk * 2 ** math.ceil(math.log2(chunks))


def counter_delta(first, last):
    """Field-by-field difference of two DriverCounters snapshots."""
    a, b = dataclasses.asdict(first), dataclasses.asdict(last)
    return {k: b[k] - a[k] for k in a}


class Window:
    """The ``on_chunk`` hook that times the window.

    Boundary 1 (the end of the call's first chunk, which allocates the
    trace and compiles its fold) starts the window; the first boundary at
    least ``seconds`` later ends it. Each boundary waits for the chunk's
    state before reading the clock. A few boundary states, drawn from the
    seed, are kept for the checks, and at every boundary the clock, the
    driver's counters (``ChunkEvent.driver``) and the seconds the hook
    spent starting or stopping the profiler.
    """

    def __init__(self, seconds, trace_seconds, trace_dir, keep, rng,
                 counter):
        self.seconds = seconds
        self.trace_seconds = trace_seconds
        self.trace_dir = trace_dir
        self.keep = keep
        self.rng = rng
        self.counter = counter
        self.boundaries = 0
        self.t0 = self.t_end = None
        self.n0 = self.n_end = None
        self.kept = []  # [(committed, state)]
        self.tracing = None  # open TraceAnnotation while the profiler runs
        self.trace_range = None  # (committed at start, committed at stop)
        self.trace_t0 = None
        self.marks = []  # [(clock, DriverCounters, profiler seconds)]

    def __call__(self, event):
        import jax

        with jax.profiler.TraceAnnotation("bench.on_chunk"):
            jax.block_until_ready(event.state)
            now = time.perf_counter()
            self.boundaries += 1
            profiler_s = 0.0
            if self.boundaries == 1:
                self.t0, self.n0 = now, event.committed
                self.counter.active = True
                if self.trace_dir is not None:
                    profiler_s = self._timed(self._start_trace,
                                             event.committed)
                self._mark(event, now, profiler_s)
                return False
            if self.tracing is not None and (
                    now - self.trace_t0 >= self.trace_seconds):
                profiler_s = self._timed(self._stop_trace, event.committed)
            self._mark(event, now, profiler_s)
            # Reservoir sample of the window's boundary states.
            seen = self.boundaries - 1
            if len(self.kept) < self.keep:
                self.kept.append((event.committed, event.state))
            else:
                j = int(self.rng.integers(seen))
                if j < self.keep:
                    self.kept[j] = (event.committed, event.state)
            if now - self.t0 >= self.seconds:
                self.t_end, self.n_end = now, event.committed
                self.counter.active = False
                return True
            return False

    def _mark(self, event, now, profiler_s):
        driver = getattr(event, "driver", None)
        if driver is not None:
            self.marks.append((now, driver, profiler_s))

    @staticmethod
    def _timed(fn, *args):
        t = time.perf_counter()
        fn(*args)
        return time.perf_counter() - t

    def driver(self):
        """The driver's counters over the window, or None where the
        program gives none: the difference between the first and the last
        boundary's, ``profiler_s`` (the seconds that starting and stopping
        the profiler took inside it, 0 untraced) and its longest stretch
        between two boundaries, split into the overflow wait and the
        host's work.

        The counters of a boundary are taken before its hook runs, so the
        window holds the hooks of every boundary but its last.
        """
        if len(self.marks) < 2:
            return None
        out = counter_delta(self.marks[0][1], self.marks[-1][1])
        out["profiler_s"] = sum(p for _, _, p in self.marks[:-1])
        steps = [(t1 - t0, counter_delta(a, b)) for (t0, a, _), (t1, b, _)
                 in zip(self.marks, self.marks[1:])]
        longest, d = max(steps, key=lambda s: s[0])
        out["longest_boundary_s"] = longest
        out["longest_wait_s"] = d["wait_s"]
        out["longest_host_s"] = sum(d[k] for k in HOST_PARTS)
        return out

    def _start_trace(self, committed):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = jax.profiler.TraceAnnotation("bench.traced")
        self.tracing.__enter__()
        self.trace_t0 = time.perf_counter()
        self.trace_range = [committed, None]

    def _stop_trace(self, committed):
        import jax

        self.tracing.__exit__(None, None, None)
        self.tracing = None
        self.trace_range[1] = committed
        jax.profiler.stop_trace()

    def close(self):
        """Stop a trace the window's end left running."""
        if self.tracing is not None:
            self._stop_trace(self.n_end)


@dataclasses.dataclass
class Fault:
    """A fault planted under the timed path, for the harness's own tests
    and the calibration of limits: "frozen" (the step returns its state
    unchanged), "half" (odd rows replaced by their even neighbours: half
    the batch left out, the sum taken as twice the rest's), "shift" (each
    θ the step produces is reported moved by ``5/√N`` in its flat
    coordinate 0, the first element in row-major order) or, for FlyMC,
    "z_frozen" (the step skips its z-update: the bright set stays as it
    started). ``theta_ndim`` is the number of θ's axes."""

    kind: str

    def plant(self, alg, n, theta_ndim):
        import jax
        import jax.numpy as jnp

        step = alg.step_data
        if self.kind == "z_frozen":
            from repro.core import flymc

            def skip(spec, data, key, theta, bright, delta_full, delta_b):
                return bright, delta_full, jnp.int32(0), jnp.bool_(False)

            def bad(key, state, data, stats):
                # The step looks the z-update up when it is traced.
                real = flymc._fused_z_update
                flymc._fused_z_update = skip
                try:
                    return step(key, state, data, stats)
                finally:
                    flymc._fused_z_update = real
            return dataclasses.replace(alg, step_data=bad)
        if self.kind == "frozen":
            def bad(key, state, data, stats):
                _, info = step(key, state, data, stats)
                return state, info
            return dataclasses.replace(alg, step_data=bad)
        if self.kind == "half":
            def halve(a):
                if a is None or a.ndim == 0 or a.shape[0] != n:
                    return a
                return jnp.repeat(a[0::2], 2, axis=0)[:n]

            def bad(key, state, data, stats):
                return step(key, state, jax.tree.map(halve, data), stats)
            return dataclasses.replace(alg, step_data=bad)
        if self.kind == "shift":
            shift = 5.0 / math.sqrt(n)
            pos = alg.position_of
            first = (Ellipsis,) + (0,) * theta_ndim
            return dataclasses.replace(
                alg, position=lambda s: pos(s).at[first].add(shift))
        raise ValueError(f"unknown fault {self.kind!r}")


def run(cell, seed, seconds, trace=False, *, t_start=None, cfg_over=None,
        traffic_over=None, fault=None, control=False, log=None, spec=None):
    """One run of ``cell``; returns the result dict the command prints.

    ``cfg_over``/``traffic_over`` shrink a cell for a CPU rehearsal or a
    test; ``fault`` plants a :class:`Fault`; ``control`` adds the control's
    readings (the reference one precision down at the same states) under
    ``"control"``; ``spec`` stands in for ``BENCHMARK.json`` (a test runs a
    cell the benchmark does not list). ``log`` receives progress lines.
    """
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.core import flymc

    log = log or (lambda *a: None)
    t_start = time.perf_counter() if t_start is None else t_start
    spec = spec or benchmark_spec()
    entry, cfg, traffic, limits = find_cell(cell, spec)
    cfg = {**cfg, **(cfg_over or {})}
    traffic = {**traffic, **(traffic_over or {})}
    fam = _module("families", cfg["family"])
    ref = _module("reference", cfg["family"])
    chains, chunk = traffic["chains"], traffic["chunk"]
    is_flymc = _choice(traffic, "bound", BOUNDS) == "map"
    start_at = _choice(traffic, "start", STARTS)
    if start_at == "dark" and not is_flymc:
        raise ValueError("start 'dark' needs a FlyMC bound")
    dev = jax.devices()[0]
    counter = CompileCounter()

    k_data, k_map, k_warm, k_window = jax.random.split(seed_key(seed), 4)
    span = jax.profiler.TraceAnnotation
    with span("bench.setup.data"):
        data = jax.jit(lambda k: fam.make_data(k, cfg))(k_data)
        jax.block_until_ready(data)
    with span("bench.setup.map"):
        model = fam.build_model(data, cfg)
        theta_map = model.map_estimate(k_map, steps=cfg["map_steps"])
        tuned = model.map_tuned(theta_map)
        jax.block_until_ready(tuned.stats)
    log(f"setup: data and MAP at {time.perf_counter() - t_start:.2f} s")
    common = dict(kernel=cfg["kernel"], step_size=cfg["step_size"],
                  adapt_target="auto", num_warmup=traffic["warmup"])
    with span("bench.setup.build"):
        if is_flymc:
            alg = api.firefly(tuned, q_db=cfg["q_db"], backend="pallas",
                              z_backend="fused", **_buffers(cfg, traffic),
                              **common)
        else:
            alg = api.regular_mcmc(tuned, **common)
        if fault is not None:
            alg = fault.plant(alg, cfg["n"], theta_map.ndim)
        start = dict(init_position=theta_map)
        if start_at == "dark":
            # Every array goes in as an operand: one baked in as a constant
            # would make the program differ from seed to seed and miss the
            # compilation cache.
            def init(k, d, s, z0, theta0):
                return flymc.init_chain_state(
                    alg.spec, d, s, theta0, k, z0=z0,
                    step_size=cfg["step_size"])
            keys = jax.random.split(jax.random.fold_in(k_warm, 1), chains)
            start = dict(init_state=jax.jit(
                jax.vmap(init, in_axes=(0, None, None, None, None)))(
                    keys, alg.data, alg.stats,
                    jnp.zeros(cfg["n"], bool), theta_map))
    with span("bench.setup.warmup"):
        rate = Rate()
        warm = api.sample(alg, k_warm, traffic["warmup"], num_chains=chains,
                          chunk_size=chunk, collectors={}, on_chunk=rate,
                          **start)
        jax.block_until_ready(warm.final_state)
    cap = window_buffer(rate.per_chain(), seconds, chunk)
    log(f"setup: warm-up done at {time.perf_counter() - t_start:.2f} s, "
        f"{rate.per_chain():.1f} iterations/s per chain, buffer {cap}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    win = Window(seconds, traffic.get("trace_seconds", seconds), trace_dir,
                 CHECK_STATES, np.random.default_rng(seed), counter)
    with span("bench.window"):
        out = api.sample(warm.algorithm, k_window, cap, num_chains=chains,
                         chunk_size=chunk, init_state=warm.final_state,
                         on_chunk=win)
    win.close()
    if win.t_end is None:  # the buffer ran out before the window did
        win.t_end = time.perf_counter()
        win.n_end = out.theta.shape[1]
    window_s = win.t_end - win.t0
    setup_s = win.t0 - t_start
    driver = win.driver()
    log(f"window: {win.n_end - win.n0} iterations x {chains} chains in "
        f"{window_s:.3f} s; compilations={counter.builds} "
        f"tracings={counter.traces}")

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": entry["chips"],
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    # ---- what the window produced, to the host -------------------------
    n0, n1 = win.n0, win.n_end
    draws = flat_theta(out.theta[:, n0:n1], 2)
    queries = np.asarray(out.stats.lik_queries[:, n0:n1], np.int64)
    bright = None
    if is_flymc:
        at = strata(n1 - n0, BRIGHT_DRAWS, np.random.default_rng([seed, 1]))
        bright = (draws[:, at],
                  np.asarray(out.stats.n_bright[:, n0:n1])[:, at])
    states = [(c, s) for c, s in win.kept if n0 < c < n1]
    states.append((n1, out.final_state))
    kept = [_state_to_host(s, is_flymc) for _, s in states]
    x = np.asarray(data["x"])
    t = np.asarray(data["t"])
    theta_star = np.asarray(theta_map).reshape(-1)
    traced = None
    if win.trace_range is not None:
        a, b = win.trace_range
        traced = {"chain_iters": chains * (b - a),
                  "queries": int(np.asarray(out.stats.lik_queries[:, a:b],
                                            np.int64).sum())}
    del out, warm, alg, model, tuned, data, start, win.kept

    ess = ess_per_coord(coordinates(ref, cfg, draws))
    min_ess = float(ess.min())
    chain_iters = chains * (n1 - n0)
    window = {"chain_iters": chain_iters, "seconds": window_s,
              "min_ess": min_ess,
              "queries_per_iter": float(queries.mean()), "driver": driver}

    # ---- checks --------------------------------------------------------
    log(f"checks: outputs on the host at {time.perf_counter() - t_start:.2f} s")
    checks, attempted = compare(ref, cfg, x, t, theta_star, kept, draws,
                                ess, is_flymc, np.random.default_rng(seed),
                                bright=bright)
    log(f"checks: compared at {time.perf_counter() - t_start:.2f} s")
    failed = [k for k, v in checks.items() if not v <= limits[k]]
    result = {
        "correct": not failed,
        "attempted": attempted,
        "failed": len(failed),
        "metrics": {},
        "device": device,
    }
    if control:
        result["control"] = compare(ref, cfg, x, t, theta_star, kept, None,
                                    None, is_flymc, None, prec="bf16")[0]
    if not trace:
        e2e = {"min_ess_per_s": min_ess / window_s,
               "chain_iters_per_s": chain_iters / window_s,
               "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if cell in m.get("workloads", [cell]) and m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    else:
        try:
            reduced, by_scope = reduce_traced(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = load_json(BENCH / "peaks.json")
        if dev.device_kind not in peaks:
            raise KeyError(f"no peaks for device kind {dev.device_kind!r} "
                           "in bench/peaks.json")
        ctx = SimpleNamespace(cfg=cfg, traffic=traffic, flymc=is_flymc,
                              trace=reduced, scopes=by_scope, traced=traced,
                              window=window, peaks=peaks[dev.device_kind])
        result["metrics"] = per_layer(spec, cell, ctx)
        device["busy_s"] = reduced["busy_ns"] * 1e-9
        device["window_s"] = reduced["window_ns"] * 1e-9
        result["breakdown"] = breakdown(reduced, by_scope)
    result["window"] = {"iterations": n1 - n0, "chains": chains,
                        "traced_chain_iters": traced and traced["chain_iters"],
                        "seconds": window_s, "min_ess": min_ess,
                        "compilations": counter.builds,
                        "tracings": counter.traces, "driver": driver}
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in checks.items()}
    return result


def reduce_traced(trace_dir):
    """(``bench.trace`` reduction with each kernel of ``bench/work``,
    ``bench.scopes`` reduction) of a recorded trace."""
    from bench import scopes
    from bench import trace as trace_lib

    kernels = [p.name[:-len(".py")] for p in (BENCH / "work").glob("*.py")
               if p.name not in ("__init__.py", "step.py")]
    _, reduced = trace_lib.reduce_trace(trace_dir, kernels)
    return reduced, scopes.reduce_trace(trace_dir)


def per_layer(spec, cell, ctx):
    """{metric: {"value", "unit"}} of the cell's per-layer metrics that
    their readers find something to read for."""
    out = {}
    for m in spec["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        value = _module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _top(ns_by_name):
    """[[name, seconds]], longest first, at most BREAKDOWN_TOP entries: past
    that the shortest are summed under "(rest)"."""
    pairs = sorted(ns_by_name.items(), key=lambda kv: -kv[1])
    if len(pairs) > BREAKDOWN_TOP:
        keep = BREAKDOWN_TOP - 1
        pairs = pairs[:keep] + [("(rest)", sum(v for _, v in pairs[keep:]))]
    return [[n, v * 1e-9] for n, v in pairs]


def breakdown(reduced, by_scope):
    """A traced line's breakdown: the device ops that took most time, the
    longest idle gaps by host event, the device time per named scope
    (``(none)`` for ops under none; the entries sum to the busy time), the
    part of each scope's time that was inferred, not named by the op's own
    metadata, and the idle time per innermost ``repro.*`` or ``bench.*``
    host span."""
    return {
        "device_ops": [[n, s * 1e-9] for n, s in reduced["top_ops"]],
        "idle_gaps": [[n, s * 1e-9] for n, s in reduced["top_gaps"]],
        "scopes": _top(by_scope["scope_ns"]),
        "scopes_inherited": _top(by_scope["inherited_ns"]),
        "idle_by_span": _top(by_scope["idle_by_span"]),
    }


def flat_theta(theta, lead):
    """θ values with ``lead`` leading axes as float64 (*lead axes, P):
    each θ flattened in row-major order."""
    theta = np.asarray(theta, np.float64)
    return theta.reshape(*theta.shape[:lead], -1)


def coordinates(ref, cfg, theta):
    """The compared coordinates (..., Q) of flat θs (..., P): the
    reference's ``coords`` where it defines one, else θ itself."""
    coords = getattr(ref, "coords", None)
    return theta if coords is None else coords(theta, cfg)


def _state_to_host(state, is_flymc):
    """θ (flat), lp and (FlyMC) the bright rows with their cached δ, per
    chain."""
    theta = flat_theta(state.sampler.theta, 1)
    lp = np.asarray(state.sampler.lp, np.float64)
    out = {"theta": theta, "lp": lp}
    if is_flymc:
        arr = np.asarray(state.bright.arr)
        num = np.asarray(state.bright.num)
        delta = np.asarray(state.delta_full)
        ids = [arr[c, :num[c]] for c in range(theta.shape[0])]
        out["bright"] = ids
        out["delta"] = [delta[c, i].astype(np.float64)
                        for c, i in enumerate(ids)]
    return out


def strata(length, k, rng):
    """One index drawn in each of ``k`` equal stretches of range(length)
    (every index once where the range is shorter)."""
    if length <= k:
        return np.arange(length)
    edges = np.linspace(0, length, k + 1).astype(int)
    return np.array([rng.integers(a, b) for a, b in zip(edges, edges[1:])])


def expected_bright(ref, thetas, x, t, xi, cfg, block=1 << 16):
    """Σ_n P(z_n = 1 | θ) = Σ_n (1 - e^{-δ_n(θ)}) under the float64
    reference, at each θ of ``thetas`` (M, P), flat: the bright count that
    the joint law of (θ, z) gives in expectation."""
    from bench.reference.common import Arith

    f64 = Arith("f64")
    th = np.asarray(thetas, np.float64).T  # (P, M): every θ at once
    total = np.zeros(th.shape[1])
    for i in range(0, x.shape[0], block):
        rows = slice(i, i + block)
        _, _, delta = ref.rows(th, x[rows], t[rows, None], xi[rows, None],
                               cfg, f64)
        total += -np.expm1(-np.maximum(delta, 0.0)).sum(axis=0)
    return total


def _finite(v):
    v = float(v)
    return v if math.isfinite(v) else NOT_FINITE


def compare(ref, cfg, x, t, theta_star, kept, draws, ess, is_flymc, rng,
            prec="f64", bright=None):
    """The numbers compared, and how many answers they cover.

    θ is flat everywhere (module docstring): a kept state's ``theta``
    (chains, P), ``draws`` (chains, iterations, P), ``bright``'s θ
    (chains, S, P); ``ess`` (Q,) is over the compared coordinates
    (:func:`coordinates`), as the reference's ``posterior`` moments are.

    lp_gap     spread (max - min, nats) of lp - reference lp over the kept
               states and chains: FlyMC's joint log density of (θ, z),
               regular MCMC's log posterior. A density is defined up to a
               constant, and only differences of lp enter a Metropolis-
               Hastings decision, so an offset common to every state
               (f32 misses the constant of a sum of 1.8M rows by hundreds
               of nats) is not an error; a gap that varies between states
               is.
    delta_gap  largest |δ - reference δ| (nats) over the bright rows of
               the kept states (FlyMC)
    mean_z     largest |window mean - posterior mean| over the compared
               coordinates, in combined standard errors
    sd_gap     largest |log(window sd / posterior sd)| over the compared
               coordinates
    bright_gap |log((Σ bright count + 1) / (Σ expected count + 1))| over
               ``bright`` = (θ (K, S, P), bright count (K, S)) at S window
               iterations per chain (FlyMC): the z-update's law. Under the
               joint, z given θ is Bernoulli(1 - e^{-δ_n(θ)}) per row, so a
               chain at its stationary law brightens Σ_n (1 - e^{-δ_n(θ)})
               rows on average, whatever z-engine draws them

    With ``prec="bf16"`` (the control) the reference is computed one
    precision down at the same states and only lp_gap and delta_gap are
    read: it samples nothing.
    """
    from bench.reference.common import Arith, log_expm1

    f64 = Arith("f64")
    ar = Arith(prec)
    xi = ref.tune(x, t, theta_star, cfg)
    lp_err, delta_gap, answers = [], 0.0, 0
    for st in kept:
        for c in range(st["theta"].shape[0]):
            theta = st["theta"][c]
            log_l, log_b, delta = ref.rows(theta, x, t, xi, cfg, f64)
            if is_flymc:
                ids = st["bright"][c]
                want = (ref.log_prior(theta, cfg, f64) + log_b.sum()
                        + log_expm1(delta[ids]).sum())
            else:
                want = ref.log_prior(theta, cfg, f64) + log_l.sum()
            if prec == "f64":
                got = st["lp"][c]
                got_delta = st["delta"][c] if is_flymc else None
            else:
                cl, cb, cd = ref.rows(theta, x, t, xi, cfg, ar)
                if is_flymc:
                    ids = st["bright"][c]
                    got = (ref.log_prior(theta, cfg, ar) + ar.total(cb)
                           + ar.total(log_expm1(np.maximum(cd[ids], 1e-30))))
                    got_delta = cd[ids]
                else:
                    got = ref.log_prior(theta, cfg, ar) + ar.total(cl)
                    got_delta = None
            lp_err.append(float(got) - float(want))
            if got_delta is not None and len(got_delta):
                delta_gap = max(delta_gap, float(np.max(np.abs(
                    np.asarray(got_delta, np.float64) - delta[st["bright"][c]]))))
            answers += 1
    checks = {"lp_gap": _finite(max(lp_err) - min(lp_err))}
    if is_flymc:
        checks["delta_gap"] = _finite(delta_gap)
    if bright is not None:
        thetas, counts = bright
        want = expected_bright(ref, thetas.reshape(-1, thetas.shape[-1]),
                               x, t, xi, cfg).sum()
        checks["bright_gap"] = _finite(abs(math.log(
            (float(counts.sum()) + 1.0) / (want + 1.0))))
        answers += counts.size
    if draws is not None:
        mean, sd, se, _ = ref.posterior(x, t, cfg, rng)
        got = coordinates(ref, cfg, draws)
        flat = got.reshape(-1, got.shape[-1])
        got_mean, got_sd = flat.mean(0), flat.std(0)
        mcse = sd / np.sqrt(ess)
        with np.errstate(divide="ignore", invalid="ignore"):
            z = np.abs(got_mean - mean) / np.sqrt(mcse**2 + se**2)
            ratio = np.abs(np.log(got_sd / sd))
        checks["mean_z"] = _finite(np.max(z))
        checks["sd_gap"] = _finite(np.max(ratio))
        answers += 2 * mean.size
    return checks, answers
