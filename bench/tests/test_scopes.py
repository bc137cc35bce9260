"""Device time by scope and idle time by span, on traces written by hand."""

import re
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, scopes, trace

W = "jit(chunk)/while/body/closed_call/"
# The scopes the program put on its ops when a closed list of them was
# what bench/scopes.py recognised.
OLD_SCOPES = (
    "flymc.theta", "flymc.z", "flymc.z.candidates", "flymc.z.delta",
    "flymc.z.flips", "flymc.refresh", "regular.theta", "driver.outputs",
    "driver.fold",
)


def old_scope_of(path):
    """The innermost scope of OLD_SCOPES in an op-name path, or NONE."""
    found = scopes.NONE
    for part in (path or "").split("/"):
        while (m := re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*\((.*)\)",
                                 part)) is not None:
            part = m.group(1)
        if part in OLD_SCOPES:
            found = part
    return found


def test_scope_of_takes_the_innermost_known_scope():
    assert scopes.scope_of(W + "vmap(flymc.z)/flymc.z.flips/scatter") == (
        "flymc.z.flips")
    assert scopes.scope_of(W + "vmap(flymc.z)/select_n") == "flymc.z"
    assert scopes.scope_of(
        W + "transpose(jvp(flymc.theta))/bright_glm/pallas_call") == (
        "flymc.theta")
    assert scopes.scope_of("jit(fold)/driver.fold/while/body/dus") == (
        "driver.fold")
    # a scope the program adds counts under its own name, through any
    # transforms
    assert scopes.scope_of(
        W + "vmap(flymc.theta)/transpose(jvp(flymc.theta.grad))/dot") == (
        "flymc.theta.grad")
    assert scopes.scope_of(W + "vmap(flymc.zz)/add") == "flymc.zz"
    # names outside the namespaces, or only a namespace, are no scope
    for path in (W + "vmap(flymc)/add", W + "flymcz.a/add",
                 W + "vmap()/vmap(jit(_threefry_split))", "jit(f)/repro.x",
                 None):
        assert scopes.scope_of(path) == scopes.NONE, path


def _ops(scope_of=scopes.scope_of):
    """Ops with their scopes, as :func:`scopes.load` gives them."""
    return [(name, s, e, scope_of(path)) for name, s, e, path in [
        # a loop op containing the others is not a leaf
        ("%while.1", 0, 100, "jit(chunk)/while"),
        # starts before the window
        ("%bright_glm.14", 0, 30, W + "vmap(flymc.theta)/bright_glm/x"),
        ("%fusion.2", 30, 40, W + "vmap(flymc.theta)/scatter"),
        ("%z_candidates.7", 40, 70,
         W + "vmap(flymc.z)/flymc.z.candidates/z_candidates/pallas_call"),
        ("%fusion.3", 72, 80, W + "vmap(flymc.z)/flymc.z.flips/scatter"),
        # overlaps the flips op by 3: counted once, under the earlier op
        ("%fusion.4", 77, 85, W + "vmap(flymc.refresh)/reduce_sum"),
        ("%dynamic-update-slice.5", 85, 90, "jit(chunk)/while/body/dus"),
        ("%fusion.6", 95, 105, W + "vmap(flymc.theta)/dot"),
    ]]


def test_the_namespace_rule_reads_the_old_scopes_as_the_closed_list_did():
    assert scopes.scope_ns(_ops(), (10, 110)) == scopes.scope_ns(
        _ops(old_scope_of), (10, 110))
    paths = [path for *_, path in _ops(lambda p: p)]
    assert [scopes.scope_of(p) for p in paths] == [
        old_scope_of(p) for p in paths]


def test_scope_ns_splits_the_busy_time_once():
    got = scopes.scope_ns(_ops(), (10, 110))
    assert got == {"flymc.theta": 20 + 10 + 10, "flymc.z.candidates": 30,
                   "flymc.z.flips": 8, "flymc.refresh": 5,
                   scopes.NONE: 5}
    # the same ops through bench/trace.py: the scopes sum to its busy time
    assert sum(got.values()) == trace.reduce(_ops(), [], [], (10, 110))[
        "busy_ns"]


def test_idle_by_span_names_the_innermost_program_span():
    spans = [("bench.window", -100, 200),
             ("repro.sample.wait", 68, 73),
             ("PjitFunction(chunk)", 70, 71),  # not a program span
             ("repro.sample.dispatch", 90, 100),
             ("repro.sample.on_chunk", 92, 94)]
    got = scopes.idle_by_span(_ops(), spans, (10, 110))
    # gaps [70, 72], [90, 95] and [105, 110] (the window ends at 110)
    assert got == {"repro.sample.wait": 2, "repro.sample.on_chunk": 5,
                   "bench.window": 5}
    assert sum(got.values()) + sum(scopes.scope_ns(
        _ops(), (10, 110)).values()) == 100
    assert scopes.idle_by_span([], [], (0, 10)) == {scopes.NONE: 10}


def test_the_kernels_keep_their_times_under_the_scoped_names():
    """Under a named scope the kernels' ops are named "bright_glm.N" and
    "z_candidates.N" where they were "vmap_bright_glm_.N" and
    "vmap_z_candidates_.N": bench/trace.py's kernel times are the same."""
    old = [("%vmap_bright_glm_.14 = custom-call(f32[4] %a)", 0, 30, None),
           ("%vmap_z_candidates_.7 = custom-call(s32[4] %b)", 40, 70, None),
           ("%fusion.9 = f32[4] fusion(f32[4] %vmap_bright_glm_.14)", 70, 75,
            None)]
    new = [("%bright_glm.14 = custom-call(f32[4] %a)", 0, 30, None),
           ("%z_candidates.7 = custom-call(s32[4] %b)", 40, 70, None),
           ("%fusion.9 = f32[4] fusion(f32[4] %bright_glm.14)", 70, 75,
            None)]
    kernels = ["bright_glm", "z_candidates"]
    a = trace.reduce(old, [], [], (0, 100), kernels)
    b = trace.reduce(new, [], [], (0, 100), kernels)
    assert a["kernel_ns"] == b["kernel_ns"] == {"bright_glm": 30,
                                                "z_candidates": 30}
    assert a["busy_ns"] == b["busy_ns"]


def _ctx(scope_ns=None, chain_iters=1000, driver=None, seconds=25.0):
    return SimpleNamespace(
        scopes=None if scope_ns is None else {"scope_ns": scope_ns,
                                              "inherited_ns": {}},
        traced={"chain_iters": chain_iters},
        window={"seconds": seconds, "driver": driver})


def _read(name, ctx):
    return harness._module("metrics", name).read(ctx)


STEP_METRICS = ("step.theta_us", "step.z_us", "step.flips_us")


def test_step_readers_split_the_step_by_scope():
    ns = {"flymc.theta": 4e6, "flymc.z": 1e6, "flymc.z.candidates": 3e6,
          "flymc.z.flips": 2e6, "driver.outputs": 5e5, scopes.NONE: 1e5}
    got = {m: _read(m, _ctx(ns)) for m in STEP_METRICS}
    assert got == pytest.approx({"step.theta_us": 4.0, "step.z_us": 6.0,
                                 "step.flips_us": 2.0})
    assert _read("step.theta_us", _ctx({"regular.theta": 7e6})) == (
        pytest.approx(7.0))
    # a scope the program adds under a phase counts in it
    ns = {"flymc.theta": 4e6, "flymc.theta.grad": 1e6, "flymc.zeta": 9e6}
    assert _read("step.theta_us", _ctx(ns)) == pytest.approx(5.0)
    assert _read("step.z_us", _ctx(ns)) is None


@pytest.mark.parametrize("name", STEP_METRICS)
def test_step_readers_read_nothing_without_scopes(name):
    assert _read(name, _ctx()) is None
    assert _read(name, _ctx({scopes.NONE: 1e6})) is None
    assert _read(name, _ctx({"flymc.theta": 1e6, "flymc.z.flips": 1e6,
                             "regular.theta": 1e6}, chain_iters=0)) is None


def test_host_share_reader():
    delta = {"dispatch_s": 0.1, "wait_s": 20.0, "regrow_s": 0.0,
             "fold_s": 0.2, "hook_s": 0.2, "profiler_s": 0.0}
    assert _read("driver.host_share", _ctx(driver=delta)) == (
        pytest.approx(2.0))
    # the profiler's start and stop, inside the hook, leave both sides
    delta = dict(delta, hook_s=5.2, profiler_s=5.0)
    assert _read("driver.host_share", _ctx(driver=delta, seconds=30.0)) == (
        pytest.approx(2.0))
    # a program without counters gives nothing to read
    assert _read("driver.host_share", _ctx()) is None


class Event:
    def __init__(self, driver, committed=0):
        self.driver, self.committed, self.state = driver, committed, None


class Clock:
    def __init__(self, *times):
        self.times = iter(times)

    def __call__(self):
        return next(self.times)


def _window(seconds, trace_dir=None, trace_seconds=None):
    return harness.Window(seconds, trace_seconds or seconds, trace_dir, 0,
                          np.random.default_rng(0),
                          SimpleNamespace(active=False))


def test_window_keeps_the_counters_and_its_longest_stretch(monkeypatch):
    from repro.api.driver import DriverCounters

    monkeypatch.setattr(harness.time, "perf_counter",
                        Clock(0.0, 1.0, 3.5, 4.0))
    snaps = [DriverCounters(chunks=1, wait_s=0.5, hook_s=0.1),
             DriverCounters(chunks=2, wait_s=1.4, hook_s=0.2),
             DriverCounters(chunks=3, reruns=1, rerun_iters=40, wait_s=2.5,
                            dispatch_s=0.3, regrow_s=0.6, hook_s=0.3),
             DriverCounters(chunks=4, reruns=1, rerun_iters=40, wait_s=3.0,
                            dispatch_s=0.3, regrow_s=0.6, hook_s=0.4)]
    win = _window(4.0)
    assert [win(Event(s, i)) for i, s in enumerate(snaps)] == [
        False, False, False, True]
    got = win.driver()
    assert got["chunks"] == 3 and got["reruns"] == 1
    assert got["rerun_iters"] == 40
    assert got["wait_s"] == pytest.approx(2.5)
    assert got["profiler_s"] == 0.0
    assert got["longest_boundary_s"] == pytest.approx(2.5)
    assert got["longest_wait_s"] == pytest.approx(1.1)
    assert got["longest_host_s"] == pytest.approx(0.3 + 0.6 + 0.1)
    # a program without counters gives nothing to read
    monkeypatch.setattr(harness.time, "perf_counter", Clock(0.0, 9.0))
    empty = _window(4.0)
    bare = SimpleNamespace(state=None, committed=0)
    assert empty(bare) is False and empty(bare) is True
    assert empty.driver() is None


def test_window_times_the_profilers_start_and_stop(monkeypatch):
    from repro.api.driver import DriverCounters

    clock = Clock(0.0, 0.0, 0.5,  # boundary 1, the start around it
                  1.0, 2.0,  # boundaries 2 and 3
                  4.0, 4.0, 6.0,  # boundary 4, the stop around it
                  7.0)  # boundary 5 ends the window
    monkeypatch.setattr(harness.time, "perf_counter", clock)

    def start(self, committed):
        self.tracing, self.trace_t0 = object(), 0.5

    def stop(self, committed):
        self.tracing = None

    monkeypatch.setattr(harness.Window, "_start_trace", start)
    monkeypatch.setattr(harness.Window, "_stop_trace", stop)
    win = _window(7.0, trace_dir="unused", trace_seconds=3.0)
    for i in range(1, 6):
        win(Event(DriverCounters(chunks=i), committed=i))
    got = win.driver()
    assert got["profiler_s"] == pytest.approx(0.5 + 2.0)
    assert got["chunks"] == 4


# ---- scopes from the HLO protos a profile holds -----------------------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A serialized protobuf message from (field number, int | bytes | str)."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _instr(iid, name, opcode, op_name="", operands=(), called=()):
    fields = [(1, name), (2, opcode), (35, iid)]
    if op_name:
        fields.append((7, _msg((1, opcode), (2, op_name))))
    if operands:  # packed, as the compiler writes them
        fields.append((36, b"".join(_varint(o) for o in operands)))
    fields += [(38, c) for c in called]  # unpacked
    return _msg(*fields)


def _hlo_proto():
    fused = _msg(
        (1, "fused_computation.1"), (5, 1), (6, 13),
        (2, _instr(10, "param_0.1", "parameter")),
        (2, _instr(11, "scatter.1", "scatter", W + "vmap(flymc.z)/"
                   "flymc.z.flips/scatter", [10])),
        (2, _instr(12, "add.1", "add", W + "vmap(flymc.z)/flymc.z.flips/add",
                   [10])),
        (2, _instr(13, "tuple.1", "tuple", "", [11, 12])))
    entry = _msg(
        (1, "main"), (5, 2), (6, 27),
        (2, _instr(20, "p.0", "parameter")),
        # a multi-output fusion without metadata: its fused root's operands
        (2, _instr(21, "fusion.1", "fusion", "", [20], [1])),
        # a copy the compiler added: what it reads
        (2, _instr(22, "copy.1", "copy", "", [21])),
        (2, _instr(23, "copy-start.1", "copy-start", "", [22])),
        (2, _instr(24, "copy-done.1", "copy-done", "", [23])),
        # reads a parameter only: takes its reader's scope
        (2, _instr(25, "copy.2", "copy", "", [20])),
        (2, _instr(26, "fusion.2", "fusion", W + "vmap(flymc.theta)/dot",
                   [25])),
        (2, _instr(27, "tuple.2", "tuple", "", [24, 26])),
        # metadata that names no scope, and an op with no neighbours
        (2, _instr(28, "xor.1", "xor", W + "vmap(jit(_threefry_split))/xor")),
        (2, _instr(29, "iota.1", "iota")),
        # a single-output fusion without metadata: its root's
        (2, _instr(30, "fusion.3", "fusion", "", [20], [3])))
    fused_add = _msg(
        (1, "fused_computation.3"), (5, 3), (6, 31),
        (2, _instr(31, "add.3", "add", W + "vmap(flymc.z)/flymc.z.flips/add",
                   [])))
    return _msg((1, _msg((1, "jit_chunk"), (3, fused), (3, fused_add),
                         (3, entry))))


def test_instruction_scopes_inherit_across_instructions_without_metadata():
    got = scopes.instruction_scopes(_hlo_proto())
    # inferred: from what they read, or (copy.2) from their reader
    assert got["fusion.1"] == got["copy.1"] == got["copy-done.1"] == (
        "flymc.z.flips", True)
    assert got["copy.2"] == ("flymc.theta", True)
    # their own metadata, or a fusion's root's
    assert got["scatter.1"] == ("flymc.z.flips", False)
    assert got["fusion.2"] == ("flymc.theta", False)
    assert got["fusion.3"] == ("flymc.z.flips", False)
    assert got["xor.1"] == got["iota.1"] == (scopes.NONE, False)


def test_hlo_protos_are_read_from_the_metadata_plane():
    proto = _hlo_proto()
    stat = _msg((1, 7), (6, proto))
    metadata_plane = _msg(
        (1, 9), (2, "/host:metadata"),
        (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))),
        (5, _msg((1, 8), (2, _msg((1, 8), (2, "other"))))),
        (4, _msg((1, 3), (2, _msg((1, 3), (2, "jit_chunk(3)"), (5, stat))))),
        (4, _msg((1, 4), (2, _msg((1, 4), (2, "jit_fold(4)"),
                                  (5, _msg((1, 8), (6, b"x"))))))))
    device_plane = _msg((1, 1), (2, "/device:TPU:0"), (3, b"\x00" * 64))
    space = _msg((1, device_plane), (1, metadata_plane))
    assert scopes.hlo_protos(space) == {"jit_chunk(3)": proto}
    assert scopes.hlo_protos(_msg((1, device_plane))) == {}


def test_op_scope_takes_the_running_programs_instruction():
    flips = ("flymc.z.flips", True)
    programs = {"jit_chunk(3)": {"fusion.1": flips}}
    modules = [(100, "jit_chunk(3)"), (200, "jit_fold(4)")]
    assert scopes.op_scope("%fusion.1 = f32[4] fusion()", 150, modules,
                           programs) == flips
    # an op the proto does not know, one of a program without a proto,
    # and one before any program, are under no scope
    for name, start in [("fusion.9", 150), ("fusion.1", 250),
                        ("fusion.1", 50)]:
        assert scopes.op_scope(name, start, modules, programs) == (
            scopes.NONE, False)


def _xspace(device_ops, modules, host_spans, protos):
    """A serialized XSpace as the TPU profiler writes one: a device plane
    with "XLA Ops" and "XLA Modules" lines, a host plane and the
    "/host:metadata" plane holding each program's HLO proto. Times in ns."""
    def plane(pid, name, lines=(), events=(), stats=()):
        fields = [(1, pid), (2, name)] + [(3, ln) for ln in lines]
        fields += [(4, _msg((1, i), (2, _msg((1, i), (2, n), *extra))))
                   for i, n, extra in events]
        fields += [(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
                   for i, n in stats]
        return _msg(*fields)

    def line(lid, name, events, ids):
        return _msg((1, lid), (2, name), (3, 0), *[
            (4, _msg((1, ids[n]), (2, s * 1000), (3, (e - s) * 1000)))
            for n, s, e in events])

    def with_ids(events, first):
        return {n: first + i for i, n in enumerate(dict.fromkeys(
            n for n, _, _ in events))}

    op_ids = with_ids(device_ops, 1)
    mod_ids = with_ids(modules, 1000)
    host_ids = with_ids(host_spans, 1)
    device = plane(1, "/device:TPU:0", lines=[
        line(1, "XLA Modules", modules, mod_ids),
        line(2, "XLA Ops", device_ops, op_ids)],
        events=[(i, n, ()) for n, i in {**op_ids, **mod_ids}.items()])
    host = plane(2, "/host:CPU", lines=[line(1, "python3", host_spans,
                                             host_ids)],
                 events=[(i, n, ()) for n, i in host_ids.items()])
    meta = plane(3, "/host:metadata", stats=[(7, "Hlo Proto")], events=[
        (i, n, [(5, _msg((1, 7), (6, p)))])
        for i, (n, p) in enumerate(protos.items(), 1)])
    return _msg((1, device), (1, host), (1, meta))


def test_reduce_trace_of_a_recorded_file(tmp_path):
    """A whole trace file through the profiler's own reader: each op by
    its running program's HLO proto, inferred time apart, the scopes'
    sum equal to bench/trace.py's busy time, the idle by span."""
    ops = [("while.1", 0, 100),  # contains the others: not a leaf
           ("fusion.2", 0, 30),  # own metadata, starts before the window
           ("copy.2", 30, 40),  # inferred from its reader
           ("fusion.1", 40, 70),  # inferred from its fused root's operands
           ("copy.1", 72, 80),  # inferred from what it reads
           ("xor.1", 77, 85),  # names no scope; overlaps copy.1 by 3
           ("iota.1", 95, 105)]  # in a program without a proto
    modules = [("jit_chunk(3)", 0, 90), ("jit_fold(4)", 90, 110)]
    spans = [("bench.traced", 10, 110), ("repro.sample.wait", 68, 73),
             ("repro.sample.dispatch", 86, 100)]
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(_xspace(
        ops, modules, spans, {"jit_chunk(3)": _hlo_proto()}))
    got = scopes.reduce_trace(str(tmp_path))
    assert got["scope_ns"] == {"flymc.theta": 20 + 10,
                               "flymc.z.flips": 30 + 8, scopes.NONE: 5 + 10}
    assert got["inherited_ns"] == {"flymc.theta": 10, "flymc.z.flips": 38}
    assert got["idle_by_span"] == {"repro.sample.wait": 2,
                                   "repro.sample.dispatch": 10,
                                   "bench.traced": 5}
    assert got["no_proto"] == ["jit_fold(4)"]
    _, first = trace.reduce_trace(str(tmp_path))
    assert sum(got["scope_ns"].values()) == first["busy_ns"] == 83
    assert first["window_ns"] == 100


def test_a_traced_line_carries_the_phase_split(tmp_path):
    """A recorded trace through the harness's own reduction, readers and
    breakdown: the step's phases and the driver's host share on the line,
    the scopes summing to the busy time."""
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(_xspace(
        [("fusion.2", 10, 50), ("fusion.1", 50, 90)],
        [("jit_chunk(3)", 0, 100)],
        [("bench.traced", 10, 110), ("repro.sample.wait", 95, 105)],
        {"jit_chunk(3)": _hlo_proto()}))
    reduced, by_scope = harness.reduce_traced(str(tmp_path))
    cell = "logistic-rwmh.map.c4"
    _, cfg, traffic, _ = harness.find_cell(cell)
    delta = {"dispatch_s": 0.01, "wait_s": 1.9, "regrow_s": 0.0,
             "fold_s": 0.0, "hook_s": 0.01, "profiler_s": 0.0}
    ctx = SimpleNamespace(
        cfg=cfg, traffic=traffic, flymc=True, trace=reduced,
        scopes=by_scope, traced={"chain_iters": 10, "queries": 1000},
        window={"chain_iters": 100, "seconds": 2.0, "min_ess": 5.0,
                "queries_per_iter": 10.0, "driver": delta},
        peaks=harness.load_json(harness.BENCH / "peaks.json")[
            "TPU v5 lite"])
    m = harness.per_layer(harness.benchmark_spec(), cell, ctx)
    assert m["step.theta_us"] == {"value": pytest.approx(40e-3 / 10),
                                  "unit": "us/iter"}
    assert m["step.z_us"]["value"] == pytest.approx(40e-3 / 10)
    assert m["step.flips_us"]["value"] == pytest.approx(40e-3 / 10)
    assert m["driver.host_share"] == {"value": pytest.approx(1.0),
                                      "unit": "%"}
    b = harness.breakdown(reduced, by_scope)
    assert b["scopes"] == [["flymc.theta", pytest.approx(40e-9)],
                           ["flymc.z.flips", pytest.approx(40e-9)]]
    assert sum(v for _, v in b["scopes"]) == pytest.approx(
        reduced["busy_ns"] * 1e-9)
    assert b["scopes_inherited"] == [["flymc.z.flips", pytest.approx(40e-9)]]
    # the one gap, [90, 110], by the span over its middle
    assert b["idle_by_span"] == [["repro.sample.wait", pytest.approx(20e-9)]]


def test_breakdown_lists_keep_ten_entries_and_the_whole_sum():
    ns = {f"flymc.s{i}": float(i) for i in range(1, 13)}
    got = harness._top(ns)
    assert len(got) == harness.BREAKDOWN_TOP
    assert got[0] == ["flymc.s12", pytest.approx(12e-9)]
    assert got[-1] == ["(rest)", pytest.approx((1 + 2 + 3) * 1e-9)]
    assert sum(v for _, v in got) == pytest.approx(78e-9)


def test_scopes_from_a_recorded_cpu_profile(tmp_path):
    """The HLO proto a CPU profile keeps of a jitted function: every
    instruction with op-name metadata gets that metadata's scope."""
    import re

    import jax
    import jax.numpy as jnp

    def f(x, idx):
        with jax.named_scope("flymc.theta"):
            y = jnp.sin(x) * 2
            s1, s2 = y.sum(), (y * y).sum()
        with jax.named_scope("flymc.z"), jax.named_scope("flymc.z.flips"):
            z = y.at[idx].set(3.0)
        return z, s1, s2

    g = jax.jit(jax.vmap(f, in_axes=(0, None)))
    args = (jnp.ones((3, 64)), jnp.array([1, 5]))
    jax.block_until_ready(g(*args))
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(g(*args))
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    protos = scopes.hlo_protos(path.read_bytes())
    (name,) = [n for n in protos if n.startswith("jit_f(")]
    got = scopes.instruction_scopes(protos[name])
    text = g.lower(*args).compile().as_text()
    own = dict(re.findall(r'%(\S+) = [^\n]*op_name="([^"]*)"', text))
    assert own and {k: got[k] for k in own} == {
        k: (scopes.scope_of(v), False) for k, v in own.items()}
    assert {"flymc.theta", "flymc.z.flips"} <= {s for s, _ in got.values()}
