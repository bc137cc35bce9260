"""Device time by named scope and idle time by host span, from a trace.

The program labels the ops of its step with named scopes
(``jax.named_scope``, carried as HLO op-name metadata such as
``jit(chunk)/while/body/vmap(flymc.z)/flymc.z.flips/scatter``) and its
host work per chunk with spans (``jax.profiler.TraceAnnotation``,
``repro.sample.*``). :func:`reduce_trace` splits the traced window's
device busy time by the scope of each leaf op, and its idle time by the
innermost ``repro.*`` or ``bench.*`` span covering each gap's middle. The
trace is read by :func:`bench.trace.load`, with its conventions:
nanoseconds on the profiler's clock, the window is the ``bench.traced``
span, only leaf ops count.

An op's scope is the innermost scope in its instruction's op-name
metadata (a path component whose name, inside any transforms, lies in one
of the program's namespaces ``flymc.``, ``regular.`` and ``driver.``: a
scope the program adds counts under its own name), read from the HLO protos that the profile keeps of every
program it saw (a fusion's metadata is its root's). The compiler leaves
some instructions without metadata (multi-output fusions, copies, sorts
it adds). Such an instruction's scope is inferred: the commonest scope of
the instructions whose data it reads, a fusion's that of its fused root,
and failing both the commonest scope of the instructions that read its
data. Inferred time is kept apart (``inherited_ns``), so that what the
program's own metadata names can be told from what the inference adds.
An op of a program the profile holds no HLO proto of, or one its proto
does not name, counts under ``(none)``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import Counter, defaultdict

from bench import trace

# The namespaces of the scopes the program puts on its ops
# (core/flymc.py: flymc.theta, flymc.z, flymc.z.candidates, flymc.z.delta,
# flymc.z.flips, flymc.refresh; api/algorithm.py: regular.theta;
# api/driver.py: driver.outputs, driver.fold). An op counts under the
# innermost name in one of them in its path.
NAMESPACES = ("flymc", "regular", "driver")
NONE = "(none)"
SPAN_PREFIXES = ("repro.", "bench.")

_WRAPPED = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\((.*)\)")
_SCOPE = re.compile(rf"(?:{'|'.join(NAMESPACES)})(?:\.[A-Za-z0-9_]+)+")


def scope_of(path):
    """The innermost scope in an op-name path, or NONE.

    A scope is a path component named ``<namespace>.<name>[.<name>...]``
    (NAMESPACES). It may be wrapped by transforms ("vmap(flymc.theta)",
    "transpose(jvp(flymc.theta.grad))"); the name inside counts.
    """
    found = NONE
    for part in (path or "").split("/"):
        while (m := _WRAPPED.fullmatch(part)) is not None:
            part = m.group(1)
        if _SCOPE.fullmatch(part):
            found = part
    return found


def scope_ns(ops, window):
    """{key: device ns} over ``window`` for ops [(name, start, end, key)],
    the key being an op's scope or any label. Only leaf ops count
    (:func:`bench.trace.leaves`), each for the part of its interval inside
    the window that no earlier-starting op covers, so the values sum to
    the window's busy time."""
    lo, hi = window
    out = defaultdict(float)
    cursor = lo
    for _, s, e, key in trace.leaves(ops):
        s, e = max(s, cursor), min(e, hi)
        if e > s:
            out[key] += e - s
            cursor = e
    return dict(out)


def idle_by_span(ops, spans, window):
    """{span name: idle ns} over ``window``: each stretch with no leaf op
    running counts under the innermost ``repro.*`` or ``bench.*`` host
    span covering its middle (NONE where none does)."""
    lo, hi = window
    busy = trace.union([(max(s, lo), min(e, hi))
                        for _, s, e, _ in trace.leaves(ops)])
    ours = [sp for sp in spans if sp[0].startswith(SPAN_PREFIXES)]
    out = defaultdict(float)
    cursor = lo
    for s, e in busy + [(hi, hi)]:
        if s > cursor:
            name = trace._innermost(ours, (cursor + s) / 2)
            out[NONE if name == "none" else name] += s - cursor
        cursor = max(cursor, e)
    return dict(out)


def _newest(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir):
    """({plane: [(name, start, end, (scope, inherited))]}, host spans,
    programs without an HLO proto) for each TPU plane of the newest
    ``.xplane.pb`` under trace_dir: the ops and spans of
    :func:`bench.trace.load`, each op with its scope and whether that
    scope was inferred (:func:`instruction_scopes`), and the names of the
    programs that ran but whose ops the profile cannot place."""
    devices, spans = trace.load(trace_dir)
    with open(_newest(trace_dir), "rb") as f:
        programs = {name: instruction_scopes(proto)
                    for name, proto in hlo_protos(f.read()).items()}
    out, no_proto = {}, set()
    for plane, (ops, modules) in devices.items():
        starts = sorted((s, name) for name, s, _ in modules)
        no_proto.update(name for _, name in starts if name not in programs)
        out[plane] = [(name, s, e, op_scope(name, s, starts, programs))
                      for name, s, e, _ in ops]
    return out, spans, sorted(no_proto)


def op_scope(name, start, modules, programs):
    """(scope, inherited) of the op ``name`` that starts at ``start``: its
    instruction's in the HLO proto of the program running then
    (``modules``, sorted [(start, program name)]); (NONE, False) where
    the profile holds no proto of that program or the proto no such
    instruction."""
    i = bisect.bisect_right(modules, (start, "\uffff")) - 1
    program = programs.get(modules[i][1]) if i >= 0 else None
    return (program or {}).get(trace.short_name(name), (NONE, False))


def reduce_trace(trace_dir):
    """The traced window of a recorded trace, by scope and by span.

    Returns, averaged over the TPU planes as :func:`bench.trace.reduce_trace`
    averages busy time: ``scope_ns`` {scope: device ns} (NONE included;
    the values sum to the busy time), ``inherited_ns`` {scope: the part of
    ``scope_ns`` whose scope was inferred, not named by the op's own
    metadata}, ``idle_by_span`` {span: idle ns} and ``no_proto``, the
    programs whose ops count under NONE for want of an HLO proto.
    """
    devices, spans, no_proto = load(trace_dir)
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    window = trace.window_of(spans)
    n = len(devices)
    scope, inherited, idle = (defaultdict(float) for _ in range(3))
    for ops in devices.values():
        for (name, inferred), ns in scope_ns(ops, window).items():
            scope[name] += ns / n
            if inferred:
                inherited[name] += ns / n
        for name, ns in idle_by_span(ops, spans, window).items():
            idle[name] += ns / n
    return {"scope_ns": dict(scope), "inherited_ns": dict(inherited),
            "idle_by_span": dict(idle), "no_proto": no_proto}


# ---- op-name metadata from the HLO protos a profile holds ------------------
# A few fields of protobuf messages, decoded here so that nothing beyond
# JAX is needed: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5 (maps: key 1, value 2); X{Event,Stat}Metadata.id = 1,
# .name = 2; XEventMetadata.stats = 5; XStat.metadata_id = 1,
# .bytes_value = 6; HloProto.hlo_module = 1; HloModuleProto.computations =
# 3; HloComputationProto.instructions = 2, .id = 5, .root_id = 6;
# HloInstructionProto.name = 1, .opcode = 2, .metadata = 7, .id = 35,
# .operand_ids = 36, .called_computation_ids = 38; OpMetadata.op_name = 2.


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of a serialized protobuf message:
    an int for a varint, a memoryview for a length-delimited field; fixed
    width fields are skipped."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield field, wire, value


def _ints(wire, value):
    """A repeated integer field's values, packed or not."""
    if wire == 0:
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def _map_values(fields, number):
    for f, _, entry in fields:
        if f == number:
            yield next((v for k, _, v in _fields(entry) if k == 2), b"")


def hlo_protos(xspace):
    """{program name: serialized HloProto} from a serialized XSpace: the
    "Hlo Proto" stats of its "/host:metadata" plane, one per program (named
    as the "XLA Modules" events are, "jit_chunk(12)")."""
    for f, _, plane in _fields(xspace):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for k, _, v in fields if k == 2), "")
        if name != "/host:metadata":
            continue
        stat_names = {}
        for meta in _map_values(fields, 5):
            m = {k: v for k, _, v in _fields(meta)}
            stat_names[m.get(1, 0)] = _text(m.get(2, b""))
        out = {}
        for meta in _map_values(fields, 4):
            event = list(_fields(meta))
            program = next((_text(v) for k, _, v in event if k == 2), "")
            for k, _, stat in event:
                if k != 5:
                    continue
                st = {j: v for j, _, v in _fields(stat)}
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    out[program] = bytes(st[6])
        return out
    return {}


def instruction_scopes(hlo_proto):
    """{instruction name: (scope, inherited)} of one program's serialized
    HloProto.

    An instruction with op-name metadata takes its innermost scope
    (NONE where it names none), and so does a fusion without any whose
    fused root has some: ``inherited`` False. Any other instruction's
    scope is inferred, ``inherited`` True: the commonest scope of the
    instructions it reads (a parameter reads nothing), else the commonest
    of its readers'; (NONE, False) where neither gives one.
    """
    module = next((v for f, _, v in _fields(hlo_proto) if f == 1), b"")
    instr, roots = {}, {}
    for f, _, comp in _fields(module):
        if f != 3:
            continue
        cid = root = 0  # proto3 writes no field that holds its default
        for g, _, v in _fields(comp):
            if g == 5:
                cid = v
            elif g == 6:
                root = v
            elif g == 2:
                rec = {"name": "", "opcode": "", "op_name": "",
                       "operands": [], "called": []}
                iid = 0
                for h, w, x in _fields(v):
                    if h == 1:
                        rec["name"] = _text(x)
                    elif h == 2:
                        rec["opcode"] = _text(x)
                    elif h == 7:
                        rec["op_name"] = next(
                            (_text(y) for k, _, y in _fields(x) if k == 2), "")
                    elif h == 35:
                        iid = x
                    elif h == 36:
                        rec["operands"] += _ints(w, x)
                    elif h == 38:
                        rec["called"] += _ints(w, x)
                instr[iid] = rec
        roots[cid] = root
    readers = defaultdict(list)
    for iid, rec in instr.items():
        for o in rec["operands"]:
            readers[o].append(iid)

    def commonest(values):
        counts = Counter(v for v in values if v is not None)
        return counts.most_common(1)[0][0] if counts else None

    def known(rec):
        """The scope the instruction's own metadata names, or None."""
        scope = scope_of(rec["op_name"])
        return None if scope == NONE else scope

    up_memo, down_memo = {}, {}

    def up(iid):
        """A scope from the instruction's own metadata or, without any, from
        what it reads (a fusion: its fused root first)."""
        if iid not in up_memo:
            rec = instr.get(iid)
            scope = None
            if rec is not None and rec["op_name"]:
                scope = known(rec)
            elif rec is not None:
                if rec["opcode"] == "fusion" and rec["called"]:
                    scope = up(roots.get(rec["called"][0]))
                scope = scope or commonest(up(o) for o in rec["operands"])
            up_memo[iid] = scope
        return up_memo[iid]

    def down(iid):
        """A scope from the instructions that read the instruction's data,
        through readers that have no metadata."""
        if iid not in down_memo:
            down_memo[iid] = commonest(
                known(instr[r]) if instr[r]["op_name"] else up(r) or down(r)
                for r in readers[iid])
        return down_memo[iid]

    def scope(iid, rec):
        if rec["op_name"]:
            return scope_of(rec["op_name"]), False
        if rec["opcode"] == "fusion" and rec["called"]:
            root = instr.get(roots.get(rec["called"][0]))
            if root is not None and root["op_name"]:
                return scope_of(root["op_name"]), False
        inferred = up(iid) or down(iid)
        return (inferred, True) if inferred else (NONE, False)

    return {rec["name"]: scope(iid, rec) for iid, rec in instr.items()}


# ---- the per-layer numbers these give --------------------------------------


def under(scope, root):
    """Whether ``scope`` is ``root`` or a scope named under it
    ("flymc.z.flips" is under "flymc.z"; "flymc.zeta" is not)."""
    return scope == root or scope.startswith(root + ".")


def phase_us(ctx, roots):
    """Device µs per traced chain-iteration in the scopes ``roots`` and
    those under them (:func:`under`), from a metric reader's ``ctx``; None
    where the run holds no scopes or none of these ran."""
    if ctx.scopes is None or ctx.traced["chain_iters"] <= 0:
        return None
    ns = [v for k, v in ctx.scopes["scope_ns"].items()
          if any(under(k, r) for r in roots)]
    if not ns:
        return None
    return sum(ns) * 1e-3 / ctx.traced["chain_iters"]
