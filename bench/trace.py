"""Reduction of a profiler trace to busy time, idle gaps and kernel time.

The profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain lists, and :func:`reduce` turns
those into numbers, so the arithmetic can be checked on a trace written
by hand (``bench/tests/test_trace.py``).

Conventions. Times are nanoseconds on the profiler's clock. The window is
the host span named ``bench.traced``. Device busy time is the union of the
intervals of the device's leaf operations (the "XLA Ops" line of each
``/device:TPU:<n>`` plane, without the ops that contain others, such as
the ``while`` of a scan, whose event spans its whole body) inside the
window: the time between two ops of a loop body, which the device spends
on control flow and waiting, counts as idle. An idle gap is a stretch of
the window with no operation running; it is named by the innermost host
event covering its middle, which is a ``bench.*`` span of the harness or
an event of the JAX runtime. The driver's boundary idle is the idle time
between the end of one chunk program (an "XLA Modules" event whose name
starts with ``jit_chunk``) and the start of the next.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.traced"
CHUNK_MODULE = "jit_chunk"


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _innermost(spans, t):
    """Name of the innermost span covering t (the latest-starting one)."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "none"


def leaves(ops):
    """The ops that contain no other op (by interval), in start order."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(ops)
    stack = []  # indices of ops whose interval is still open
    for i, (_, s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [op for op, p in zip(ops, parent) if not p]


def short_name(name):
    """An HLO op's name without its text: "%fusion.3 = f32[..] ..." -> "fusion.3"."""
    return name.split(" = ", 1)[0].lstrip("%")


_KERNEL_NAME = re.compile(r'kernel_name\W+([A-Za-z_][A-Za-z0-9_.]*)')


def _base(name):
    """A kernel's op name without batching prefixes, the trailing "_" and
    the ".N" suffix: "vmap_z_candidates_.7" -> "z_candidates"."""
    base = re.sub(r"\.\d+$", "", name)
    while base.startswith("vmap_"):
        base = base[len("vmap_"):]
    return base.rstrip("_")


def kernel_names(name, long_name=None):
    """The names an op answers to as a kernel: its own name
    ("%vmap_z_candidates_.7 = custom-call(...)" -> "z_candidates") and,
    for a custom call, its ``kernel_name`` attribute where the text gives
    one. An op that only names a kernel's output among its operands
    answers to neither."""
    names = {_base(short_name(name))}
    for text in (name, long_name or ""):
        if " custom-call(" in text:
            names.update(_base(m) for m in _KERNEL_NAME.findall(text))
    return names


def reduce(ops, modules, spans, window, kernels=(), top=10):
    """Numbers of one device over ``window`` = (start, end).

    ops      [(name, start, end, long_name)] device operations; busy time
             and the top ops count only the leaves (:func:`leaves`)
    modules  [(name, start, end)] device programs
    spans    [(name, start, end)] host events
    kernels  names to sum device time for: an op counts toward a kernel
             when it is the kernel's own op (:func:`kernel_names`), never
             because it names the kernel's output among its operands

    Returns busy_ns, window_ns, the boundary idle, per-kernel time, the
    ``top`` ops by summed time and the ``top`` longest idle gaps with the
    host event covering each.
    """
    lo, hi = window
    per_kernel = {k: 0.0 for k in kernels}
    for name, s, e, long_name in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            for k in kernel_names(name, long_name) & per_kernel.keys():
                per_kernel[k] += d
    ops = leaves(ops)
    busy = union(_clip([(s, e) for _, s, e, _ in ops], lo, hi))
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = e
    if cursor < hi:
        gaps.append((cursor, hi))
    by_op = defaultdict(float)
    for name, s, e, _ in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            by_op[short_name(name)] += d
    chunks = sorted((s, e) for name, s, e in modules
                    if name.startswith(CHUNK_MODULE))
    boundary = 0.0
    for (_, end), (start, _) in zip(chunks, chunks[1:]):
        between = _clip([(end, start)], lo, hi)
        if between:
            b0, b1 = between[0]
            boundary += (b1 - b0) - _length(_clip(busy, b0, b1))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_ns": float(_length(busy)),
        "window_ns": float(hi - lo),
        "boundary_idle_ns": float(boundary),
        "kernel_ns": per_kernel,
        "chunk_programs": len(chunks),
        "top_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
        "top_gaps": [(_innermost(spans, (s + e) / 2), float(e - s))
                     for s, e in gaps[:top]],
    }


def _stat(event, key):
    for name, value in getattr(event, "stats", ()) or ():
        if name == key:
            return value
    return None


def load(trace_dir):
    """(devices, spans) from the newest ``.xplane.pb`` under trace_dir.

    devices: {plane name: (ops, modules)} for each TPU plane;
    spans:   every host event [(name, start, end)].
    """
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.end_ns,
                             _stat(e, "long_name")) for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            devices[plane.name] = (ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return devices, spans


def window_of(spans):
    """(start, end) of the harness's traced window span."""
    hits = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not hits:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return hits[0]


def reduce_trace(trace_dir, kernels=()):
    """Reduce every TPU plane of a recorded trace over the traced window.

    Returns (per-device results, the first device's result with busy and
    kernel times averaged over the devices).
    """
    devices, spans = load(trace_dir)
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    window = window_of(spans)
    per = {name: reduce(ops, modules, spans, window, kernels)
           for name, (ops, modules) in sorted(devices.items())}
    first = dict(next(iter(per.values())))
    n = len(per)
    first["busy_ns"] = sum(r["busy_ns"] for r in per.values()) / n
    first["boundary_idle_ns"] = sum(r["boundary_idle_ns"]
                                    for r in per.values()) / n
    first["kernel_ns"] = {k: sum(r["kernel_ns"][k] for r in per.values()) / n
                          for k in kernels}
    return per, first
