"""Interval-domain abstract interpretation over Pallas kernel jaxprs.

The bounds analysis: prove that every dynamic ref index — ``get``/``swap``
NDIndexers, ``pl.dynamic_slice`` starts, and the HBM side of every
``dma_start`` — stays inside the ref it indexes, for every grid step.

The domain is the classic integer interval lattice ``[lo, hi]`` with
±inf. Sources of precision, in the order they matter for this repo's
kernels:

* ``program_id(axis)`` is ``[0, grid[axis] - 1]`` — the grid is static.
* scalar-prefetch operands carry the *outer* jaxpr's provenance: an index
  buffer that went through :func:`repro.kernels.common.clamp_index`
  (a ``clamp`` eqn against literal bounds) enters the kernel as
  ``[0, N - 1]``, which is exactly what makes the bright-GLM row DMA
  provable (see :mod:`repro.analysis.kernels.extract`).
* ``iota`` / ``broadcasted_iota`` are ``[0, dim - 1]``; shifts, adds,
  multiplies, min/max/clamp, and reductions have exact transfer functions.
* ``pl.when`` lowers to ``cond`` whose predicate we recognize when it is a
  conjunction of direct comparisons — the taken branch refines the
  compared operand (this proves the z-update's guarded row store:
  row ``r`` is only written under ``r < cand_rows``).
* ``fori_loop`` lowers to ``while``; carries are solved by a small inner
  fixpoint with widening, refined through the loop condition (this bounds
  the z-update's placement counter ``g ∈ [0, n_out - 1]``).

Mutable refs (accumulators, scratch) are handled by a store-join fixpoint
across whole-kernel passes with widening: each ref's abstract *content* is
the join of everything ever stored to it, reads see the join of prior-pass
content and same-pass stores so far. The z-update running count therefore
stabilizes at ``[0, +inf]`` — enough to prove the store's lower bound,
while its upper bound comes from the ``pl.when`` guard refinement.

Soundness posture: unknown primitives decay to the dtype's full range, so
missing transfer functions can only create false *positives* (an index we
fail to prove in-bounds), never false negatives. The one modeled
assumption is the sequential-grid scratch contract documented in
:mod:`repro.kernels.common` — first-step ``pl.when`` initialization is
assumed to precede reads, as it does under TPU's sequential grid.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax.extend.core as jex_core
import numpy as np

NEG_INF = float("-inf")
POS_INF = float("inf")


@dataclasses.dataclass(frozen=True)
class Interval:
    """Closed interval [lo, hi]; lo > hi encodes bottom (unreachable)."""

    lo: float
    hi: float

    @property
    def empty(self) -> bool:
        return self.lo > self.hi

    def join(self, o: "Interval") -> "Interval":
        if self.empty:
            return o
        if o.empty:
            return self
        return Interval(min(self.lo, o.lo), max(self.hi, o.hi))

    def meet(self, o: "Interval") -> "Interval":
        return Interval(max(self.lo, o.lo), min(self.hi, o.hi))

    def add(self, o: "Interval") -> "Interval":
        return Interval(self.lo + o.lo, self.hi + o.hi)

    def sub(self, o: "Interval") -> "Interval":
        return Interval(self.lo - o.hi, self.hi - o.lo)

    def neg(self) -> "Interval":
        return Interval(-self.hi, -self.lo)

    def mul(self, o: "Interval") -> "Interval":
        cands = [
            _mul(self.lo, o.lo), _mul(self.lo, o.hi),
            _mul(self.hi, o.lo), _mul(self.hi, o.hi),
        ]
        return Interval(min(cands), max(cands))

    def max_(self, o: "Interval") -> "Interval":
        return Interval(max(self.lo, o.lo), max(self.hi, o.hi))

    def min_(self, o: "Interval") -> "Interval":
        return Interval(min(self.lo, o.lo), min(self.hi, o.hi))

    def widen(self, newer: "Interval") -> "Interval":
        """Classic widening: any still-moving bound jumps to ±inf."""
        lo = self.lo if newer.lo >= self.lo else NEG_INF
        hi = self.hi if newer.hi <= self.hi else POS_INF
        return Interval(lo, hi)

    def __str__(self) -> str:
        def f(v):
            return str(int(v)) if math.isfinite(v) else (
                "-inf" if v < 0 else "+inf"
            )

        return f"[{f(self.lo)}, {f(self.hi)}]"


def _mul(a: float, b: float) -> float:
    if a == 0 or b == 0:
        return 0.0
    return a * b


TOP = Interval(NEG_INF, POS_INF)
BOOL = Interval(0, 1)


def dtype_interval(dtype) -> Interval:
    """The full range of a dtype — the decay value for unknown eqns."""
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return BOOL
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        return Interval(float(info.min), float(info.max))
    return TOP


def _aval_of(atom) -> Any:
    return getattr(atom, "aval", None)


def _is_ref(atom) -> bool:
    aval = _aval_of(atom)
    return aval is not None and "Ref" in type(aval).__name__


def literal_interval(value) -> Interval:
    arr = np.asarray(value)
    if arr.size == 0:
        return TOP
    if not np.issubdtype(arr.dtype, np.number) and arr.dtype != np.bool_:
        return TOP
    return Interval(float(arr.min()), float(arr.max()))


# Comparison refinements: in the TRUE branch of `op(lhs, rhs)`, what does
# lhs's interval become (given rhs's interval), and symmetrically for rhs.
_CMP_NEGATE = {"lt": "ge", "le": "gt", "gt": "le", "ge": "lt"}


def refine_cmp(op: str, iv: Interval, other: Interval, is_lhs: bool
               ) -> Interval:
    """Refine one side of a true comparison. Integer semantics (lt = le-1)
    are safe for floats too — every refined var in these kernels is int."""
    if not is_lhs:
        op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq",
              "ne": "ne"}.get(op, op)
    if op == "lt":
        return iv.meet(Interval(NEG_INF, other.hi - 1))
    if op == "le":
        return iv.meet(Interval(NEG_INF, other.hi))
    if op == "gt":
        return iv.meet(Interval(other.lo + 1, POS_INF))
    if op == "ge":
        return iv.meet(Interval(other.lo, POS_INF))
    if op == "eq":
        return iv.meet(other)
    return iv


_CMP_OPS = {"lt", "le", "gt", "ge", "eq", "ne"}

# Float-unary primitives whose output is nonnegative.
_NONNEG_UNARY = {"exp", "abs", "square", "sqrt", "exp2", "logistic"}

# Primitives that pass their (single) operand's interval through.
_PASSTHROUGH = {
    "reshape", "broadcast_in_dim", "squeeze", "expand_dims", "transpose",
    "copy", "rev", "stop_gradient", "reduce_precision", "slice",
    "real", "device_put",
}


class _RefStore:
    """Abstract contents of the kernel's refs, shared across scopes.

    Refs are aliased through sub-jaxpr boundaries (cond branches close over
    refs as invars), so contents are keyed by a canonical var resolved
    through ``alias``. ``content[r] is None`` means ⊥ — nothing stored yet.
    """

    def __init__(self):
        self.content: dict[Any, Interval | None] = {}
        self.alias: dict[Any, Any] = {}

    def canon(self, var):
        try:
            while var in self.alias:
                var = self.alias[var]
        except TypeError:  # Literals are unhashable; they are never refs
            pass
        return var

    @staticmethod
    def _hashable(var) -> bool:
        return not isinstance(var, jex_core.Literal)

    def declare(self, var, init: Interval | None):
        self.content[self.canon(var)] = init

    def is_ref(self, var) -> bool:
        if not self._hashable(var):
            return False
        return self.canon(var) in self.content

    def read(self, var) -> Interval:
        cur = self.content.get(self.canon(var))
        if cur is None:
            aval = _aval_of(var)
            return dtype_interval(getattr(aval, "dtype", np.float32))
        return cur

    def store(self, var, value: Interval):
        var = self.canon(var)
        cur = self.content.get(var)
        self.content[var] = value if cur is None else cur.join(value)

    def snapshot(self) -> dict:
        return dict(self.content)


@dataclasses.dataclass
class BoundsFinding:
    """One unprovable (or provably-escaping) ref index."""

    ref: str          # operand origin / scratch label
    eqn: str          # primitive that performed the access
    dim: int
    index: Interval
    valid: Interval   # [0, dim - span]
    proven_bad: bool  # interval provably escapes vs merely unprovable

    def message(self) -> str:
        kind = "escapes" if self.proven_bad else "is not provably inside"
        return (
            f"{self.eqn} index into {self.ref} dim {self.dim} has interval "
            f"{self.index}, which {kind} the valid range {self.valid}"
        )


class BoundsInterpreter:
    """Run the interval analysis over one extracted KernelCall."""

    MAX_PASSES = 4
    MAX_LOOP_ITERS = 4

    def __init__(self, call):
        self.call = call
        self.findings: list[BoundsFinding] = []
        self._seen: set = set()
        self.collect = False

    # -- driver --------------------------------------------------------------

    def run(self) -> list[BoundsFinding]:
        jaxpr = self.call.jaxpr
        carry: dict | None = None
        for pass_i in range(self.MAX_PASSES):
            refs = _RefStore()
            env: dict[Any, Interval] = {}
            preds: dict[Any, list] = {}
            for invar, op in zip(jaxpr.invars, self.call.operands):
                if _is_ref(invar):
                    init = op.interval
                    if carry is not None:
                        prev = carry.get(invar)
                        if prev is not None:
                            init = prev if init is None else init.join(prev)
                    refs.declare(invar, init)
                else:
                    env[invar] = op.interval or dtype_interval(
                        getattr(_aval_of(invar), "dtype", np.float32)
                    )
            self.collect = pass_i == self.MAX_PASSES - 1
            self._eval_eqns(jaxpr.eqns, env, refs, preds)
            snap = {refs.canon(v): c for v, c in refs.snapshot().items()}
            if carry is not None:
                widened = {}
                stable = True
                for var, cur in snap.items():
                    prev = carry.get(var)
                    if prev is None or cur is None:
                        widened[var] = cur if prev is None else prev
                        stable = stable and prev == cur
                    elif pass_i >= 2:
                        widened[var] = prev.widen(cur)
                        stable = stable and widened[var] == prev
                    else:
                        widened[var] = prev.join(cur)
                        stable = stable and widened[var] == prev
                snap = widened
                if stable and not self.collect:
                    # Converged early: do one final collecting pass.
                    self.collect = True
                    refs2 = _RefStore()
                    env2: dict[Any, Interval] = {}
                    for invar, op in zip(jaxpr.invars, self.call.operands):
                        if _is_ref(invar):
                            refs2.declare(invar, snap.get(invar))
                        else:
                            env2[invar] = op.interval or dtype_interval(
                                getattr(_aval_of(invar), "dtype", np.float32)
                            )
                    self._eval_eqns(jaxpr.eqns, env2, refs2, {})
                    return self.findings
            carry = snap
        return self.findings

    # -- helpers -------------------------------------------------------------

    def _ival(self, atom, env) -> Interval:
        if isinstance(atom, jex_core.Literal):
            return literal_interval(atom.val)
        if atom in env:
            return env[atom]
        return dtype_interval(getattr(_aval_of(atom), "dtype", np.float32))

    def _ref_name(self, var, refs) -> str:
        var = refs.canon(var)
        jaxpr = self.call.jaxpr
        for invar, op in zip(jaxpr.invars, self.call.operands):
            if invar is var:
                return op.origin
        return "<local ref>"

    def _check_index(self, refs, ref_var, eqn_name, dim, span, iv: Interval):
        if not self.collect or iv.empty:
            return
        valid = Interval(0, dim - span)
        if iv.lo >= 0 and iv.hi <= dim - span:
            return
        key = (self._ref_name(ref_var, refs), eqn_name, dim,
               (iv.lo, iv.hi))
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(BoundsFinding(
            ref=key[0], eqn=eqn_name, dim=dim, index=iv, valid=valid,
            proven_bad=iv.hi < 0 or iv.lo > dim - span,
        ))

    def _check_indexer(self, refs, ref_var, eqn_name, shape, indexer, env):
        """Check one NDIndexer against ``shape``; return the result shape."""
        out_shape = []
        indices = getattr(indexer, "indices", None)
        if indices is None:
            return tuple(shape)
        for dim_i, idx in enumerate(indices):
            dim = shape[dim_i] if dim_i < len(shape) else 1
            if hasattr(idx, "size") and hasattr(idx, "start"):  # pl.Slice
                size = int(idx.size)
                stride = int(getattr(idx, "stride", 1) or 1)
                start = idx.start
                if isinstance(start, (int, np.integer)):
                    s_iv = Interval(float(start), float(start))
                else:
                    s_iv = self._ival(start, env)
                span = (size - 1) * stride + 1
                self._check_index(refs, ref_var, eqn_name, dim, span, s_iv)
                out_shape.append(size)
            elif isinstance(idx, (int, np.integer)):
                self._check_index(refs, ref_var, eqn_name, dim, 1,
                                  Interval(float(idx), float(idx)))
            else:  # dynamic scalar or advanced (array) index
                iv = self._ival(idx, env)
                self._check_index(refs, ref_var, eqn_name, dim, 1, iv)
                idx_shape = tuple(getattr(_aval_of(idx), "shape", ()) or ())
                out_shape.extend(idx_shape)
        out_shape.extend(shape[len(indices):])
        return tuple(out_shape)

    def _indexers_of(self, tree, flat):
        """Unflatten a state-primitive transforms tree; yield NDIndexers."""
        try:
            import jax.tree_util as jtu

            transforms = jtu.tree_unflatten(tree, list(flat))
        except Exception:
            return []
        out = []

        def walk(obj):
            if hasattr(obj, "indices") and hasattr(obj, "shape"):
                out.append(obj)
            elif isinstance(obj, (list, tuple)):
                for item in obj:
                    walk(item)

        walk(transforms)
        return out

    # -- the interpreter -----------------------------------------------------

    def _eval_eqns(self, eqns, env, refs, preds):
        for eqn in eqns:
            self._eval_eqn(eqn, env, refs, preds)

    def _default_out(self, eqn, env):
        for ov in eqn.outvars:
            env[ov] = dtype_interval(
                getattr(_aval_of(ov), "dtype", np.float32)
            )

    def _eval_eqn(self, eqn, env, refs, preds):
        name = eqn.primitive.name
        params = eqn.params
        iv = lambda i: self._ival(eqn.invars[i], env)

        def pred_of(atom):
            if isinstance(atom, jex_core.Literal):
                return None
            return preds.get(atom)

        def out(value: Interval, pred=None):
            env[eqn.outvars[0]] = value
            if pred is not None:
                preds[eqn.outvars[0]] = pred

        if name == "program_id":
            axis = int(params.get("axis", 0))
            grid = self.call.grid
            hi = grid[axis] - 1 if axis < len(grid) else 0
            out(Interval(0, float(max(hi, 0))))
        elif name == "num_programs":
            axis = int(params.get("axis", 0))
            grid = self.call.grid
            n = grid[axis] if axis < len(grid) else 1
            out(Interval(float(n), float(n)))
        elif name == "iota":
            dim = int(params.get("dimension", 0))
            shape = params.get("shape") or getattr(
                _aval_of(eqn.outvars[0]), "shape", (1,)
            )
            out(Interval(0, float(max(int(shape[dim]) - 1, 0))))
        elif name == "add":
            out(iv(0).add(iv(1)))
        elif name == "sub":
            out(iv(0).sub(iv(1)))
        elif name == "mul":
            out(iv(0).mul(iv(1)))
        elif name == "neg":
            out(iv(0).neg())
        elif name == "max":
            out(iv(0).max_(iv(1)))
        elif name == "min":
            out(iv(0).min_(iv(1)))
        elif name == "clamp":  # clamp(lo, x, hi)
            lo, x, hi = iv(0), iv(1), iv(2)
            out(x.max_(lo).min_(hi))
        elif name in ("div", "floor_divide"):
            q = self._div(iv(0), iv(1))
            dtype = getattr(_aval_of(eqn.outvars[0]), "dtype", np.float32)
            if np.issubdtype(dtype, np.integer) and q is not TOP:
                # Integer division rounds (monotonically) to an integer.
                rnd = math.trunc if name == "div" else math.floor
                whole = lambda v: v if math.isinf(v) else float(rnd(v))
                q = Interval(whole(q.lo), whole(q.hi))
            out(q)
        elif name == "rem":
            out(self._rem(iv(0), iv(1)))
        elif name == "convert_element_type":
            tgt = dtype_interval(params.get("new_dtype", np.float32))
            out(iv(0).meet(tgt) if not iv(0).empty else tgt,
                pred=pred_of(eqn.invars[0]))
        elif name in _PASSTHROUGH:
            out(iv(0), pred=pred_of(eqn.invars[0]))
        elif name == "concatenate":
            acc = self._ival(eqn.invars[0], env)
            for a in eqn.invars[1:]:
                acc = acc.join(self._ival(a, env))
            out(acc)
        elif name == "pad":
            out(iv(0).join(iv(1)))
        elif name == "select_n":
            acc = self._ival(eqn.invars[1], env)
            for a in eqn.invars[2:]:
                acc = acc.join(self._ival(a, env))
            out(acc)
        elif name in _CMP_OPS:
            out(BOOL, pred=[(name, eqn.invars[0], eqn.invars[1])])
        elif name == "and":
            p = (pred_of(eqn.invars[0]) or []) + (pred_of(eqn.invars[1]) or [])
            out(BOOL, pred=p or None)
        elif name in ("or", "not", "xor", "is_finite"):
            aval = _aval_of(eqn.outvars[0])
            out(BOOL if np.dtype(getattr(aval, "dtype", np.bool_))
                == np.bool_ else dtype_interval(aval.dtype))
        elif name == "shift_right_logical":
            rhs = iv(1)
            aval = _aval_of(eqn.invars[0])
            nbits = np.dtype(getattr(aval, "dtype", np.int32)).itemsize * 8
            if rhs.lo == rhs.hi and math.isfinite(rhs.lo):
                out(Interval(0, float(2 ** (nbits - int(rhs.lo)) - 1)))
            else:
                out(Interval(0, float(2 ** nbits - 1)))
        elif name in ("shift_left", "shift_right_arithmetic"):
            self._default_out(eqn, env)
        elif name == "reduce_sum":
            axes = params.get("axes", ())
            shape = tuple(getattr(_aval_of(eqn.invars[0]), "shape", ()) or ())
            n = 1
            for a in axes:
                if a < len(shape):
                    n *= int(shape[a])
            x = iv(0)
            out(Interval(_mul(n, min(x.lo, 0.0)) if x.lo < 0 else n * x.lo,
                         _mul(n, x.hi) if x.hi > 0 else x.hi))
        elif name in ("reduce_max", "reduce_min", "cummax", "cummin"):
            out(iv(0))
        elif name in ("reduce_and", "reduce_or"):
            out(BOOL)
        elif name in ("argmax", "argmin"):
            axes = params.get("axes", (0,))
            shape = tuple(getattr(_aval_of(eqn.invars[0]), "shape", ()) or ())
            hi = max((int(shape[a]) - 1 for a in axes if a < len(shape)),
                     default=0)
            out(Interval(0, float(hi)))
        elif name in _NONNEG_UNARY:
            out(Interval(0, POS_INF))
        elif name == "get":
            self._eval_get(eqn, env, refs)
        elif name == "swap":
            self._eval_swap(eqn, env, refs, preds)
        elif name in ("addupdate",):
            self._eval_swap(eqn, env, refs, preds, accumulate=True)
        elif name == "dma_start":
            self._eval_dma(eqn, env, refs)
        elif name in ("dma_wait", "semaphore_signal", "semaphore_wait"):
            pass
        elif name == "dynamic_slice":
            operand = eqn.invars[0]
            shape = tuple(getattr(_aval_of(operand), "shape", ()) or ())
            sizes = params.get("slice_sizes", ())
            for d, (dim, size) in enumerate(zip(shape, sizes)):
                start = self._ival(eqn.invars[1 + d], env)
                # clamped semantics in XLA, but Pallas lowers unclamped —
                # hold kernels to the strict contract
                self._check_index(refs, operand, name, dim, int(size), start) \
                    if refs.is_ref(operand) else None
            out(iv(0))
        elif name == "cond":
            self._eval_cond(eqn, env, refs, preds)
        elif name == "while":
            self._eval_while(eqn, env, refs, preds)
        elif name == "scan":
            self._eval_scan(eqn, env, refs)
        elif name in ("pjit", "closed_call", "core_call", "remat",
                      "checkpoint", "custom_jvp_call", "custom_vjp_call",
                      "custom_vmap_call"):
            self._eval_call(eqn, env, refs, preds)
        elif name == "dot_general":
            self._default_out(eqn, env)
        else:
            self._default_out(eqn, env)

    @staticmethod
    def _div(a: Interval, b: Interval) -> Interval:
        if b.lo <= 0 <= b.hi:
            return TOP
        cands = []
        for x in (a.lo, a.hi):
            for y in (b.lo, b.hi):
                if math.isinf(x) and math.isinf(y):
                    cands.extend([-1.0, 1.0])
                elif math.isinf(y):
                    cands.append(0.0)
                else:
                    cands.append(x / y)
        return Interval(min(cands), max(cands))

    @staticmethod
    def _rem(a: Interval, b: Interval) -> Interval:
        if b.lo == b.hi and math.isfinite(b.lo) and b.lo > 0:
            m = b.lo
            if a.lo >= 0:
                return Interval(0, min(a.hi, m - 1))
            return Interval(-(m - 1), m - 1)
        return TOP

    def _eval_get(self, eqn, env, refs):
        ref = eqn.invars[0]
        shape = tuple(getattr(_aval_of(ref), "shape", ()) or ())
        for idxr in self._indexers_of(eqn.params.get("tree"),
                                      eqn.invars[1:]):
            shape = self._check_indexer(refs, ref, "get", shape, idxr, env)
        env[eqn.outvars[0]] = refs.read(ref)

    def _eval_swap(self, eqn, env, refs, preds, accumulate=False):
        ref, val = eqn.invars[0], eqn.invars[1]
        shape = tuple(getattr(_aval_of(ref), "shape", ()) or ())
        for idxr in self._indexers_of(eqn.params.get("tree"),
                                      eqn.invars[2:]):
            shape = self._check_indexer(refs, ref, "swap", shape, idxr, env)
        stored = self._ival(val, env)
        if accumulate:
            stored = stored.add(refs.read(ref))
        refs.store(ref, stored)
        for ov in eqn.outvars:
            env[ov] = refs.read(ref)

    def _eval_dma(self, eqn, env, refs):
        """dma_start: check every NDIndexer against the ref it transforms."""
        try:
            import jax.tree_util as jtu

            tree = eqn.params.get("tree")
            structure = jtu.tree_unflatten(tree, list(eqn.invars))
        except Exception:
            return
        items = list(structure) if isinstance(structure, (tuple, list)) \
            else [structure]
        cur_ref = None
        src_ref = None
        dst_ref = None
        for item in items:
            if _is_ref(item) and not isinstance(item, (tuple, list)):
                cur_ref = item
                if src_ref is None:
                    src_ref = item
                elif dst_ref is None and "Semaphore" not in str(
                    _aval_of(item)
                ):
                    dst_ref = item
            elif cur_ref is not None:
                shape = tuple(getattr(_aval_of(cur_ref), "shape", ()) or ())
                for idxr in self._indexers_of_value(item):
                    shape = self._check_indexer(
                        refs, cur_ref, "dma_start", shape, idxr, env
                    )
        if dst_ref is not None and refs.is_ref(dst_ref):
            refs.store(dst_ref, refs.read(src_ref) if src_ref is not None
                       and refs.is_ref(src_ref) else
                       dtype_interval(getattr(_aval_of(dst_ref), "dtype",
                                              np.float32)))

    @staticmethod
    def _indexers_of_value(value):
        out = []

        def walk(obj):
            if hasattr(obj, "indices") and hasattr(obj, "shape"):
                out.append(obj)
            elif isinstance(obj, (list, tuple)):
                for item in obj:
                    walk(item)

        walk(value)
        return out

    def _refined_env(self, constraints, operands, inner_vars, env, truth):
        """Env for a cond branch: operand intervals, refined by the pred."""
        inner_env = {}
        for outer, inner in zip(operands, inner_vars):
            inner_env[inner] = self._ival(outer, env)
        if not constraints:
            return inner_env
        for op, lhs, rhs in constraints:
            use_op = op
            if not truth:
                if len(constraints) > 1 or op not in _CMP_NEGATE:
                    continue  # ¬(a ∧ b) is a disjunction — no refinement
                use_op = _CMP_NEGATE[op]
            lhs_iv = self._ival(lhs, env)
            rhs_iv = self._ival(rhs, env)
            for outer, inner in zip(operands, inner_vars):
                if outer is lhs:
                    inner_env[inner] = refine_cmp(
                        use_op, inner_env[inner], rhs_iv, True
                    )
                elif outer is rhs:
                    inner_env[inner] = refine_cmp(
                        use_op, inner_env[inner], lhs_iv, False
                    )
        return inner_env

    def _eval_cond(self, eqn, env, refs, preds):
        branches = eqn.params.get("branches", ())
        operands = list(eqn.invars[1:])
        constraints = preds.get(eqn.invars[0], [])
        joined: list[Interval] | None = None
        for b_i, closed in enumerate(branches):
            body = closed.jaxpr
            if len(body.invars) != len(operands):
                continue
            truth = (b_i == len(branches) - 1) if len(branches) == 2 \
                else None
            inner_env = self._refined_env(
                constraints if truth is not None else [],
                operands, body.invars, env, bool(truth),
            )
            for outer, inner in zip(operands, body.invars):
                if refs.is_ref(outer):
                    refs.alias[inner] = refs.canon(outer)
            inner_preds: dict[Any, list] = {}
            self._eval_eqns(body.eqns, inner_env, refs, inner_preds)
            outs = [
                self._ival(ov, inner_env)
                if not isinstance(ov, jex_core.Literal)
                else literal_interval(ov.val)
                for ov in body.outvars
            ]
            joined = outs if joined is None else [
                a.join(b) for a, b in zip(joined, outs)
            ]
        for i, ov in enumerate(eqn.outvars):
            env[ov] = joined[i] if joined and i < len(joined) else \
                dtype_interval(getattr(_aval_of(ov), "dtype", np.float32))

    def _cond_constraints(self, cond_jaxpr, cnc):
        """Constraints the loop condition imposes on carry positions."""
        body = cond_jaxpr.jaxpr
        local_preds: dict[Any, list] = {}
        pos_of = {v: i - cnc for i, v in enumerate(body.invars) if i >= cnc}
        for eqn in body.eqns:
            name = eqn.primitive.name
            if name in _CMP_OPS:
                local_preds[eqn.outvars[0]] = [
                    (name, eqn.invars[0], eqn.invars[1])
                ]
            elif name == "and":
                local_preds[eqn.outvars[0]] = (
                    local_preds.get(eqn.invars[0], [])
                    + local_preds.get(eqn.invars[1], [])
                )
            elif name == "convert_element_type" and eqn.invars[0] in \
                    local_preds:
                local_preds[eqn.outvars[0]] = local_preds[eqn.invars[0]]
        outv = body.outvars[0]
        out = []
        for op, lhs, rhs in local_preds.get(outv, []):
            lhs_pos = pos_of.get(lhs)
            rhs_pos = pos_of.get(rhs)
            out.append((op, lhs, lhs_pos, rhs, rhs_pos))
        return out

    def _eval_while(self, eqn, env, refs, preds):
        params = eqn.params
        cnc = params.get("cond_nconsts", 0)
        bnc = params.get("body_nconsts", 0)
        cond_jaxpr = params["cond_jaxpr"]
        body = params["body_jaxpr"].jaxpr
        cond_consts = eqn.invars[:cnc]
        body_consts = eqn.invars[cnc:cnc + bnc]
        init = eqn.invars[cnc + bnc:]
        carry = [self._ival(a, env) for a in init]
        constraints = self._cond_constraints(cond_jaxpr, cnc)

        def const_ival(atom, consts, jaxpr_invars):
            if isinstance(atom, jex_core.Literal):
                return literal_interval(atom.val)
            for outer, inner in zip(consts, jaxpr_invars):
                if inner is atom:
                    return self._ival(outer, env)
            return None

        def refine_carry(c):
            refined = list(c)
            for op, lhs, lhs_pos, rhs, rhs_pos in constraints:
                lhs_iv = refined[lhs_pos] if lhs_pos is not None else \
                    const_ival(lhs, cond_consts, cond_jaxpr.jaxpr.invars)
                rhs_iv = refined[rhs_pos] if rhs_pos is not None else \
                    const_ival(rhs, cond_consts, cond_jaxpr.jaxpr.invars)
                if lhs_pos is not None and rhs_iv is not None:
                    refined[lhs_pos] = refine_cmp(
                        op, refined[lhs_pos], rhs_iv, True
                    )
                if rhs_pos is not None and lhs_iv is not None:
                    refined[rhs_pos] = refine_cmp(
                        op, refined[rhs_pos], lhs_iv, False
                    )
            return refined

        for it in range(self.MAX_LOOP_ITERS):
            body_env: dict[Any, Interval] = {}
            for outer, inner in zip(body_consts, body.invars[:bnc]):
                body_env[inner] = self._ival(outer, env)
                if refs.is_ref(outer):
                    refs.alias[inner] = refs.canon(outer)
            refined = refine_carry(carry)
            for c_iv, inner in zip(refined, body.invars[bnc:]):
                body_env[inner] = c_iv
            inner_preds: dict[Any, list] = {}
            self._eval_eqns(body.eqns, body_env, refs, inner_preds)
            outs = [
                literal_interval(ov.val)
                if isinstance(ov, jex_core.Literal)
                else self._ival(ov, body_env)
                for ov in body.outvars
            ]
            new = [a.join(b) for a, b in zip(carry, outs)]
            if it >= 1:
                new = [a.widen(b) for a, b in zip(carry, new)]
            if new == carry:
                break
            carry = new
        for ov, c_iv in zip(eqn.outvars, carry):
            env[ov] = c_iv

    def _eval_scan(self, eqn, env, refs):
        params = eqn.params
        body = params["jaxpr"].jaxpr
        nc = params.get("num_consts", 0)
        body_env: dict[Any, Interval] = {}
        for outer, inner in zip(eqn.invars[:nc], body.invars[:nc]):
            body_env[inner] = self._ival(outer, env)
            if refs.is_ref(outer):
                refs.alias[inner] = refs.canon(outer)
        for inner in body.invars[nc:]:
            body_env[inner] = dtype_interval(
                getattr(_aval_of(inner), "dtype", np.float32)
            )
        for _ in range(2):
            self._eval_eqns(body.eqns, dict(body_env), refs, {})
        self._default_out(eqn, env)

    def _eval_call(self, eqn, env, refs, preds):
        for value in eqn.params.values():
            subs = []
            if isinstance(value, jex_core.ClosedJaxpr):
                subs = [value.jaxpr]
            elif isinstance(value, jex_core.Jaxpr):
                subs = [value]
            for sub in subs:
                if len(sub.invars) != len(eqn.invars):
                    continue
                inner_env = {}
                for outer, inner in zip(eqn.invars, sub.invars):
                    inner_env[inner] = self._ival(outer, env)
                    if not isinstance(outer, jex_core.Literal) and \
                            refs.is_ref(outer):
                        refs.alias[inner] = refs.canon(outer)
                inner_preds: dict[Any, list] = {}
                self._eval_eqns(sub.eqns, inner_env, refs, inner_preds)
                for ov, sub_ov in zip(eqn.outvars, sub.outvars):
                    env[ov] = (
                        literal_interval(sub_ov.val)
                        if isinstance(sub_ov, jex_core.Literal)
                        else self._ival(sub_ov, inner_env)
                    )
                return
        self._default_out(eqn, env)


def check_bounds(call) -> list[BoundsFinding]:
    """All bounds findings for one extracted KernelCall."""
    return BoundsInterpreter(call).run()
