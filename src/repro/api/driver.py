"""Device-resident multi-chain sampling driver.

The chain lives on device end to end, and the chain axis is carried
NATIVELY: a multi-chain run is one chunked ``lax.scan`` whose carry is the
chain-stacked state and whose body applies a chain-batched step — not a
``vmap`` of per-chain scans. Batching the step batches its kernels: the
Pallas kernels coalesce the chain axis into a leading kernel-grid
dimension (one launch for all chains — ``custom_vmap`` rules in
``kernels/*/ops``). Algorithms that provide ``step_chains`` (e.g. the
distributed chain fleet, which shard_maps the chain axis) are dispatched
directly; for the rest the driver batches ``alg.step`` itself.
Each chunk of ``chunk_size`` iterations is one jitted scan, and the only
host synchronization is a single overflow-flag read per chunk. Output is
produced by :mod:`repro.api.collectors` — pure ``(init, update, finalize)``
reductions whose carries thread through the scan, so memory is
O(what-you-ask-for): the default :class:`~repro.api.collectors.FullTrace`
materializes the dense trajectory exactly as before, while a collectors-only
call (online moments, split-R̂, query accounting, …) allocates nothing that
scales with ``num_samples`` — zero per-iteration ``device_get``s, unlike the
legacy host loop (~4 syncs/step).

Exactness under bounded buffers (DESIGN.md §3.1) is preserved at chunk
granularity: the pre-chunk state is kept alive, and if any step in the chunk
overflowed its bright/candidate capacity, the *whole chunk* is re-run from
that saved state with doubled capacities and the identical per-iteration RNG
keys (``fold_in(chain_key, iteration)``), so the realized chain is bitwise
the one an infinite-capacity sampler would have produced. Collector carries
only ever fold *committed* chunks (the fold runs after the overflow check
passes), so every streamed reduction is bitwise capacity/chunk-invariant
too — with no carry rollback needed.

Tracing: the host's work per chunk runs under the :data:`SPANS`, which a
``jax.profiler`` trace records beside the device's ops, and is counted,
always, in :class:`DriverCounters` (``ChunkEvent.driver``,
``Trace.driver``). The chunk program's ops carry named scopes: the step's
phases (``flymc.*``, ``regular.theta``), ``driver.outputs`` and, in the
fold program, ``driver.fold``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import OrderedDict
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import collectors as collectors_lib
from repro.api.algorithm import SamplingAlgorithm
from repro.core.flymc import StepStats
from repro.kernels import common as kernels_common


# jit cache for the driver's chunk functions, keyed on the algorithm's
# stable function identities plus ``(num_chains, chunk_size, capacity)``
# (and the collector set / chain-batching flag where they shape the trace):
# repeated sample() calls on the same algorithm reuse compiled chunk/init
# executables, and a capacity-doubling overflow re-run re-traces ONLY the
# chain scan at the grown capacity — the committed-chunk fold is keyed
# capacity-independently (chunk outputs are O(cs) θ/stats, no buffer-shaped
# operands), so an overflow retry never recompiles it. Collectors hash by
# identity, so reusing collector instances across calls is what makes the
# cache hit. LRU-bounded: entries keep the algorithm's closed-over data
# arrays alive, so stale algorithms must age out (and hot ones must not be
# mass-evicted).
_JIT_CACHE: OrderedDict = OrderedDict()
_JIT_CACHE_MAX = 64

# The back-compat default collector set: one shared instance so repeated
# sample() calls without collectors= hit the same compiled chunk fn.
_DEFAULT_TRACE = collectors_lib.FullTrace()

# Host spans of a sample() call (jax.profiler.TraceAnnotation), on the
# profiler's clock with the device's ops: set-up, then per chunk the call
# into the chunk program (a retrace or compile lands here), the overflow
# read (the host waiting on the device), the grow-and-resize before an
# overflow re-run, the collector fold and the on_chunk hook, then the
# results.
SPANS = (
    SPAN_INIT, SPAN_DISPATCH, SPAN_WAIT, SPAN_REGROW, SPAN_FOLD,
    SPAN_ON_CHUNK, SPAN_FINALIZE,
) = (
    "repro.sample.init", "repro.sample.dispatch", "repro.sample.wait",
    "repro.sample.regrow", "repro.sample.fold", "repro.sample.on_chunk",
    "repro.sample.finalize",
)


@dataclasses.dataclass
class DriverCounters:
    """Cumulative host-side counts of one sample() call's chunk loop.

    chunks       committed chunks
    reruns       chunk re-runs after a capacity overflow
    rerun_iters  chain-iterations thrown away by those re-runs
    dispatch_s   seconds calling into the chunk program (asynchronous: the
                 host enqueues, unless it traces or compiles)
    wait_s       seconds reading the overflow flag: the host waiting on the
                 device
    regrow_s     seconds growing the algorithm and resizing the state
    fold_s       seconds dispatching the collector fold
    hook_s       seconds in the on_chunk hook
    """

    chunks: int = 0
    reruns: int = 0
    rerun_iters: int = 0
    dispatch_s: float = 0.0
    wait_s: float = 0.0
    regrow_s: float = 0.0
    fold_s: float = 0.0
    hook_s: float = 0.0


@contextlib.contextmanager
def _timed(counters: DriverCounters, field: str, span: str):
    """Run the block under host span ``span``; add its seconds to
    ``counters.<field>``."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(span):
            yield
    finally:
        setattr(counters, field,
                getattr(counters, field) + time.perf_counter() - t0)


def _cached(key, build):
    fn = _JIT_CACHE.get(key)
    if fn is None:
        while len(_JIT_CACHE) >= _JIT_CACHE_MAX:
            _JIT_CACHE.popitem(last=False)
        fn = _JIT_CACHE[key] = build()
    else:
        _JIT_CACHE.move_to_end(key)
    return fn


def cached_jit(key, build):
    """The driver's LRU jit cache, for layers that extend the driver.

    ``repro.serve``'s group engines key their chain-scan executables here so
    a service restart (or an engine torn down and repacked after device
    loss) re-enters a warm cache instead of recompiling — the same policy,
    same LRU, same eviction as the driver's own chunk functions.
    """
    return _cached(key, build)


class NonFiniteError(RuntimeError):
    """A chunk produced non-finite chain state (NaN/Inf in θ, log-joint, or
    the δ cache). Raised at the chunk boundary BEFORE the fold, so the
    collector carries still hold the last healthy committed prefix.

    Non-finiteness must be trapped, not tolerated: a NaN'd proposal
    log-ratio compares False, so a poisoned chain can keep "running" —
    always rejecting, θ frozen or silently diverged from its law — while
    every summary statistic still looks plausible. The serve engines run
    the same predicate per lane and quarantine just the sick lane
    (:meth:`repro.serve.engine.GroupEngine.run_chunk`).
    """


def finite_lanes(arrays, lane_axis: int = 0):
    """Per-lane all-finite mask over floating-point ``arrays`` sharing a
    common ``lane_axis``: a lane is healthy iff every float entry of every
    array is finite. Non-float arrays are ignored (counters, flags, int
    z-partitions cannot go NaN). Returns a bool vector over the lane axis,
    or None if no array is floating-point. Pure jnp — usable inside jit
    (the serve chunk computes it on-device so health rides the existing
    per-chunk host sync instead of adding one)."""
    ok = None
    for a in arrays:
        if not jnp.issubdtype(a.dtype, jnp.floating):
            continue
        lanes = jnp.moveaxis(a, lane_axis, 0)
        this = jnp.all(
            jnp.isfinite(lanes.reshape(lanes.shape[0], -1)), axis=1
        )
        ok = this if ok is None else (ok & this)
    return ok


class Trace(NamedTuple):
    """Everything one `sample()` call produced.

    theta         : (num_chains, num_samples // thin, *theta_shape) — the
                    ``theta[thin - 1 :: thin]`` slice of the per-iteration
                    trajectory, i.e. entry ``i`` is iteration
                    ``(i + 1)·thin - 1`` (the LAST iteration of each thin
                    window, not the first), and a trailing partial window
                    contributes nothing. None when ``collectors=`` was given
                    (ask for a FullTrace/ThinnedTrace collector instead).
    stats         : StepStats with (num_chains, num_samples) leaves
                    (unthinned); None when ``collectors=`` was given
    total_queries : int — total per-datum likelihood evaluations, all chains
                    (an int64 total: per-step counts are int32 and would wrap
                    at paper scale, e.g. N=1.8M × slice × 1200 iters ≈ 2.6e10
                    > 2^31). From the on-device QueryBudget collector when one
                    was passed; from a host-side sum over materialized stats
                    on the default path; None otherwise.
    final_state   : chain state pytree (leading chain axis iff num_chains > 1),
                    suitable for resuming via sample(..., init_state=...)
    algorithm     : the (possibly capacity-grown) SamplingAlgorithm
    results       : {name: finalized result} for the ``collectors=`` dict
                    passed in; None on the default (FullTrace) path
    driver        : the call's final :class:`DriverCounters`
    """

    theta: jax.Array | None
    stats: StepStats | None
    total_queries: Any
    final_state: Any
    algorithm: SamplingAlgorithm
    results: dict | None = None
    driver: DriverCounters | None = None


def _broadcast_positions(position, num_chains: int, reference):
    """Give every chain a starting position: accepts one position (shared)
    or a pytree with a leading (num_chains, ...) axis. ``reference`` (the
    algorithm's default position) disambiguates the two when shapes collide."""
    shape_of = lambda tree: jax.tree.map(jnp.shape, tree)
    if reference is not None and shape_of(position) == shape_of(reference):
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l, (num_chains,) + jnp.shape(l)),
            position,
        )
    leaves = jax.tree.leaves(position)
    if leaves and all(
        hasattr(l, "shape") and l.shape[:1] == (num_chains,) for l in leaves
    ):
        return position
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l, (num_chains,) + jnp.shape(l)), position
    )


def _identity(state):
    return state


def _capacity_of(alg: SamplingAlgorithm):
    spec = alg.spec
    return (getattr(spec, "capacity", None), getattr(spec, "cand_capacity", None))


def _threads_data(alg: SamplingAlgorithm) -> bool:
    """Whether the chunk scan takes the dataset as a traced operand.

    True for algorithms providing the ``step_data`` form (and no custom
    ``step_chains`` dispatch, which owns its own data placement). The
    operand form is shared bit-for-bit with the :mod:`repro.serve` group
    engines — baking the dataset in as a jit constant changes XLA's
    low-bit rounding of the likelihood reductions, so the form is part of
    the exactness contract, not an implementation detail.
    """
    return (
        alg.step_data is not None
        and alg.data is not None
        and alg.step_chains is None
    )


def _threads_data_chains(alg: SamplingAlgorithm) -> bool:
    """Whether a MULTI-chain chunk scan takes the dataset as an operand via
    the algorithm's own chain-batched dispatch (``step_chains_data``, e.g.
    the distributed fleet's shard_map with replicated data). Same exactness
    rationale as :func:`_threads_data`; this form wins over it when both
    are available and ``num_chains > 1``."""
    return alg.step_chains_data is not None and alg.data is not None


def _make_scan_fn(alg: SamplingAlgorithm, num_chains: int, cs: int):
    """One jitted chunk of the chain: cs steps, carrying the chain-stacked
    state natively when num_chains > 1 (one scan whose body is the
    chain-batched step — no per-chain scans). Emits the per-step
    (θ, StepStats) as chunk-local O(cs) scan outputs (time axis leading,
    chain axis second) plus (final_state, any_overflow). Algorithms with
    the ``step_data`` form get the dataset threaded as a trailing operand
    (see :func:`_threads_data`); the chunk signature grows accordingly."""
    multi = num_chains > 1
    if multi and _threads_data_chains(alg):
        step = alg.step_chains_data
    elif _threads_data(alg):
        step = (
            jax.vmap(alg.step_data, in_axes=(0, 0, None, None))
            if multi else alg.step_data
        )
    else:
        step = alg.batched_step() if multi else alg.step
    if multi:
        fold_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))
        position = jax.vmap(alg.position_of)
    else:
        fold_keys, position = jax.random.fold_in, alg.position_of

    def chunk(state, keys, start, *operands):
        def body(carry, i):
            new_state, info = step(fold_keys(keys, i), carry, *operands)
            with jax.named_scope("driver.outputs"):
                return new_state, (position(new_state), info)

        iters = start + jnp.arange(cs, dtype=jnp.int32)
        final, (pos, infos) = jax.lax.scan(body, state, iters)
        with jax.named_scope("driver.outputs"):
            return final, pos, infos, jnp.any(infos.overflow)

    return jax.jit(chunk)


class ChunkEvent:
    """What the driver exposes to ``on_chunk`` at each committed boundary.

    ``start``/``size`` locate the chunk (``start`` counts committed samples
    before it, so ``start + size`` is the total committed so far);
    ``num_samples`` is the run's target; ``state`` the post-chunk chain
    state; ``driver`` a snapshot of the call's :class:`DriverCounters` at
    this boundary (the hook's own time is added after it returns).
    ``peek(name)`` reads the named collector's would-be result
    through :func:`repro.api.collectors.peek` — non-destructive, never
    aliasing the live carry, so peeking cannot perturb the run.
    """

    def __init__(self, start, size, num_samples, state, colls, carries, multi,
                 driver=None):
        self.start = start
        self.size = size
        self.num_samples = num_samples
        self.state = state
        self.driver = driver
        self._colls = colls
        self._carries = carries
        self._multi = multi

    @property
    def committed(self) -> int:
        return self.start + self.size

    def peek(self, name: str):
        carry = self._carries[name]
        if not self._multi:  # finalize/peek contract: leading chain axis
            carry = jax.tree.map(lambda l: l[None], carry)
        return collectors_lib.peek(self._colls[name], carry)


def make_collector_fold(colls: dict, multi: bool, max_count: int | None = None):
    """Fold one COMMITTED chunk's (θ, StepStats) outputs into the collector
    carries, in step order. The chunk outputs arrive time-major
    ((cs, K, ...) for multi); the fold is one scan over the time axis whose
    body batches each collector's per-chain ``update`` over the chain axis,
    so the carries keep their leading (K, ...) layout.

    A separate jit from the chain scan for two reasons: (a) it runs only
    after the chunk's overflow check passes, so an overflowed chunk never
    touches collector state and capacity re-runs need no carry rollback —
    and its cache key is capacity-independent, so a capacity-doubling
    re-run never recompiles it; (b) the carry argument is donated (where
    the backend supports input-output aliasing), so a trace-type
    collector's O(num_samples) buffer is updated in place instead of being
    copied at every chunk boundary.

    Public because the :mod:`repro.serve` group engines fold the identical
    protocol over their slot axis — one encoding of the committed-chunk
    fold, shared by the driver and the service.

    ``max_count`` is the serve engines' masked variant: the fold signature
    becomes ``fold(carries, counts, pos, infos) -> (carries, counts)`` with
    int32 ``counts`` of samples folded so far (per-chain ``(K,)`` when
    ``multi``, scalar otherwise), and updates stop being absorbed once the
    count reaches ``max_count``. In a packed serve group every member runs
    the same chunk, so a job whose ``max_samples`` is not chunk-aligned
    overshoots by up to one chunk — the mask discards exactly the overshoot
    updates, making the carry bitwise the carry of a solo run of
    ``max_count`` samples (the kept updates see identical inputs in
    identical order; collector updates are pure, so discarded applications
    leave no residue).
    """
    names = tuple(colls)
    updates = {
        n: (jax.vmap(colls[n].update) if multi else colls[n].update)
        for n in names
    }

    if max_count is None:

        def fold(carries, pos, infos):
            def body(cars, x):
                p, inf = x
                return {n: updates[n](cars[n], p, inf) for n in names}, None

            with jax.named_scope("driver.fold"):
                cars, _ = jax.lax.scan(body, carries, (pos, infos))
            return cars

    else:
        limit = jnp.int32(max_count)

        def fold(carries, counts, pos, infos):
            def body(carry, x):
                cars, cnt = carry
                p, inf = x
                new = {n: updates[n](cars[n], p, inf) for n in names}
                active = cnt < limit

                def sel(a, b):
                    m = active.reshape(
                        active.shape + (1,) * (a.ndim - active.ndim)
                    )
                    return jnp.where(m, a, b)

                cars = jax.tree.map(sel, new, cars)
                return (cars, cnt + active.astype(cnt.dtype)), None

            with jax.named_scope("driver.fold"):
                (cars, cnt), _ = jax.lax.scan(
                    body, (carries, counts), (pos, infos)
                )
            return cars, cnt

    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(fold, donate_argnums=donate)


def sample(
    alg: SamplingAlgorithm,
    key: jax.Array,
    num_samples: int,
    *,
    num_chains: int = 1,
    thin: int = 1,
    chunk_size: int = 128,
    init_position=None,
    init_state=None,
    collectors: dict | None = None,
    on_chunk=None,
    health_check: bool = False,
) -> Trace:
    """Run ``num_samples`` iterations of ``alg`` on device; return a Trace.

    ``init_position`` seeds ``alg.init`` (default: ``alg.default_position``);
    pass a (num_chains, ...) array for per-chain starts. ``init_state``
    resumes from an existing chain state instead — single chain, or
    ``num_chains > 1`` with a leading-axis state (e.g. a previous multi-chain
    run's ``final_state``) — using ``key`` as the per-iteration key root with
    the fold-in counter offset by the state's ``iteration``: resuming with
    the prefix's key continues its exact stream (split == contiguous,
    bitwise) instead of replaying it.

    ``num_chains > 1`` runs the chains inside ONE chunked scan over
    chain-stacked state: the step is the algorithm's ``step_chains`` when it
    has one (the distributed fleet's shard_maps the chain axis), else
    ``alg.step`` batched here — each Pallas kernel then dispatches as a
    single launch with a leading chain grid dimension. Either way the
    realized trajectories are bitwise those of per-chain execution with
    keys ``split(key, num_chains)``.

    ``collectors`` maps names to :mod:`repro.api.collectors` instances; their
    ``update`` runs inside the jitted chunk scans (batched over the chain
    axis) and their finalized results land on ``Trace.results``. Without it,
    the default :class:`~repro.api.collectors.FullTrace` reproduces the dense
    ``Trace.theta``/``Trace.stats`` bitwise; with it, nothing O(num_samples)
    is materialized unless a trace collector asks for it. ``thin`` keeps
    every thin-th θ sample on the default path (the last of each window;
    stats stay per-iteration) — with explicit collectors use
    :class:`~repro.api.collectors.ThinnedTrace` instead. Host syncs: one per
    chunk (plus one at resume).

    ``on_chunk`` is the chunk-boundary hook: called with a
    :class:`ChunkEvent` after every COMMITTED chunk (never for an overflowed
    chunk awaiting its capacity re-run). ``event.peek(name)`` streams any
    collector's current value without consuming its carry — peeking leaves
    the run bitwise unchanged. Returning a truthy value stops the run early
    at that boundary (convergence-based termination): the Trace then holds
    only the committed samples (``theta``/``stats`` sliced on the default
    path; streaming collectors simply saw fewer updates).

    ``health_check`` raises :class:`NonFiniteError` at any chunk boundary
    whose outputs or post-chunk state contain NaN/Inf, BEFORE the fold — the
    collector carries then hold exactly the last healthy committed prefix.
    Off by default (it costs one extra device round-trip per chunk); the
    serve engines run the per-lane equivalent unconditionally because a
    multi-tenant group must contain one tenant's poison.
    """
    if num_samples <= 0:
        raise ValueError("num_samples must be positive")
    if num_chains < 1:
        raise ValueError("num_chains must be >= 1")
    chunk_size = max(1, min(int(chunk_size), num_samples))
    multi = num_chains > 1

    if collectors is None:
        colls = {"trace": _DEFAULT_TRACE}
        default_path = True
    else:
        if thin != 1:
            raise ValueError(
                "thin applies to the default trace only; with collectors= "
                "use ThinnedTrace(thin) instead"
            )
        colls = collectors_lib.validate_collectors(collectors)
        default_path = False

    counters = DriverCounters()
    with jax.profiler.TraceAnnotation(SPAN_INIT):
        alg, state, start_offset, k_steps = _initial_state(
            alg, key, num_chains, init_position, init_state
        )
        chain_keys = (
            jax.random.split(k_steps, num_chains) if multi else k_steps
        )
        # Collector carries, built from shape/dtype structs only (no
        # compute): one carry per chain, broadcast over the leading chain
        # axis.
        pos_struct, stats_struct = alg.output_structs(
            jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(
                    jnp.shape(l)[1:] if multi else jnp.shape(l), l.dtype
                ),
                state,
            )
        )
        carries = {
            name: col.init(num_samples, pos_struct, stats_struct)
            for name, col in colls.items()
        }
        if multi:
            carries = jax.tree.map(
                lambda l: jnp.broadcast_to(l, (num_chains,) + l.shape),
                carries,
            )

    def scan_fn_for(alg, cs):
        # Keyed on (num_chains, chunk_size, capacity) plus the step/dispatch
        # identities: an overflow re-run at a grown capacity traces its own
        # entry, and a later sample() call that reaches the same capacity
        # (memoized alg.grow() → same step identity) reuses it.
        return _cached(
            ("scan", alg.step, alg.step_chains, alg.position, num_chains,
             cs, _capacity_of(alg), kernels_common.chain_batching_enabled(),
             alg.step_data, alg.step_chains_data),
            lambda: _make_scan_fn(alg, num_chains, cs),
        )

    # Capacity-independent on purpose: chunk outputs are (cs, K) θ/stats
    # with no buffer-shaped operand, so one fold serves every capacity and
    # an overflow retry never recompiles it.
    fold_fn = _cached(
        ("fold", tuple(colls.items()), multi),
        lambda: make_collector_fold(colls, multi),
    )

    def scan_operands(alg):
        threads = _threads_data(alg) or (multi and _threads_data_chains(alg))
        return (alg.data, alg.stats) if threads else ()

    start = 0
    while start < num_samples:
        cs = min(chunk_size, num_samples - start)
        # Keep the pre-chunk state alive for the exact re-run on overflow.
        prev = state
        with _timed(counters, "dispatch_s", SPAN_DISPATCH):
            final, pos, infos, overflow = scan_fn_for(alg, cs)(
                state, chain_keys, jnp.int32(start_offset + start),
                *scan_operands(alg)
            )
        while _overflowed(counters, overflow):
            counters.reruns += 1
            counters.rerun_iters += num_chains * cs
            with _timed(counters, "regrow_s", SPAN_REGROW):
                alg = _grown(alg)
                resize = alg.resize if alg.resize is not None else _identity
                prev = _cached(
                    ("resize", resize, multi),
                    lambda: jax.jit(jax.vmap(resize) if multi else resize),
                )(prev)
            with _timed(counters, "dispatch_s", SPAN_DISPATCH):
                final, pos, infos, overflow = scan_fn_for(alg, cs)(
                    prev, chain_keys, jnp.int32(start_offset + start),
                    *scan_operands(alg)
                )
        if health_check:
            floats = [pos] + [
                l for l in jax.tree.leaves((infos, final))
                if jnp.issubdtype(l.dtype, jnp.floating)
            ]
            ok = _cached(
                ("health", len(floats)),
                lambda: jax.jit(lambda ls: jnp.all(
                    jnp.stack([jnp.all(jnp.isfinite(l)) for l in ls])
                )),
            )(floats)
            if not bool(jax.device_get(ok)):
                raise NonFiniteError(
                    f"non-finite chain state in iterations "
                    f"[{start_offset + start}, {start_offset + start + cs}); "
                    f"committed prefix of {start} samples is intact"
                )
        # Only a committed (non-overflowed) chunk reaches the collectors, so
        # capacity re-runs never need a carry rollback; the donated carry is
        # updated in place on backends with input-output aliasing.
        if colls:
            with _timed(counters, "fold_s", SPAN_FOLD):
                carries = fold_fn(carries, pos, infos)
        state = final
        start += cs
        counters.chunks += 1
        if on_chunk is not None:
            event = ChunkEvent(start - cs, cs, num_samples, state, colls,
                               carries, multi, dataclasses.replace(counters))
            with _timed(counters, "hook_s", SPAN_ON_CHUNK):
                stop = on_chunk(event)
            if stop:
                break

    committed = start

    with jax.profiler.TraceAnnotation(SPAN_FINALIZE):
        # finalize() always sees a leading (num_chains, ...) carry axis.
        if not multi:
            carries = jax.tree.map(lambda l: l[None], carries)
        results = {
            name: colls[name].finalize(carries[name]) for name in colls
        }

        if default_path:
            tr = results["trace"]
            theta, stats = tr["theta"], tr["stats"]
            if committed < num_samples:  # on_chunk stopped the run early
                theta = theta[:, :committed]
                stats = jax.tree.map(lambda l: l[:, :committed], stats)
            if thin > 1:
                theta = theta[:, thin - 1 :: thin]
            total_queries = int(
                np.asarray(
                    jax.device_get(stats.lik_queries), dtype=np.int64
                ).sum()
            )
            results = None
        else:
            theta = stats = None
            total_queries = next(
                (
                    results[name]
                    for name, col in colls.items()
                    if isinstance(col, collectors_lib.QueryBudget)
                ),
                None,
            )
    return Trace(
        theta=theta,
        stats=stats,
        total_queries=total_queries,
        final_state=state,
        algorithm=alg,
        results=results,
        driver=counters,
    )


def _overflowed(counters, overflow) -> bool:
    """Read a chunk's overflow flag: the chunk's one host sync, where the
    host waits for the device (under the wait span and ``wait_s``)."""
    with _timed(counters, "wait_s", SPAN_WAIT):
        return bool(jax.device_get(overflow))


def _initial_state(alg, key, num_chains, init_position, init_state):
    """The chains' starting state, for :func:`sample`: ``init_state``
    resumed, or ``alg``'s init at ``init_position``, growing ``alg`` until
    the state fits. Returns (alg, state, iteration offset, step key)."""
    multi = num_chains > 1
    start_offset = 0
    if init_state is not None:
        state = init_state
        if multi:
            leading = {
                jnp.shape(l)[:1] for l in jax.tree.leaves(state)
            }
            if leading != {(num_chains,)}:
                raise ValueError(
                    f"init_state resume with num_chains={num_chains} needs a "
                    f"state with a leading ({num_chains},) chain axis on "
                    f"every leaf (e.g. a previous multi-chain final_state)"
                )
        # Resume must NOT replay the prefix's key stream: per-iteration keys
        # are fold_in(chain_key, iteration), so a resumed segment continues
        # the counter at the state's iteration instead of restarting at 0.
        # With the same chain key, split runs are bitwise identical to one
        # contiguous run; with a fresh key, the segment is at least not a
        # replay of the original run's randomness. One host sync, up front.
        it = getattr(state, "iteration", None)
        if it is not None:
            vals = np.asarray(jax.device_get(it))
            if vals.ndim and not (vals == vals.flat[0]).all():
                raise ValueError(
                    "init_state chains are at different iterations "
                    f"({vals.tolist()}); resume needs a uniform offset"
                )
            start_offset = int(vals.flat[0] if vals.ndim else vals)
        # A checkpointed state may carry buffers grown past the algorithm's
        # built capacity (overflow doubles them mid-run), so the two can
        # disagree on buffer shapes at resume. Normalize the algorithm UP
        # to the state's capacity — growing is lossless, and trajectories
        # are bitwise capacity-invariant — then (if the doubling overshot)
        # resize the state up to the algorithm's capacity so they agree.
        if alg.resize is not None:
            struct = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(
                    jnp.shape(l)[1:] if multi else jnp.shape(l), l.dtype
                ),
                state,
            )

            def _alg_undersized(a):
                tgt = jax.eval_shape(a.resize, struct)
                return any(
                    np.prod(t.shape) < np.prod(c.shape)
                    for t, c in zip(
                        jax.tree.leaves(tgt), jax.tree.leaves(struct)
                    )
                )

            while alg.grow is not None and _alg_undersized(alg):
                alg = _grown(alg)
            tgt = jax.eval_shape(alg.resize, struct)
            if any(
                t.shape != c.shape
                for t, c in zip(jax.tree.leaves(tgt), jax.tree.leaves(struct))
            ):
                resize = alg.resize
                state = _cached(
                    ("resize", resize, multi),
                    lambda: jax.jit(jax.vmap(resize) if multi else resize),
                )(state)
        k_steps = key
    else:
        k_init, k_steps = jax.random.split(key)
        position = init_position if init_position is not None else alg.default_position
        if position is None:
            raise ValueError(
                "no init_position given and the algorithm has no default"
            )
        def init_fn(alg):
            if alg.init_data is not None and alg.data is not None:
                # The operand form, like the chunks: no dataset constant.
                # A chain-sharded fleet inits here too, so its chains start
                # bitwise where a one-device batch starts.
                f = alg.init_data
                fn = _cached(("init_data", f, multi), lambda: jax.jit(
                    jax.vmap(f, in_axes=(0, 0, None, None)) if multi else f
                ))
                return lambda k, p: fn(k, p, alg.data, alg.stats)
            build = lambda: jax.jit(alg.batched_init() if multi else alg.init)
            return _cached(
                ("init", alg.init, alg.init_chains, multi), build
            )

        if multi:
            init_keys = jax.random.split(k_init, num_chains)
            positions = _broadcast_positions(
                position, num_chains, alg.default_position
            )
            state = init_fn(alg)(init_keys, positions)
        else:
            state = init_fn(alg)(k_init, position)
        # Grow until the initial bright set fits (deterministic re-init from
        # the same keys) — one host sync, before any sampling starts.
        while alg.init_overflow is not None and bool(
            jax.device_get(
                jnp.any(
                    (jax.vmap(alg.init_overflow) if multi else alg.init_overflow)(
                        state
                    )
                )
            )
        ):
            alg = _grown(alg)
            if multi:
                state = init_fn(alg)(init_keys, positions)
            else:
                state = init_fn(alg)(k_init, position)
    return alg, state, start_offset, k_steps

def _grown(alg: SamplingAlgorithm) -> SamplingAlgorithm:
    if alg.grow is None:
        raise RuntimeError(
            "capacity overflow reported but the algorithm cannot grow "
            "(buffers already at data size, or a non-growing algorithm "
            "emitted overflow=True)"
        )
    return alg.grow()
