"""GroupEngine: one batching group's jobs, packed on a lane axis.

One engine owns every admitted job of one :func:`repro.serve.job.group_key`
equivalence class. A **lane** is one whole job: its K chains stacked on a
chain axis, its dataset stored once and shared by those chains. A chunk is
ONE jitted call that advances every lane ``chunk_size`` steps — jobs at
wildly different progress points, each following exactly its own solo
trajectory.

Exactness contract (pinned in ``tests/test_serve.py``): every job's
trajectory and every collector result is bitwise the solo
``api.sample(build_algorithm(job), jax.random.key(job.seed), max_samples,
num_chains=K)`` run — regardless of which neighbors share the group, when
the job joined or left, how often the group re-packed, or a neighbor's
capacity overflow. The load-bearing pieces:

  * **Lane-local compute.** The default lane backend is ``lax.map`` over
    lanes: each lane runs the SAME per-job computation a solo driver run
    compiles — an unbatched chunk scan for K = 1, the driver's
    vmap-over-K body for K > 1 — so its floating-point rounding cannot
    depend on who else is packed. This is forced, not a style choice: XLA
    codegen (and hence low-bit rounding) varies with the batched extent,
    so ``vmap`` over a slot axis of heterogeneous jobs is bitwise
    REPRODUCIBLE only at one fixed width — a non-starter under continuous
    join/leave. (Verified empirically on CPU: identical chain states
    stepped at widths 2/3/4 differ in final bits.) ``lane_backend="vmap"``
    exists for throughput on accelerators where the packed launch wins and
    bit-stability across packings is not required — same chain law, same
    key streams, low-bit rounding tied to the group width; the exactness
    tests pin the default.
  * **Per-lane key streams come from the state, not the schedule.** Each
    lane scans ``i = state.iteration[0] + arange(cs)`` and keys with
    ``fold_in(chain_key, i)`` — the driver's exact discipline at whatever
    progress point the lane is at (``FlyMCState.iteration`` is carried in
    the state, so a lane can't desync).
  * **Admission replicates ``api.sample``'s init discipline** via
    :func:`repro.serve.job.chain_rows` (same ``split``/init-key layout).
  * **Capacity is a group property.** Members run at one (capacity,
    cand_capacity); overflow doubles the group (clamped to N) and re-runs
    the chunk from the saved pre-chunk states. Trajectories are bitwise
    capacity-invariant (the repo's core exactness property), so neither
    normalizing a member up on admit nor growing the whole group on one
    member's overflow perturbs anyone.
  * **Folds are masked per lane** (:func:`repro.api.driver.
    make_collector_fold` with ``max_count``): a chunk that overshoots a
    job's ``max_samples`` contributes nothing past it, so carries equal
    the solo run's bitwise.
  * **Padding replicates lane 0.** The lane axis is padded to a power-of-2
    bucket, bounding recompiles under continuous join/leave to
    O(log max_lanes); pad lanes are copies of lane 0 with saturated fold
    counts — same key stream as lane 0, so no novel overflow, and never
    folded. (Under the ``map`` backend pad lanes do cost sequential
    compute; the bucket trades that for compile time, which dominates.)

Chunk executables, folds and resizers are cached in
:func:`repro.api.driver.cached_jit` keyed on ``(group_key, capacity,
cand_capacity, bucket, chunk_size)`` — the group key is a pure value, so an
engine torn down (device loss, service restart) and rebuilt re-enters a
warm cache instead of recompiling.

Host-side state is "lanes": pytrees with a leading lane axis, typed PRNG
leaves held as raw ``key_data`` (uint32) so gather/concat/checkpoint are
plain array ops; keys are wrapped on the way into the jitted chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.api import collectors as collectors_lib
from repro.api import driver
from repro.core import flymc
from repro.serve import job as job_lib


def bucket_size(n: int) -> int:
    """Lane-axis padding: the next power of two ≥ n (≥ 1)."""
    b = 1
    while b < n:
        b *= 2
    return b


def _cat_lanes(trees: list):
    return jax.tree.map(lambda *ls: jnp.concatenate(ls, axis=0), *trees)


def _take_lanes(tree, idx):
    idx = jnp.asarray(idx, jnp.int32)
    return jax.tree.map(lambda l: jnp.take(l, idx, axis=0), tree)


def _raw(state):
    """FlyMCState with the typed rng leaf lowered to raw key_data."""
    return state._replace(rng=jax.random.key_data(state.rng))


def _wrap(state):
    return state._replace(rng=jax.random.wrap_key_data(state.rng))


class GroupEngine:
    """The packed lanes of one group key. See the module docstring.

    ``template`` is any member job: it supplies the spec construction and
    the collector instances (the group key pins both, so every member
    yields the identical spec and collector configuration — instances only
    matter through their config). Lane pytrees:

    ==========  =====================================================
    states      FlyMCState, leaves ``(L, K, ...)``, rng as key_data
    keys        ``(L, K, *keyshape)`` uint32 chain-key data
    data        GLMData, leaves ``(L, N, ...)`` — one copy per job
    stats       CollapsedStats, leaves ``(L, ...)``
    carries     {collector: leaves ``(L, K, ...)``}
    counts      ``(L,)`` int32 folded (committed) samples per lane
    ==========  =====================================================
    """

    def __init__(self, template: job_lib.Job, capacity: int | None = None,
                 cand_capacity: int | None = None,
                 lane_backend: str = "map"):
        if lane_backend not in ("map", "vmap"):
            raise ValueError(f"unknown lane_backend {lane_backend!r}")
        self.group_key = job_lib.group_key(template)
        self.template = template
        self.num_chains = template.num_chains
        self.max_samples = template.policy.max_samples
        self.lane_backend = lane_backend
        self.colls = collectors_lib.validate_collectors(template.collectors)
        alg = job_lib.build_algorithm(
            template,
            capacity=template.capacity if capacity is None else capacity,
            cand_capacity=(template.cand_capacity if cand_capacity is None
                           else cand_capacity),
        )
        self._spec = alg.spec  # capacities already clamped to N
        self._n = template.data.x.shape[0]
        self._members: list[str] = []  # lane order == membership order
        self._jobs: dict[str, job_lib.Job] = {}
        self._lanes: dict | None = None  # the lane pytrees, padded to bucket
        self._quarantined: list[str] = []  # sentinel hits, pending eviction

    # ------------------------------------------------------------ geometry

    @property
    def capacity(self) -> int:
        return self._spec.capacity

    @property
    def cand_capacity(self) -> int:
        return self._spec.cand_capacity

    @property
    def num_slots(self) -> int:
        """Budgeted chain slots (lanes × chains); padding is not billed."""
        return len(self._members) * self.num_chains

    @property
    def job_ids(self) -> list[str]:
        return list(self._members)

    def job(self, job_id: str) -> job_lib.Job:
        return self._jobs[job_id]

    def _lane_of(self, job_id: str) -> int:
        try:
            return self._members.index(job_id)
        except ValueError:
            raise KeyError(f"job {job_id!r} is not in this group") from None

    # ------------------------------------------------------------- packing

    def _repack(self, real: dict):
        """Install real lanes, padded to the bucket with copies of lane 0
        whose counts saturate at ``max_samples`` (never folded)."""
        n_real = real["counts"].shape[0]
        pad = bucket_size(n_real) - n_real
        if pad:
            zeros = jnp.zeros((pad,), jnp.int32)
            real = {
                name: (jnp.concatenate(
                    [t, jnp.full((pad,), self.max_samples, jnp.int32)])
                    if name == "counts"
                    else _cat_lanes([t, _take_lanes(t, zeros)]))
                for name, t in real.items()
            }
        self._lanes = real

    def _real_lanes(self) -> dict:
        n = len(self._members)
        return {k: jax.tree.map(lambda l: l[:n], t)
                for k, t in self._lanes.items()}

    # ------------------------------------------------------------ capacity

    def _grow_spec(self):
        """Double the group capacities (clamped to N) — spec only."""
        alg = job_lib.build_algorithm(
            self.template,
            capacity=min(2 * self.capacity, self._n),
            cand_capacity=min(2 * self.cand_capacity, self._n),
        )
        self._spec = alg.spec

    def _resize_fn(self):
        """Lane×chain-batched ``flymc.resize_state`` at the current
        capacity: zero likelihood queries, bitwise-identical chain law."""
        spec = self._spec
        return driver.cached_jit(
            ("serve_resize", self.group_key, spec.capacity),
            lambda: jax.jit(jax.vmap(jax.vmap(
                functools.partial(flymc.resize_state, spec)
            ))),
        )

    def _resize_states(self, states):
        return _raw(self._resize_fn()(_wrap(states)))

    def _grow(self):
        self._grow_spec()
        if self._lanes is not None:
            self._lanes["states"] = self._resize_states(self._lanes["states"])

    # ----------------------------------------------------------- admission

    def build_lane(self, job: job_lib.Job) -> tuple[dict, bool]:
        """One fresh lane for ``job`` at the CURRENT group capacity (leading
        axis 1), plus whether its initial bright set overflowed. The single
        encoding of a lane's structure: admission runs it under the grow
        loop (:meth:`_init_lane`); service restore runs it once on a
        placeholder job purely as the checkpoint-restore target skeleton
        (every value is then overwritten by ``Checkpointer.restore``)."""
        alg = job_lib.build_algorithm(
            job, capacity=self.capacity, cand_capacity=self.cand_capacity
        )
        states, chain_keys = job_lib.chain_rows(job, alg)
        over = bool(jax.device_get(
            jnp.any(jax.vmap(alg.init_overflow)(states))
        ))
        single = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), states
        )
        pos_s, stats_s = alg.output_structs(single)
        k = job.num_chains
        carries = {
            name: jax.tree.map(
                lambda l: jnp.broadcast_to(l, (k,) + l.shape),
                col.init(self.max_samples, pos_s, stats_s),
            )
            for name, col in self.colls.items()
        }
        lane = lambda t: jax.tree.map(lambda l: jnp.asarray(l)[None], t)
        return {
            "states": lane(_raw(states)),
            "keys": jax.random.key_data(chain_keys)[None],
            # alg.data, not the model's: for backend="pallas" it carries
            # the kernel's gather layout, built once here.
            "data": lane(alg.data),
            "stats": lane(alg.stats),
            "carries": lane(carries),
            "counts": jnp.zeros((1,), jnp.int32),
        }, over

    def _init_lane(self, job: job_lib.Job) -> dict:
        """A fresh job's lane, grown until the initial bright set fits —
        the driver's init-overflow loop lifted to group scope."""
        while True:
            lane, over = self.build_lane(job)
            if not over:
                return lane
            if self.capacity >= self._n and self.cand_capacity >= self._n:
                raise RuntimeError("initial bright set exceeds data size")
            self._grow()

    def admit(self, job: job_lib.Job):
        """Join a fresh job at the next chunk boundary."""
        if job_lib.group_key(job) != self.group_key:
            raise ValueError(f"job {job.job_id!r} does not match this group")
        if job.job_id in self._jobs:
            raise ValueError(f"job {job.job_id!r} already admitted")
        self._append(job, self._init_lane(job))

    def admit_restored(self, job: job_lib.Job, lane: dict):
        """Re-join a job from checkpointed/suspended lane trees (leading
        axis 1, states possibly at a different saved capacity): the states
        carry their iteration counters and the keys are the originals, so
        the per-lane key stream continues exactly where it left off."""
        if job.job_id in self._jobs:
            raise ValueError(f"job {job.job_id!r} already admitted")
        saved_cap = lane["states"].sampler.aux.shape[-1]
        if saved_cap > self.capacity:
            # Normalize the GROUP up — shrinking a state would lose aux rows.
            while self.capacity < min(saved_cap, self._n):
                self._grow()
        if saved_cap != self.capacity:
            lane = dict(lane)
            lane["states"] = self._resize_states(lane["states"])
        self._append(job, lane)

    def _append(self, job: job_lib.Job, lane: dict):
        if self._lanes is None:
            merged = lane
        else:
            real = self._real_lanes()
            merged = {
                name: (jnp.concatenate([real[name], lane[name]])
                       if name == "counts"
                       else _cat_lanes([real[name], lane[name]]))
                for name in real
            }
        self._members.append(job.job_id)
        self._jobs[job.job_id] = job
        self._repack(merged)

    def lane_of(self, job_id: str) -> dict:
        """A job's lane trees (leading axis 1), without removing it —
        the checkpoint export. Plain gathers of live device arrays."""
        i = self._lane_of(job_id)
        return {k: _take_lanes(t, [i]) for k, t in self._real_lanes().items()}

    def evict(self, job_id: str) -> dict:
        """Remove a job at a chunk boundary; returns its lane trees
        (leading axis 1) for result finalization, suspension, or
        checkpointing."""
        i = self._lane_of(job_id)
        lane = self.lane_of(job_id)
        keep = [j for j in range(len(self._members)) if j != i]
        self._members.pop(i)
        del self._jobs[job_id]
        if not self._members:
            self._lanes = None
        else:
            self._repack(
                {k: _take_lanes(t, keep) for k, t in self._lanes.items()}
            )
        return lane

    # ------------------------------------------------------------ the chunk

    def _map_lanes(self, fn, args):
        if self.lane_backend == "map":
            return jax.lax.map(fn, args)
        return jax.vmap(fn)(args)

    def _build_chunk(self, cs: int):
        """One jitted group chunk: every lane advances ``cs`` steps.

        The per-lane body reproduces :func:`repro.api.driver._make_scan_fn`
        exactly — unbatched for K = 1, the chain-batched step for K > 1,
        per-iteration keys ``fold_in(chain_key, start + i)`` — with the
        lane's own (data, stats) in place of the solo closure's.
        """
        spec = self._spec
        k = self.num_chains

        def per_lane(args):
            st_raw, keys_raw, data, stats = args
            step1 = lambda key, s: flymc.flymc_step(
                spec, data, stats, s._replace(rng=key)
            )
            st = _wrap(st_raw)
            if k == 1:
                st1 = jax.tree.map(lambda l: l[0], st)
                key = jax.random.wrap_key_data(keys_raw)[0]

                def body(s, i):
                    new, info = step1(jax.random.fold_in(key, i), s)
                    return new, (new.sampler.theta, info)

                iters = st1.iteration + jnp.arange(cs, dtype=jnp.int32)
                fin, (pos, infos) = jax.lax.scan(body, st1, iters)
                fin = jax.tree.map(lambda l: l[None], fin)
                pos = pos[:, None]
                infos = jax.tree.map(lambda l: l[:, None], infos)
            else:
                keys = jax.random.wrap_key_data(keys_raw)
                step = jax.vmap(step1)
                fold_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))
                position = jax.vmap(lambda s: s.sampler.theta)

                def body(s, i):
                    new, info = step(fold_keys(keys, i), s)
                    return new, (position(new), info)

                iters = st.iteration[0] + jnp.arange(cs, dtype=jnp.int32)
                fin, (pos, infos) = jax.lax.scan(body, st, iters)
            return _raw(fin), pos, infos

        def chunk(states_raw, key_rows, data, stats):
            fin, pos, infos = self._map_lanes(
                per_lane, (states_raw, key_rows, data, stats)
            )
            # Numerical-health sentinel, per lane. θ/log-joint alone are not
            # enough: a NaN'd dataset makes every proposal log-ratio compare
            # False — the lane keeps "running" with finite θ while its
            # trajectory silently leaves its law — so the δ cache, sampler
            # log-prob and the lane's own float data leaves are checked too.
            # Poison is caught at the very next boundary and the chunk is
            # never folded for that lane (quarantine in run_chunk).
            healthy = driver.finite_lanes(
                [pos, infos.joint_lp, fin.delta_full, fin.sampler.lp,
                 fin.sampler.theta]
                + [l for l in jax.tree.leaves(data)
                   if jnp.issubdtype(l.dtype, jnp.floating)]
            )
            # A poisoned lane must not drive capacity growth either: NaN
            # comparisons can assert overflow forever, and growth is a
            # group-wide re-run. Only healthy lanes' overflow counts.
            overflow = jnp.any(infos.overflow & healthy[:, None, None])
            return fin, pos, infos, overflow, healthy

        return jax.jit(chunk)

    def _build_fold(self):
        """Lane-mapped committed-chunk fold: per lane, exactly the driver's
        :func:`repro.api.driver.make_collector_fold` masked at
        ``max_samples`` (vmap-over-K updates for K > 1, unbatched for
        K = 1) — the one shared encoding of the collector fold."""
        k = self.num_chains
        lane_fold = driver.make_collector_fold(
            self.colls, multi=(k > 1), max_count=self.max_samples
        )

        def per_lane(args):
            carries, count, pos, infos = args
            if k == 1:
                cars, cnt = lane_fold(
                    jax.tree.map(lambda l: l[0], carries),
                    count, pos[:, 0],
                    jax.tree.map(lambda l: l[:, 0], infos),
                )
                return jax.tree.map(lambda l: l[None], cars), cnt
            cars, cnts = lane_fold(
                carries, jnp.full((k,), count, jnp.int32), pos, infos
            )
            return cars, cnts[0]

        def fold(carries, counts, pos, infos):
            return self._map_lanes(per_lane, (carries, counts, pos, infos))

        return jax.jit(fold)

    def run_chunk(self, chunk_size: int) -> int:
        """Advance every lane ``chunk_size`` steps and fold the committed
        outputs (masked at ``max_samples``). Returns the number of
        overflow re-runs (0 on the happy path) — the scheduler's
        congestion signal.

        Transactional at the host level: the lane trees are reassigned only
        after the chunk committed, so a raise anywhere in here leaves the
        engine at the previous boundary and the supervised service path can
        simply re-run the chunk (identical keys → bitwise the same chunk).

        **Quarantine.** Lanes the chunk sentinel marks unhealthy are NOT
        folded and NOT advanced: the masked fold is fed saturated counts for
        them (its ``active`` select then passes their carries through
        bitwise — the same mechanism that protects pad lanes — which also
        sidesteps the carry donation: the blend happens inside the fold's
        output, never by re-reading a donated buffer), their counts and
        states are restored from the pre-chunk values, and their job_ids
        land in :meth:`take_quarantined` for the service to evict. Healthy
        neighbors commit this chunk exactly as if the sick lane had never
        been admitted — lane compute is lane-local under the ``map``
        backend, so nothing of a neighbor's trajectory ever depended on it.
        """
        if self._lanes is None:
            return 0
        cs = int(chunk_size)
        bucket = self._lanes["counts"].shape[0]
        lanes = self._lanes
        reruns = 0
        cache_key = lambda: ("serve_scan", self.group_key, self.lane_backend,
                             self.capacity, self.cand_capacity, bucket, cs)
        scan = driver.cached_jit(cache_key(), lambda: self._build_chunk(cs))
        prev = lanes["states"]
        final, pos, infos, overflow, healthy = scan(
            prev, lanes["keys"], lanes["data"], lanes["stats"]
        )
        # The chunk's one host sync fetches overflow and lane health together.
        over, ok = jax.device_get((overflow, healthy))
        while bool(over):
            reruns += 1
            if self.capacity >= self._n and self.cand_capacity >= self._n:
                raise RuntimeError(
                    "overflow at full-data capacity — sampler bug"
                )
            # Grow and re-run THIS chunk from the saved pre-chunk states:
            # identical keys (they derive from the states' iteration
            # counters), bigger buffers — bitwise the infinite-capacity
            # trajectory, exactly the driver's overflow protocol.
            self._grow_spec()
            prev = self._resize_states(prev)
            scan = driver.cached_jit(cache_key(),
                                     lambda: self._build_chunk(cs))
            final, pos, infos, overflow, healthy = scan(
                prev, lanes["keys"], lanes["data"], lanes["stats"]
            )
            over, ok = jax.device_get((overflow, healthy))
        fold = driver.cached_jit(
            ("serve_fold", self.group_key, self.lane_backend),
            self._build_fold,
        )
        sick = [self._members[i] for i in range(len(self._members))
                if not bool(ok[i])]
        if not sick:
            new_carries, new_counts = fold(
                lanes["carries"], lanes["counts"], pos, infos
            )
            lanes["carries"], lanes["counts"] = new_carries, new_counts
            lanes["states"] = final
        else:
            lane_ok = jnp.asarray(ok)
            old_counts = lanes["counts"]
            counts_in = jnp.where(
                lane_ok, old_counts, jnp.int32(self.max_samples)
            )
            new_carries, folded_counts = fold(
                lanes["carries"], counts_in, pos, infos
            )
            blend = lambda new, old: jnp.where(
                lane_ok.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
            )
            lanes["carries"] = new_carries
            lanes["counts"] = jnp.where(lane_ok, folded_counts, old_counts)
            lanes["states"] = jax.tree.map(blend, final, prev)
            self._quarantined.extend(sick)
        return reruns

    def take_quarantined(self) -> list[str]:
        """Job ids quarantined by the last chunk's health sentinel (their
        lanes hold the pre-chunk committed state); clears the list. The
        service evicts and retires them as FAILED at this boundary."""
        out, self._quarantined = self._quarantined, []
        return out

    # ------------------------------------------------------------- readouts

    def committed(self, job_id: str) -> int:
        """Folded samples for this job (chains advance in lockstep)."""
        i = self._lane_of(job_id)
        return int(jax.device_get(self._lanes["counts"][i]))

    def peek(self, job_id: str, name: str):
        """Stream a collector's would-be result for one job, mid-run,
        without touching its carry (:func:`repro.api.collectors.peek`).
        The carry is handed over with its leading (K,) chain axis — the
        same contract as ``finalize``."""
        i = self._lane_of(job_id)
        carry = jax.tree.map(lambda l: l[i], self._carries_tree()[name])
        return collectors_lib.peek(self.colls[name], carry)

    def _carries_tree(self):
        return self._lanes["carries"]

    def finalize_lane(self, lane: dict) -> dict:
        """{name: finalized result} for an evicted lane (leading chain
        axis, exactly what a solo ``Trace.results`` holds)."""
        return {
            name: col.finalize(
                jax.tree.map(lambda l: l[0], lane["carries"][name])
            )
            for name, col in self.colls.items()
        }
