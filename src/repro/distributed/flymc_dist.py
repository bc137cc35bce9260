"""Distributed FlyMC: the paper's algorithm sharded across a pod.

Mapping (DESIGN.md §5):
  * data rows sharded over the data axes (and ``pod`` for multi-pod) —
    each shard owns a slice of x, the z-partition, and the δ cache;
  * bound sufficient statistics psum'd ONCE at setup — the collapsed bound
    product stays O(D²) replicated work per step (zero per-step collective
    cost for the bound term, the paper's key property at pod scale);
  * per θ-proposal, one scalar psum of shard-local bright log-pseudo-
    likelihood sums — the minimum communication any exact method needs;
  * z-updates are embarrassingly parallel given θ (shard-local data), with
    per-shard independent RNG (keys folded with the shard index);
  * per-shard bright capacities bound straggler skew: no shard ever does
    data-dependent work beyond C rows (the host grows C globally on
    overflow, exactly as in the single-device chain);
  * streaming collectors (:mod:`repro.api.collectors`) compose for free:
    the sharded step emits θ and StepStats replicated (``out_specs PS()``,
    stats psum'd in-step), so the driver's collector updates run on
    replicated values and the carries stay replicated — online moments,
    split-R̂, and exact query accounting at pod scale cost zero extra
    collectives and no O(iterations) memory.

The collective contract (statically enforced by
``repro.analysis.collectives`` — the ``dist.step`` registry entry pins
these counts exactly; regressions fail the static-analysis CI lane):

===================  ======================================================
psum × 4 per step    1 θ-proposal (the scalar bright log-L̃ sum — the
                     paper's "one scalar reduction per proposal"),
                     1 post-z sampler refresh (same scalar, at the new
                     bright set), 2 StepStats reductions (n_bright,
                     lik_queries) so the driver sees global counts
pmax × 1 per step    the scalar overflow flag — every shard must agree on
                     capacity growth or the re-run protocol diverges
axis_index × 1       per-shard z-key fold (zero wire bytes: it lowers to
                     partition-id) — what makes shard RNG independent
z-phase              ZERO collectives, including inside the z-update scan
                     body: brightness is per-datum, so z-moves are
                     shard-local at any mesh size
===================  ======================================================

Every ``shard_map`` below passes ``check_vma=False`` (jax's own
replication checker off — it rewrites the jaxpr and slows tracing), which
means a ``PS()`` out-spec is TRUSTED, not checked: jax silently installs
shard 0's value everywhere. The replication-consistency rule in
``repro.analysis.collectives`` re-proves every replicated output from the
dataflow instead; per-shard quantities (the bright count ``num``) are
sharded as length-1 rows so no shard-varying value ever crosses a ``PS()``
boundary.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PS

from repro.core import bounds as bounds_lib
from repro.core import brightness, flymc, samplers
from repro.core.bounds import GLMData


def shard_data(data: GLMData, mesh) -> GLMData:
    """Place a host GLMData onto the mesh, rows sharded over all data axes."""
    axes = tuple(mesh.axis_names)
    sharding = NamedSharding(mesh, PS(axes))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), data)


def _state_pspecs(axes):
    # Replicated leaves (PS()) are values every shard provably computes
    # identically: θ/lp/grad come out of the psum'd proposal, log_step
    # adapts on the replicated accept_prob, rng/iteration are threaded
    # replicated by the driver. Everything per-datum (partition arr/tab,
    # the δ cache, sampler aux) is row-sharded. The per-shard bright COUNT
    # is sharded too — as a length-1 row per shard (scalars can't shard),
    # packed/unpacked at the shard_map boundary by _pack/_unpack: declaring
    # it PS() would silently broadcast shard 0's count over every shard
    # under check_vma=False (shards disagree on their bright prefix, so
    # z-updates and overflow detection would run against the wrong count).
    row = PS(axes)
    return flymc.FlyMCState(
        sampler=samplers.SamplerState(
            theta=PS(), lp=PS(), grad=PS(), aux=row
        ),
        bright=brightness.BrightState(arr=row, tab=row, num=row),
        delta_full=row,
        log_step=PS(),
        rng=PS(),
        iteration=PS(),
    )


def _pack(state):
    """Lift the shard-local scalar bright count to a (1,) row so shard_map
    can shard it (global shape: one entry per shard)."""
    return state._replace(
        bright=state.bright._replace(num=state.bright.num[None])
    )


def _unpack(state):
    """Drop the (1,) packing back to the scalar the core sampler expects."""
    return state._replace(
        bright=state.bright._replace(num=state.bright.num[0])
    )


def make_dist_flymc(bound, log_prior, mesh, n_global: int, **spec_kw):
    """Build (spec, init_fn, step_fn, stats_fn) for a data-sharded chain.

    ``capacity``/``cand_capacity`` in spec_kw are PER-SHARD. Pass
    ``backend="pallas"`` to route each shard's θ-update through the fused
    bright-GLM kernel (the pallas_call runs shard-local inside shard_map;
    only the scalar log L̃ sum is psum'd, exactly like the jnp path), and
    ``z_backend="fused"`` to stream each shard's z-update through the
    ``kernels/z_update`` candidate kernel + incremental partition updates —
    z-moves are shard-local (per-shard folded keys), so the fused engine
    needs no extra collectives either.
    """
    axes = tuple(mesh.axis_names)
    # mesh.size (not mesh.devices.size): works for AbstractMesh too, so the
    # static-analysis sweep can trace these programs with no devices at all.
    n_shards = mesh.size
    assert n_global % n_shards == 0
    spec = flymc.FlyMCSpec(
        bound=bound, log_prior=log_prior, axis_names=axes, **spec_kw
    )
    data_ps = PS(axes)  # every GLMData leaf row-sharded, x_rows included
    stats_ps = PS()  # replicated, the expansion point included
    state_ps = _state_pspecs(axes)
    stats_out_ps = flymc.StepStats(*([PS()] * 5))

    def _stats_local(data):
        stats = bounds_lib.psum_stats(bound.suffstats(data), axes)
        return bounds_lib.recenter(bound, stats)

    # check_vma=False at every call site below: jax's replication checker is
    # skipped for trace speed, so replicated (PS()) outputs are TRUSTED —
    # the repro.analysis.collectives replication rule re-proves each one
    # from the dataflow instead. Here: the stats come out of psum_stats.
    stats_fn = jax.jit(
        jax.shard_map(
            _stats_local, mesh=mesh, in_specs=(data_ps,),
            out_specs=stats_ps, check_vma=False,
        )
    )

    def _init_local(data, stats, theta0, key):
        state, nb, _ = flymc.init_chain(spec, data, stats, theta0, key)
        # nb is the shard-local initial bright count: psum for the global
        # (replicated) number; the per-shard count stays in the state.
        return _pack(state), jax.lax.psum(nb, axes)

    # check_vma=False: replicated outputs are the psum'd nb and the state's
    # PS() leaves (θ/lp/grad from the replicated init, rng/iteration);
    # per-shard leaves (incl. the packed bright count) are row-sharded.
    init_fn = jax.jit(
        jax.shard_map(
            _init_local, mesh=mesh,
            in_specs=(data_ps, stats_ps, PS(), PS()),
            out_specs=(state_ps, PS()),
            check_vma=False,
        )
    )

    def _step_local(data, stats, state):
        new_state, stats_out = flymc.flymc_step(
            spec, data, stats, _unpack(state)
        )
        return _pack(new_state), stats_out

    # check_vma=False: the contract in the module docstring is what makes
    # the PS() outputs sound — θ/lp/grad/accept/log_step derive from the
    # psum'd proposal, StepStats are psum'd/pmax'd in-step — and the
    # dist.step entry point in repro.analysis.registry verifies exactly
    # that (budget: 4 scalar psum + 1 pmax + 1 axis_index, z-phase zero).
    step_fn = jax.jit(
        jax.shard_map(
            _step_local, mesh=mesh,
            in_specs=(data_ps, stats_ps, state_ps),
            out_specs=(state_ps, stats_out_ps),
            check_vma=False,
        )
    )
    return spec, init_fn, step_fn, stats_fn


def _spec_kw_of(spec: flymc.FlyMCSpec) -> dict:
    return {
        f.name: getattr(spec, f.name)
        for f in dataclasses.fields(spec)
        if f.name not in ("bound", "log_prior", "axis_names")
    }


def dist_algorithm(bound, log_prior, mesh, data: GLMData, **spec_kw):
    """A data-sharded FlyMC chain as a repro.api SamplingAlgorithm.

    ``data`` must already be placed on the mesh (see :func:`shard_data`).
    ``spec_kw`` accepts every FlyMCSpec field, including
    ``backend="pallas"`` for the fused θ-update kernel and
    ``z_backend="fused"`` for the streamed z-update engine.
    The returned algorithm plugs into ``repro.api.sample`` — the chunked
    ``lax.scan`` runs over the shard-mapped step, so the whole chunk stays on
    device and capacity growth follows the same chunk-boundary re-run
    protocol as the single-host chain (per-shard capacities doubled
    globally, same replicated RNG keys). ``sample(..., collectors=...)``
    works unchanged: collector carries live outside the shard_map on the
    replicated (θ, psum'd StepStats) outputs, so streamed diagnostics need
    no extra collectives and re-run bitwise on capacity growth.
    """
    from repro.api import SamplingAlgorithm

    n_global = data.x.shape[0]
    if spec_kw.get("backend") == "pallas":
        data = shard_data(bounds_lib.with_gather_layout(data), mesh)
    # Capacities are PER-SHARD: growth must cap at the shard-local row count,
    # not N — bright_buffer slices the shard-local arr inside shard_map.
    n_local = n_global // mesh.size
    spec, init_fn, step_fn, stats_fn = make_dist_flymc(
        bound, log_prior, mesh, n_global, **spec_kw
    )
    stats = stats_fn(data)
    axes = tuple(mesh.axis_names)

    def init_data(key, position, data_, stats_):
        state, _ = init_fn(data_, stats_, position, key)
        return state

    def step_data(key, state, data_, stats_):
        return step_fn(data_, stats_, state._replace(rng=key))

    init = lambda key, position: init_data(key, position, data, stats)
    step = lambda key, state: step_data(key, state, data, stats)

    grown = []  # memoized so the driver's jit cache sees a stable identity

    def grow():
        if not grown:
            grown.append(
                dist_algorithm(
                    bound, log_prior, mesh, data,
                    **_spec_kw_of(flymc._grow(spec, n_local)),
                )
            )
        return grown[0]

    def resize(state):
        return _resize_dist(spec, state, mesh)

    # Replicated "any shard's initial bright set exceeds its capacity" flag,
    # so the driver re-initializes at a grown capacity exactly like the
    # single-host chain (init_chain_state leaves the state truncated).
    # check_vma=False: the single PS() output is sound because the pmax is
    # what replicates it — each shard contributes its OWN bright count
    # (num arrives sharded, (1,) per shard), so a shard-local overflow on
    # any device raises the flag everywhere.
    _overflow_fn = jax.jit(
        jax.shard_map(
            lambda s: jax.lax.pmax(
                (s.bright.num[0] > spec.capacity).astype(jnp.int32), axes
            ).astype(bool),
            mesh=mesh,
            in_specs=(_state_pspecs(axes),),
            out_specs=PS(),
            check_vma=False,
        )
    )

    can_grow = spec.capacity < n_local or spec.cand_capacity < n_local
    return SamplingAlgorithm(
        init=init,
        step=step,
        grow=grow if can_grow else None,
        resize=resize,
        init_overflow=_overflow_fn if can_grow else None,
        default_position=jnp.zeros(data.x.shape[-1]),
        spec=spec,
        # The operand forms: the driver passes the sharded rows in, so no
        # jit bakes them into its executable.
        step_data=step_data,
        init_data=init_data,
        data=data,
        stats=stats,
    )


def chain_fleet(alg, mesh):
    """Shard a SamplingAlgorithm's CHAIN axis across a mesh of devices.

    The complement of :func:`dist_algorithm`: instead of sharding the *data*
    rows of one chain, shard the *chains* of a fleet — each device owns
    ``num_chains / n_devices`` whole chains (data replicated) and advances
    them with the algorithm's chain-batched step (:func:`repro.api.firefly`'s
    dispatches its Pallas kernels as one chain-grid launch per device).
    Chains are independent, so the step needs ZERO cross-chain collectives —
    shard_map here is pure placement, and throughput scales with devices at
    the same marginal cost per chain as single-device batching.

    The returned algorithm plugs into ``repro.api.sample(num_chains=K)``
    unchanged (K must be divisible by the mesh size; shard_map enforces it).
    Capacity growth composes: ``grow()`` re-wraps the grown inner algorithm
    on the same mesh, memoized so the driver's jit cache keys stay stable.
    Use this for fleets of independent chains on replicated data; use
    :func:`dist_algorithm` when one chain's DATA does not fit a device (the
    two compose only as alternatives today, not nested).
    """
    from repro.api import SamplingAlgorithm

    axes = tuple(mesh.axis_names)
    mesh = _auto_mesh(mesh)
    row = PS(axes)  # leading-axis (chain) sharding, as a pytree prefix
    # check_vma=False on all three fleet shard_maps: trivially sound — every
    # in/out spec is chain-sharded (no PS() output exists to mis-replicate)
    # and the bodies contain ZERO collectives, the budget the
    # dist.chain_fleet entry point pins (chains are independent; shard_map
    # here is pure placement).
    step_chains = jax.shard_map(
        alg.batched_step(), mesh=mesh, in_specs=(row, row),
        out_specs=(row, row), check_vma=False,
    )
    init_chains = jax.shard_map(
        alg.batched_init(), mesh=mesh, in_specs=(row, row), out_specs=row,
        check_vma=False,
    )
    # The operand-data form: chains sharded, the dataset REPLICATED as a
    # traced operand (PS() specs) rather than closed over — the fleet's
    # chunk jit then carries no dataset-sized constant, same exactness
    # rationale as the driver's _threads_data path (and what lets the
    # repro.analysis closure-constant rule pass on the fleet entry point).
    step_chains_data = None
    if alg.step_data is not None and alg.data is not None:
        step_chains_data = jax.shard_map(
            jax.vmap(alg.step_data, in_axes=(0, 0, None, None)),
            mesh=mesh, in_specs=(row, row, PS(), PS()),
            out_specs=(row, row), check_vma=False,
        )

    grown = []  # memoized so the driver's jit cache sees a stable identity

    def grow():
        if not grown:
            grown.append(chain_fleet(alg.grow(), mesh))
        return grown[0]

    return SamplingAlgorithm(
        init=alg.init,
        step=alg.step,
        step_chains=step_chains,
        init_chains=init_chains,
        step_data=alg.step_data,
        init_data=alg.init_data,
        step_chains_data=step_chains_data,
        data=alg.data,
        stats=alg.stats,
        grow=grow if alg.grow is not None else None,
        resize=alg.resize,
        init_overflow=alg.init_overflow,
        position=alg.position,
        default_position=alg.default_position,
        spec=alg.spec,
    )


def _auto_mesh(mesh):
    """``mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
    fleet's outputs carry their chain sharding in their types; the
    driver's collector fold then vmaps chain-sharded chunk outputs
    against unsharded carries, which jax rejects. The fleet is pure
    placement, so the sharding stays out of the types.
    """
    auto = (jax.sharding.AxisType.Auto,) * len(mesh.axis_names)
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


def run_dist_chain(
    bound, log_prior, mesh, data: GLMData, theta0, key, num_iters: int,
    **spec_kw,
):
    """Sharded-chain driver (shim over ``repro.api.sample``).

    Returns (thetas, trace, total_queries) like the original host loop, but
    the chain now runs in chunked on-device scans with one host sync per
    chunk instead of ~4 per iteration.
    """
    from repro import api

    data = shard_data(data, mesh)
    alg = dist_algorithm(bound, log_prior, mesh, data, **spec_kw)
    trace = api.sample(alg, key, num_iters, init_position=theta0)
    thetas = list(jax.device_get(trace.theta[0]))
    st = jax.device_get(trace.stats)
    trace_dicts = [
        {
            "n_bright": int(st.n_bright[0, i]),
            "lik_queries": int(st.lik_queries[0, i]),
            "accept_prob": float(st.accept_prob[0, i]),
        }
        for i in range(num_iters)
    ]
    return thetas, trace_dicts, int(jax.device_get(trace.total_queries))


def _resize_dist(spec, state, mesh):
    axes = tuple(mesh.axis_names)
    # check_vma=False: resize is shard-local (pure buffer growth) — every
    # replicated leaf passes through untouched, per-shard leaves stay
    # sharded (the packed bright count crosses the boundary as a row).
    fn = jax.jit(
        jax.shard_map(
            lambda s: _pack(flymc.resize_state(spec, _unpack(s))),
            mesh=mesh,
            in_specs=(_state_pspecs(axes),), out_specs=_state_pspecs(axes),
            check_vma=False,
        )
    )
    return fn(state)
