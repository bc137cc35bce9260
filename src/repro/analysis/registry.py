"""The registered hot-path entry points the CLI sweep gates.

Every jit the sampler's hot loop runs through is (or should be) registered
here with the rules it must satisfy: the fused / jnp / pallas steps, the
driver's chunk scan and committed-chunk fold, the serve group chunk, and
the distributed chain fleet. ``python -m repro.analysis`` sweeps them all;
the ``static-analysis`` CI lane fails on any regression. New subsystems
(data_fleet, paged bright-set memory) register here as part of landing.

Registering a new entry point::

    @entry_point("mything.step")
    def _mything():
        fn, args = ...          # what to trace (structs are fine)
        return check(fn, *args, rules=[...], name="mything.step")

Builders trace with ``jax.eval_shape``-derived structs wherever possible —
the sweep never *runs* a sampler step, it only traces and (for the
donation rule) lowers, so it stays cheap enough to gate every commit. The
jnp z-engine is registered ``expect_fail={"cost-model"}`` on purpose: it
is the known-O(N) engine, and its report going quiet would mean the
detector went blind (reported as ``xpass``, which fails the sweep).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp

from repro.analysis.collectives import (
    CommBytesRule,
    ReplicationRule,
    collective_rules,
)
from repro.analysis.kernels import kernel_rules
from repro.analysis.report import Report, Summary
from repro.analysis.rules import (
    CapacityIndependenceRule,
    ClosureConstRule,
    CostModelRule,
    DonationRule,
    RngLineageRule,
    check,
)

# One shared problem shape for the whole sweep: big enough that O(N) work
# is unambiguous (N well above every capacity-shaped buffer), small enough
# to trace in milliseconds.
N, D, CAPACITY = 1024, 4, 64

REGISTRY: OrderedDict[str, Callable[[], Report]] = OrderedDict()


def entry_point(name: str):
    """Register a thunk producing one entry point's Report."""

    def deco(build):
        REGISTRY[name] = build
        return build

    return deco


def run_registry(names=None) -> Summary:
    """Run the sweep (all entry points, or a subset by name)."""
    selected = list(REGISTRY) if names is None else list(names)
    reports = []
    for name in selected:
        reports.append(REGISTRY[name]())
    return Summary(reports=reports)


# ---------------------------------------------------------------------------
# shared fixtures (built lazily, cached — the sweep reuses one dataset)
# ---------------------------------------------------------------------------

_CACHE: dict = {}


def _data():
    if "data" not in _CACHE:
        from repro.data import logistic_data

        _CACHE["data"] = logistic_data(jax.random.key(0), n=N, d=D,
                                       separation=1.5)
    return _CACHE["data"]


def _alg(z_backend="fused", backend="jnp", capacity=CAPACITY):
    key = ("alg", z_backend, backend, capacity)
    if key not in _CACHE:
        from repro import api
        from repro.models.bayes_glm import GLMModel

        model = GLMModel.logistic(_data(), prior_scale=2.0, xi=1.5)
        _CACHE[key] = api.firefly(
            model, kernel="rwmh", capacity=capacity, cand_capacity=capacity,
            q_db=0.01, step_size=0.1, backend=backend, z_backend=z_backend,
        )
    return _CACHE[key]


def _key_struct():
    return jax.eval_shape(lambda: jax.random.key(0))


def _state_struct(alg):
    return jax.eval_shape(alg.init, _key_struct(), alg.default_position)


def _step_rules():
    return [CostModelRule(n=N), ClosureConstRule(), RngLineageRule()]


def _check_step(alg, name, **kw):
    # The operand-data form is the form the driver/serve actually jit; it
    # is also what makes closure-constant meaningful (data is an operand).
    return check(
        alg.step_data, _key_struct(), _state_struct(alg), alg.data, alg.stats,
        rules=_step_rules(), name=name, **kw,
    )


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


@entry_point("step.fused")
def _step_fused() -> Report:
    """The production CPU/TPU step: jnp θ-engine + fused z-engine."""
    return _check_step(_alg(z_backend="fused"), "step.fused")


@entry_point("step.jnp")
def _step_jnp() -> Report:
    """The known-O(N) reference engine — the cost-model rule's sanity case:
    its (N,) uniforms and full-N cumsum MUST trip the detector."""
    return _check_step(
        _alg(z_backend="jnp"), "step.jnp", expect_fail=("cost-model",)
    )


@entry_point("step.pallas")
def _step_pallas() -> Report:
    """Fused θ-kernel (pallas_call) + fused z-engine: the walker descends
    into the Pallas inner jaxprs, so in-kernel tile RNG is costed too."""
    return _check_step(
        _alg(z_backend="fused", backend="pallas"), "step.pallas"
    )


@entry_point("driver.chunk")
def _driver_chunk() -> Report:
    """api.sample's jitted chunk scan (multi-chain, operand-data form)."""
    from repro.api import driver

    alg = _alg()
    k = 2
    chunk = driver._make_scan_fn(alg, num_chains=k, cs=8)
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), k))
    states = jax.eval_shape(
        alg.batched_init(), keys,
        jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((k,) + jnp.shape(l), l.dtype),
            alg.default_position,
        ),
    )
    start = jax.ShapeDtypeStruct((), jnp.int32)
    return check(
        chunk, states, keys, start, alg.data, alg.stats,
        rules=_step_rules(), name="driver.chunk",
    )


def _fold_args(alg, colls, k=2, cs=8, num_samples=32):
    """(carries, pos, infos) structs for a committed-chunk fold of ``alg``."""
    state1 = _state_struct(alg)
    pos_s, stats_s = alg.output_structs(state1)
    carries = {
        name: jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((k,) + l.shape, l.dtype),
            col.init(num_samples, pos_s, stats_s),
        )
        for name, col in colls.items()
    }
    chunked = lambda s: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((cs, k) + l.shape, l.dtype), s
    )
    return carries, chunked(pos_s), chunked(stats_s)


@entry_point("driver.fold")
def _driver_fold() -> Report:
    """The committed-chunk collector fold: donated carries must really
    alias, and the jaxpr must be IDENTICAL across buffer capacities (the
    PR 5 pin — overflow re-runs retrace only the chain scan, never this)."""
    from repro.api import collectors as collectors_lib
    from repro.api import driver

    colls = {
        "trace": collectors_lib.FullTrace(),
        "moments": collectors_lib.OnlineMoments(),
    }
    fold = driver.make_collector_fold(colls, multi=True)
    args = _fold_args(_alg(capacity=CAPACITY), colls)

    def variant(capacity):
        return lambda: jax.make_jaxpr(fold)(
            *_fold_args(_alg(capacity=capacity), colls)
        )

    rules = [
        ClosureConstRule(),
        DonationRule(donate_argnums=(0,)),
        CapacityIndependenceRule({
            f"capacity-{c}": variant(c) for c in (CAPACITY, 2 * CAPACITY)
        }),
    ]
    return check(fold, *args, rules=rules, name="driver.fold")


@entry_point("serve.run_chunk")
def _serve_run_chunk() -> Report:
    """The serve GroupEngine's group chunk (lane axis over jobs)."""
    from repro.data import logistic_data
    from repro.serve.engine import GroupEngine
    from repro.serve.job import Job, TerminationPolicy

    if "serve_engine" not in _CACHE:
        job = Job(
            job_id="analysis-probe", family="logistic",
            data=logistic_data(jax.random.key(1), n=256, d=D,
                               separation=1.5),
            capacity=32, cand_capacity=32, z_backend="fused",
            policy=TerminationPolicy(max_samples=64),
        )
        engine = GroupEngine(job)
        engine.admit(job)
        _CACHE["serve_engine"] = engine
    engine = _CACHE["serve_engine"]
    chunk = engine._build_chunk(cs=4)
    lanes = engine._lanes
    rules = [CostModelRule(n=256), ClosureConstRule(), RngLineageRule()]
    return check(
        chunk, lanes["states"], lanes["keys"], lanes["data"], lanes["stats"],
        rules=rules, name="serve.run_chunk",
    )


# ---------------------------------------------------------------------------
# sharded entry points: every shard_map program, traced under an
# AbstractMesh (axis names + sizes, NO physical devices — the sweep
# verifies 8-way-sharded programs on a 1-device CI host). Each runs the
# four collective analyses (budget census, replication-consistency,
# comm-bytes, shard-shape) with its declared per-step budget; the dist
# step additionally pins the derived per-device wire bytes, which the
# test suite cross-validates against the compiled program's HLO.
# ---------------------------------------------------------------------------

_DATA_SHARDS = 8


def _dist_mesh():
    return jax.sharding.AbstractMesh((("data", _DATA_SHARDS),))


def _fleet_mesh():
    return jax.sharding.AbstractMesh((("chains", _DATA_SHARDS),))


def _fleet_keys_states(fleet, k):
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), k))
    states = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((k,) + l.shape, l.dtype),
        _state_struct(fleet),
    )
    return keys, states


def _fleet():
    if "fleet" not in _CACHE:
        from repro.distributed.flymc_dist import chain_fleet

        _CACHE["fleet"] = chain_fleet(_alg(), _fleet_mesh())
    return _CACHE["fleet"]


def _dist_step_fixture():
    """(step_fn, data/stats/state structs) for the data-sharded chain."""
    if "dist_step" not in _CACHE:
        from repro.distributed.flymc_dist import make_dist_flymc
        from repro.models.bayes_glm import GLMModel

        model = GLMModel.logistic(_data(), prior_scale=2.0, xi=1.5)
        _, init_fn, step_fn, _ = make_dist_flymc(
            model.bound, model.log_prior, _dist_mesh(), N,
            kernel="rwmh", capacity=CAPACITY, cand_capacity=CAPACITY,
            q_db=0.01,
        )
        data_s = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), _data()
        )
        stats_s = jax.eval_shape(model.bound.suffstats, data_s)
        theta_s = jax.ShapeDtypeStruct((D,), jnp.float32)
        state_s, _ = jax.eval_shape(
            init_fn, data_s, stats_s, theta_s, _key_struct()
        )
        _CACHE["dist_step"] = (step_fn, data_s, stats_s, state_s)
    return _CACHE["dist_step"]


# The dist step's collective contract (see flymc_dist module docstring):
# 4 scalar psums (θ-proposal, post-z refresh, n_bright, lik_queries) +
# 1 scalar pmax (overflow) + 1 axis_index (z-key fold, zero wire) — and
# NOTHING in the z-phase. Wire: 5 scalar ring all-reduces × 2·4 B = 40 B
# per device per step, cross-validated against compiled HLO by test.
DIST_STEP_BUDGET = {"psum@data": 4, "pmax@data": 1, "axis_index@data": 1}
DIST_STEP_WIRE_BYTES = 40


@entry_point("dist.step")
def _dist_step() -> Report:
    """The data-sharded FlyMC step: one scalar psum per θ-proposal, a
    collective-free z-phase, and every replicated output proven so."""
    step_fn, data_s, stats_s, state_s = _dist_step_fixture()
    rules = _step_rules() + collective_rules(
        DIST_STEP_BUDGET,
        expected_wire_bytes=DIST_STEP_WIRE_BYTES,
        # flat operand 0 is data.x: each of the 8 shards owns N/8 rows
        # (which the per-shard capacity is sized against)
        pin_locals={0: {0: N // _DATA_SHARDS}},
    )
    return check(
        step_fn, data_s, stats_s, state_s, rules=rules, name="dist.step",
    )


@entry_point("dist.step.zphase_psum")
def _dist_step_zphase_psum() -> Report:
    """Known-bad twin: a naive data-parallel z-phase that psums every
    candidate decision — the budget census must see the scan-body psum
    trip-multiplied (×n_local per step), or the detector is blind."""
    mesh = _dist_mesh()
    from jax.sharding import PartitionSpec as P

    def naive(x):
        def body(xs):
            theta_term = jax.lax.psum(jnp.sum(xs), "data")

            def zstep(carry, xi):
                # one collective PER DATUM: the O(N) communication the
                # paper's per-datum brightness exists to avoid
                return carry + jax.lax.psum(xi, "data"), xi

            z_term, _ = jax.lax.scan(zstep, 0.0, xs)
            return theta_term + z_term

        return jax.shard_map(
            body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False,
        )(x)

    return check(
        naive, jax.ShapeDtypeStruct((N,), jnp.float32),
        rules=collective_rules({"psum@data": 1}),
        name="dist.step.zphase_psum",
        expect_fail=("collective-budget",),
    )


@entry_point("dist.step.wire_drift")
def _dist_step_wire_drift() -> Report:
    """Known-bad twin: the REAL dist step against a drifted wire-bytes pin
    — proves the comm-bytes model actually constrains the program."""
    step_fn, data_s, stats_s, state_s = _dist_step_fixture()
    return check(
        step_fn, data_s, stats_s, state_s,
        rules=[CommBytesRule(expected_total=DIST_STEP_WIRE_BYTES + 8)],
        name="dist.step.wire_drift",
        expect_fail=("comm-bytes",),
    )


@entry_point("dist.fleet.rep_leak")
def _dist_fleet_rep_leak() -> Report:
    """Known-bad twin: a shard-varying value escaping as replicated — the
    check_vma=False foot-gun (shard 0's value silently wins). This is the
    bug class the replication rule caught in the real state pspecs (the
    per-shard bright count was declared PS() before this analysis landed)."""
    mesh = _dist_mesh()
    from jax.sharding import PartitionSpec as P

    def leak(x):
        # per-shard mean returned with out_specs=P(): NOT replicated
        return jax.shard_map(
            lambda xs: jnp.mean(xs), mesh=mesh, in_specs=(P("data"),),
            out_specs=P(), check_vma=False,
        )(x)

    return check(
        leak, jax.ShapeDtypeStruct((N,), jnp.float32),
        rules=[ReplicationRule()],
        name="dist.fleet.rep_leak",
        expect_fail=("replication-consistency",),
    )


@entry_point("dist.chain_fleet")
def _dist_chain_fleet() -> Report:
    """The chain fleet's sharded step in its operand-data form: even across
    a mesh, the dataset must be a (replicated) traced operand, not a
    closure constant baked into every device's executable — and chains are
    independent, so the budget is ZERO cross-chain collectives."""
    fleet = _fleet()
    keys, states = _fleet_keys_states(fleet, _DATA_SHARDS)
    rules = _step_rules() + collective_rules({}, expected_wire_bytes=0)
    return check(
        fleet.step_chains_data, keys, states, fleet.data, fleet.stats,
        rules=rules, name="dist.chain_fleet",
    )


@entry_point("dist.chain_fleet.closure")
def _dist_chain_fleet_closure() -> Report:
    """The fleet's closure-data form (step_chains): the other operand form
    the driver can dispatch. Same zero-collective budget; the closure-
    constant rule is deliberately absent here — baking data is this form's
    known trade-off, and dist.chain_fleet pins the operand form instead."""
    fleet = _fleet()
    keys, states = _fleet_keys_states(fleet, _DATA_SHARDS)
    return check(
        fleet.step_chains, keys, states,
        rules=collective_rules({}, expected_wire_bytes=0),
        name="dist.chain_fleet.closure",
    )


@entry_point("dist.collector_fold")
def _dist_collector_fold() -> Report:
    """The committed-chunk collector fold shard_mapped with every spec
    replicated. The dist driver runs collector updates on the replicated
    (θ, psum'd StepStats) outputs, so the fold must be mesh-safe: zero
    collectives AND no device-varying computation (no axis_index) — its
    carries stay replicated at any mesh size, which is what makes streamed
    diagnostics free at pod scale."""
    from jax.sharding import PartitionSpec as P

    from repro.api import collectors as collectors_lib
    from repro.api import driver

    colls = {
        "trace": collectors_lib.FullTrace(),
        "moments": collectors_lib.OnlineMoments(),
    }
    fold = driver.make_collector_fold(colls, multi=True)
    args = _fold_args(_alg(capacity=CAPACITY), colls)
    sharded = jax.shard_map(
        fold, mesh=_dist_mesh(), in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False,
    )
    return check(
        sharded, *args,
        rules=collective_rules({}, expected_wire_bytes=0),
        name="dist.collector_fold",
    )


@entry_point("serve.fleet_probe")
def _serve_fleet_probe() -> Report:
    """A fake-mesh serve placement probe: the GroupEngine's group chunk
    shard_mapped over a ('lanes', 2) AbstractMesh. Lanes are independent
    jobs, so the only collective a lane-parallel serve placement needs is
    ONE scalar pmax per chunk — the shared overflow flag that keeps the
    grow-and-rerun protocol in lockstep across lane shards. Budget pinned
    exactly there (16 B wire per chunk); replication proves that flag is
    the only replicated output."""
    from jax.sharding import PartitionSpec as P

    from repro.data import logistic_data
    from repro.serve.engine import GroupEngine
    from repro.serve.job import Job, TerminationPolicy

    if "serve_probe" not in _CACHE:
        def _job(i):
            return Job(
                job_id=f"fleet-probe-{i}", family="logistic",
                data=logistic_data(jax.random.key(2 + i), n=256, d=D,
                                   separation=1.5),
                capacity=32, cand_capacity=32, z_backend="fused",
                policy=TerminationPolicy(max_samples=64),
            )

        engine = GroupEngine(_job(0))
        engine.admit(_job(0))
        engine.admit(_job(1))
        _CACHE["serve_probe"] = engine
    engine = _CACHE["serve_probe"]
    chunk = engine._build_chunk(cs=4)
    lanes = engine._lanes
    row = P(("lanes",))

    def probe(states, keys, data, stats):
        final, pos, infos, overflow, healthy = chunk(states, keys, data,
                                                     stats)
        overflow = jax.lax.pmax(
            jnp.asarray(overflow).astype(jnp.int32), "lanes"
        ).astype(bool)
        # The health sentinel is per-lane by construction — it stays
        # row-sharded, proving quarantine needs ZERO collectives.
        return final, pos, infos, overflow, healthy

    sharded = jax.shard_map(
        probe, mesh=jax.sharding.AbstractMesh((("lanes", 2),)),
        in_specs=(row, row, row, row),
        out_specs=(row, row, row, P(), row),
        check_vma=False,
    )
    return check(
        sharded, lanes["states"], lanes["keys"], lanes["data"],
        lanes["stats"],
        rules=collective_rules({"pmax@lanes": 1}, expected_wire_bytes=8),
        name="serve.fleet_probe",
    )

# ---------------------------------------------------------------------------
# kernel entry points: the four kernel-level analyses (bounds, race,
# padding-taint, bytes model) over every pallas_call in src/repro/kernels/.
# Each entry declares its sequential accumulators BY OUTPUT INDEX (inner
# kernel functions are all literally named `kernel`, so names can't key
# them) — see the sequential-grid contract in repro.kernels.common. The
# FlyMC kernels additionally pin the derived HBM byte totals the
# benchmarks record, so a BlockSpec change that silently alters traffic
# fails the sweep until the model is consciously re-pinned.
# ---------------------------------------------------------------------------

_KD = 4        # chains in the chain-batched variants
_DP = 128      # bright's lane-padded feature width


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _bright_fn(family, **kw):
    from repro.kernels.bright_glm.ops import bright_glm

    def fn(x_rows, t, xi, idx, nb, theta):
        return bright_glm(x_rows, t, xi, idx, nb, theta, family=family,
                          interpret=True, **kw)

    return fn


def _bright_args(family):
    x = _s((N, 1, _DP))  # gather_layout(x)
    idx = _s((CAPACITY,), jnp.int32)
    nb = _s((), jnp.int32)
    if family == "softmax":
        k = 3
        return (x, _s((N,), jnp.int32), _s((N, k)), idx, nb, _s((k, D)))
    return (x, _s((N,)), _s((N,)), idx, nb, _s((D,)))


# bright's single-chain traffic: the (deleted) hand model's exact terms —
# row DMA C·D·4, lane-padded theta block, t/xi streams + delta out (3·C·4),
# and the 4-byte running total.
_BRIGHT_BYTES = CAPACITY * D * 4 + _DP * 4 + 3 * CAPACITY * 4 + 4


@entry_point("kernel.bright_glm.logistic")
def _kernel_bright_logistic() -> Report:
    return check(
        _bright_fn("logistic"), *_bright_args("logistic"),
        rules=kernel_rules(accumulators={1: (1,)},
                           expected_bytes={"kernel": _BRIGHT_BYTES}),
        name="kernel.bright_glm.logistic",
    )


@entry_point("kernel.bright_glm.student_t")
def _kernel_bright_student_t() -> Report:
    return check(
        _bright_fn("student_t"), *_bright_args("student_t"),
        rules=kernel_rules(accumulators={1: (1,)},
                           expected_bytes={"kernel": _BRIGHT_BYTES}),
        name="kernel.bright_glm.student_t",
    )


@entry_point("kernel.bright_glm.softmax")
def _kernel_bright_softmax() -> Report:
    return check(
        _bright_fn("softmax"), *_bright_args("softmax"),
        rules=kernel_rules(accumulators={1: (1,)}),
        name="kernel.bright_glm.softmax",
    )


@entry_point("kernel.bright_glm.chains")
def _kernel_bright_chains() -> Report:
    """The chain-batched megakernel (custom_vmap → chain-grid launch):
    grid leads with the chain axis; per-chain totals still accumulate
    along the row axis only, and traffic is exactly K× the single-chain
    model."""
    fn = jax.vmap(_bright_fn("logistic"),
                  in_axes=(None, None, None, 0, 0, 0))
    x, t, xi, idx, nb, theta = _bright_args("logistic")
    args = (x, t, xi, _s((_KD, CAPACITY), jnp.int32), _s((_KD,), jnp.int32),
            _s((_KD, D)))
    return check(
        fn, *args,
        rules=kernel_rules(accumulators={1: (0, 1)},  # whole-(K, 1) SMEM
                           expected_bytes={"kernel": _KD * _BRIGHT_BYTES}),
        name="kernel.bright_glm.chains",
    )


# z-update shapes: large enough that the row-block grid axis really
# revisits the candidate accumulators (16384 ids = 2 tiles of 64×128,
# the tile that ops.tile_rows gives at this N).
_ZN = 16384


def _z_fn():
    from repro.kernels.z_update.ops import z_candidates

    def fn(arr, num, kw):
        return z_candidates(arr, num, kw, q_db=0.01,
                            cand_capacity=CAPACITY, interpret=True)

    return fn


# arr streams once (4·N after exact tiling), the compacted candidate
# buffer writes back C_pad·4, plus the 4-byte count the hand model omitted.
_Z_BYTES = _ZN * 4 + CAPACITY * 4 + 4


@entry_point("kernel.z_update")
def _kernel_z_update() -> Report:
    return check(
        _z_fn(), _s((_ZN,), jnp.int32), _s((), jnp.int32),
        _s((2,), jnp.int32),
        rules=kernel_rules(accumulators={0: (1,), 1: (1,)},
                           expected_bytes={"kernel": _Z_BYTES}),
        name="kernel.z_update",
    )


@entry_point("kernel.z_update.chains")
def _kernel_z_chains() -> Report:
    # The count is one whole (K, 1) SMEM block every grid step revisits,
    # along the chain axis too; each chain owns its own row of it.
    return check(
        jax.vmap(_z_fn()), _s((_KD, _ZN), jnp.int32), _s((_KD,), jnp.int32),
        _s((_KD, 2), jnp.int32),
        rules=kernel_rules(accumulators={0: (1,), 1: (0, 1)},
                           expected_bytes={"kernel": _KD * _Z_BYTES}),
        name="kernel.z_update.chains",
    )


@entry_point("kernel.decode_attention")
def _kernel_decode_attention() -> Report:
    """w=192 forces ring padding (pad_w=64 with pos = -1 sentinel): the
    taint analysis must see the in-kernel validity mask scrub it."""
    from repro.kernels.decode_attention.ops import decode_attention

    b, h, hk, d, w = 2, 4, 2, 128, 192
    fn = lambda q, k, v, pos, t: decode_attention(
        q, k, v, pos, t, interpret=True)
    return check(
        fn, _s((b, h, d)), _s((b, w, hk, d)), _s((b, w, hk, d)),
        _s((w,), jnp.int32), _s((), jnp.int32),
        rules=kernel_rules(accumulators={0: (2,), 1: (2,), 2: (2,)}),
        name="kernel.decode_attention",
    )


@entry_point("kernel.fused_ce")
def _kernel_fused_ce() -> Report:
    """T=10 with block_t=8 forces row padding (tp=16): the zero-padded
    rows must stay out of every vocab-axis reduction."""
    from repro.kernels.fused_ce.ops import fused_ce

    fn = lambda x, w, labels: fused_ce(x, w, labels, interpret=True)
    return check(
        fn, _s((10, 128)), _s((128, 1024)), _s((10,), jnp.int32),
        rules=kernel_rules(accumulators={0: (1,), 1: (1,)}),
        name="kernel.fused_ce",
    )


@entry_point("kernel.rglru_scan")
def _kernel_rglru_scan() -> Report:
    """100 channels pad to the 128-lane block; the final-state output
    revisits the sequence-chunk axis (axis 2) as its accumulator."""
    from repro.kernels.rglru_scan.ops import rglru_scan

    fn = lambda a, bx: rglru_scan(a, bx, interpret=True)
    return check(
        fn, _s((1, 256, 100)), _s((1, 256, 100)),
        rules=kernel_rules(accumulators={1: (2,)}),
        name="kernel.rglru_scan",
    )


@entry_point("kernel.rwkv6_scan")
def _kernel_rwkv6_scan() -> Report:
    from repro.kernels.rwkv6_scan.ops import rwkv6_scan

    fn = lambda r, k, v, lw, u: rwkv6_scan(r, k, v, lw, u, chunk=64,
                                           interpret=True)
    s4 = _s((1, 2, 128, 128))
    return check(
        fn, s4, s4, s4, s4, _s((2, 128)),
        rules=kernel_rules(accumulators={1: (2,)}),
        name="kernel.rwkv6_scan",
    )
