"""Streaming observables (ISSUE 4 acceptance criteria).

  * collector-vs-offline equivalence: every streaming estimate matches the
    same quantity computed offline from the dense trace (bitwise for exact
    reductions — thinning, query counts; fp tolerance for Welford moments);
  * the default path (no ``collectors=``) reproduces the dense
    ``Trace.theta``/``Trace.stats`` via the FullTrace collector bitwise;
  * overflow-chunk-re-run invariance: every built-in collector's result is
    bitwise identical between a chain that grows capacity mid-run and one
    at ample capacity throughout;
  * memory: a collectors-only ``sample`` traces no O(num_samples) buffer
    (asserted on the chunk jaxpr) and returns ``theta=None``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis, api
from repro.api import driver as driver_lib
from repro.core import diagnostics
from repro.core.flymc import StepStats
from repro.data import logistic_data
from repro.models.bayes_glm import GLMModel

jax.config.update("jax_platform_name", "cpu")

N, D = 400, 4


@pytest.fixture(scope="module")
def model():
    data = logistic_data(jax.random.key(0), n=N, d=D, separation=1.5)
    return GLMModel.logistic(data, prior_scale=2.0, xi=1.5)


@pytest.fixture(scope="module")
def alg(model):
    return api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )


def _all_builtins(model):
    return {
        "full": api.FullTrace(),
        "thin": api.ThinnedTrace(4),
        "moments": api.OnlineMoments(),
        "rhat": api.RHat(),
        "ess": api.BatchMeansESS(num_batches=8),
        "pp": api.PosteriorPredictive(x_eval=model.data.x[:7]),
        "queries": api.QueryBudget(),
    }


# ---------------------------------------------------------------------------
# Back-compat: the default path IS the FullTrace collector
# ---------------------------------------------------------------------------


def test_default_path_is_fulltrace_bitwise(alg):
    key = jax.random.key(1)
    default = api.sample(alg, key, 50, chunk_size=16)
    explicit = api.sample(
        alg, key, 50, chunk_size=16, collectors={"trace": api.FullTrace()}
    )
    assert explicit.theta is None and explicit.stats is None
    np.testing.assert_array_equal(
        np.asarray(default.theta),
        np.asarray(explicit.results["trace"]["theta"]),
    )
    for a, b in zip(default.stats, explicit.results["trace"]["stats"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_thinned_trace_matches_host_slice_bitwise(alg):
    key = jax.random.key(2)
    full = api.sample(alg, key, 43, chunk_size=17)  # 43: partial tail window
    thinned = api.sample(
        alg, key, 43, chunk_size=17, collectors={"t": api.ThinnedTrace(4)}
    )
    got = np.asarray(thinned.results["t"]["theta"])
    assert got.shape == (1, 43 // 4, D)
    np.testing.assert_array_equal(got[0], np.asarray(full.theta[0])[3::4])
    # degenerate: fewer samples than the thinning stride keeps nothing
    tiny = api.sample(alg, key, 3, collectors={"t": api.ThinnedTrace(4)})
    assert tiny.results["t"]["theta"].shape == (1, 0, D)


def test_thin_kwarg_with_collectors_raises(alg):
    with pytest.raises(ValueError, match="ThinnedTrace"):
        api.sample(
            alg, jax.random.key(0), 10, thin=2, collectors={"m": api.OnlineMoments()}
        )


# ---------------------------------------------------------------------------
# Collector-vs-offline equivalence
# ---------------------------------------------------------------------------


def test_online_moments_match_offline(alg):
    key = jax.random.key(3)
    mom = api.OnlineMoments()
    tr = api.sample(
        alg, key, 300, num_chains=2, chunk_size=64,
        collectors={"m": mom, "full": api.FullTrace()},
    )
    off = np.asarray(tr.results["full"]["theta"], np.float64)  # (2, T, D)
    res = tr.results["m"]
    assert res["mean"].shape == (2, D) and res["cov"].shape == (2, D, D)
    np.testing.assert_array_equal(res["count"], [300, 300])
    np.testing.assert_allclose(res["mean"], off.mean(1), rtol=0, atol=1e-4)
    for c in range(2):
        np.testing.assert_allclose(
            res["cov"][c], np.cov(off[c].T, ddof=1), rtol=1e-3, atol=1e-5
        )


def test_online_rhat_matches_split_r_hat(alg):
    key = jax.random.key(4)
    tr = api.sample(
        alg, key, 301, num_chains=4, chunk_size=50,  # odd: tail-drop path
        collectors={"r": api.RHat(), "full": api.FullTrace()},
    )
    off = np.asarray(tr.results["full"]["theta"], np.float64)
    res = tr.results["r"]
    expected = diagnostics.split_r_hat(off)
    per_coord = [
        diagnostics.split_r_hat(off[:, :, j]) for j in range(D)
    ]
    np.testing.assert_allclose(res["per_coordinate"], per_coord, rtol=1e-5)
    np.testing.assert_allclose(res["r_hat"], expected, rtol=1e-5)


def test_batch_means_ess_matches_offline_and_geyer(alg):
    key = jax.random.key(5)
    tr = api.sample(
        alg, key, 512, chunk_size=128,
        collectors={"e": api.BatchMeansESS(num_batches=16),
                    "full": api.FullTrace()},
    )
    off = np.asarray(tr.results["full"]["theta"][0], np.float64)
    res = tr.results["e"]
    expected = diagnostics.batch_means_ess(off, num_batches=16)
    # f32 on-device (sum, sum_sq) vs f64 two-pass variance: ~1e-5 relative
    np.testing.assert_allclose(res["ess"][0], expected, rtol=1e-3)
    # coarse-vs-Geyer cross-check: same order of magnitude on a real chain
    geyer = diagnostics.effective_sample_size(off)
    assert 0.1 < res["ess"][0] / geyer < 10.0, (res["ess"][0], geyer)


def test_batch_means_ess_stable_on_long_offcenter_chain():
    """A long chain with mean ≫ sd is exactly where a raw f32 (sum, sum_sq)
    variance cancels catastrophically; the running-mean/Welford carry must
    track the f64 offline estimate on 64k iterations at mean 50, sd 0.5."""
    col = api.BatchMeansESS(num_batches=16)
    n = 64_000
    xs = 50.0 + 0.5 * jax.random.normal(jax.random.key(0), (n, 1))
    carry = col.init(n, jax.ShapeDtypeStruct((1,), jnp.float32), None)
    carry, _ = jax.lax.scan(
        lambda c, x: (col.update(c, x, None), None), carry, xs
    )
    res = col.finalize(jax.tree.map(lambda l: l[None], carry))
    expected = diagnostics.batch_means_ess(np.asarray(xs, np.float64), 16)
    np.testing.assert_allclose(res["ess"][0], expected, rtol=0.1)


def test_posterior_predictive_matches_offline(model, alg):
    key = jax.random.key(6)
    x_eval = model.data.x[:9]
    tr = api.sample(
        alg, key, 200, chunk_size=64,
        collectors={"pp": api.PosteriorPredictive(x_eval=x_eval),
                    "full": api.FullTrace()},
    )
    off = np.asarray(tr.results["full"]["theta"][0])
    expected = np.mean(
        [jax.nn.sigmoid(np.asarray(x_eval) @ t) for t in off], axis=0
    )
    np.testing.assert_allclose(
        tr.results["pp"]["mean_prob"][0], expected, rtol=0, atol=1e-5
    )
    assert int(tr.results["pp"]["count"][0]) == 200


def test_query_budget_matches_host_sum_exactly(alg):
    key = jax.random.key(7)
    tr = api.sample(
        alg, key, 150, num_chains=3, chunk_size=64,
        collectors={"q": api.QueryBudget(), "full": api.FullTrace()},
    )
    stats = tr.results["full"]["stats"]
    offline = int(
        np.asarray(jax.device_get(stats.lik_queries), np.int64).sum()
    )
    assert tr.results["q"] == offline
    assert tr.total_queries == offline  # QueryBudget feeds Trace.total_queries


def test_query_budget_two_lane_uint32_does_not_wrap():
    """The on-device lo-lane wraps at 2³²; the hi-lane must carry it so the
    reassembled total is the exact int64 a host sum would produce."""
    qb = api.QueryBudget()
    carry = qb.init(0, None, None)
    big = np.int32(2**31 - 1)
    update = jax.jit(qb.update)
    steps = 5  # 5 × (2³¹-1) ≈ 1.07e10 > 2³²
    stats = StepStats(
        n_bright=jnp.int32(0), lik_queries=jnp.asarray(big),
        accept_prob=jnp.float32(0), overflow=jnp.bool_(False),
        joint_lp=jnp.float32(0),
    )
    for _ in range(steps):
        carry = update(carry, None, stats)
    total = qb.finalize(jax.tree.map(lambda l: l[None], carry))
    assert total == steps * int(big) > 2**32


# ---------------------------------------------------------------------------
# Overflow-chunk-re-run invariance of every built-in
# ---------------------------------------------------------------------------


def test_all_collectors_bitwise_invariant_to_capacity_overflow(model):
    """Collector carries are saved with the pre-chunk state, so a mid-run
    capacity-doubling re-run replays identical updates: each built-in's
    result must be bitwise the ample-capacity one."""
    key = jax.random.key(9)

    def run(cap, cand_cap):
        alg = api.firefly(
            model, kernel="rwmh", capacity=cap, cand_capacity=cand_cap,
            q_db=0.02, step_size=0.1,
        )
        return api.sample(
            alg, key, 300, chunk_size=32, collectors=_all_builtins(model)
        )

    # Overflow by construction: the initial bright set (2·q_db·N = 16
    # expected) exceeds capacity 8, and after init growth the candidate
    # buffer (2–4 slots) is below the q_db·N ≈ 8 candidates a step proposes.
    t_small = run(8, 1)
    assert t_small.algorithm.spec.capacity > 8, (
        "test must exercise a mid-chain capacity overflow"
    )
    t_big = run(N, N)  # full capacity: can never overflow
    small, big = t_small.results, t_big.results
    assert small.keys() == big.keys()
    for name in small:
        leaves_s = jax.tree.leaves(small[name])
        leaves_b = jax.tree.leaves(big[name])
        assert len(leaves_s) == len(leaves_b), name
        for ls, lb in zip(leaves_s, leaves_b):
            np.testing.assert_array_equal(
                np.asarray(ls), np.asarray(lb), err_msg=f"collector {name}"
            )


def test_collectors_bitwise_invariant_to_chunk_size(model, alg):
    key = jax.random.key(10)
    colls = _all_builtins(model)
    t1 = api.sample(alg, key, 60, chunk_size=7, collectors=colls)
    t2 = api.sample(alg, key, 60, chunk_size=60, collectors=colls)
    for name in colls:
        for ls, lb in zip(
            jax.tree.leaves(t1.results[name]), jax.tree.leaves(t2.results[name])
        ):
            np.testing.assert_array_equal(
                np.asarray(ls), np.asarray(lb), err_msg=f"collector {name}"
            )


# ---------------------------------------------------------------------------
# Memory: collectors-only sampling materializes no O(num_samples) buffer
# ---------------------------------------------------------------------------


# The local _walk_eqns/_subjaxprs/_max_dim copies migrated to
# repro.analysis.walker — the same traversal the static-analysis CLI sweep
# runs over the registered driver entry points.
_max_dim = analysis.walker.max_dim


def test_collectors_only_chunk_traces_no_num_samples_buffer(model, alg):
    """Neither the jitted chain-scan chunk nor a collectors-only carry fold
    may contain any array with a dimension of size num_samples — the trace
    buffer is simply absent from the program, not merely discarded. A
    FullTrace fold (sanity) trips the same detector."""
    num_samples = 50_000  # ≫ N and every state/buffer dim
    cs = 64
    colls = {
        "m": api.OnlineMoments(), "r": api.RHat(), "q": api.QueryBudget(),
        "e": api.BatchMeansESS(),
    }
    state = jax.jit(alg.init)(jax.random.key(0), alg.default_position)
    pos_struct, stats_struct = alg.output_structs(state)

    # the chain scan emits chunk-local O(cs) outputs regardless of collectors
    scan = driver_lib._make_scan_fn(alg, False, cs)
    operands = (alg.data, alg.stats) if driver_lib._threads_data(alg) else ()
    scan_jaxpr = jax.make_jaxpr(scan)(
        state, jax.random.key(1), jnp.int32(0), *operands
    )
    assert _max_dim(scan_jaxpr.jaxpr) < num_samples

    # a collectors-only fold carries nothing O(num_samples) either
    pos = jnp.zeros((cs,) + pos_struct.shape, pos_struct.dtype)
    infos = jax.tree.map(
        lambda s: jnp.zeros((cs,) + s.shape, s.dtype), stats_struct
    )
    carries = {
        n: c.init(num_samples, pos_struct, stats_struct)
        for n, c in colls.items()
    }
    fold = driver_lib.make_collector_fold(colls, False)
    jaxpr = jax.make_jaxpr(fold)(carries, pos, infos)
    assert _max_dim(jaxpr.jaxpr) < num_samples

    full = {"full": api.FullTrace()}
    carries_f = {"full": full["full"].init(num_samples, pos_struct, stats_struct)}
    fold_f = driver_lib.make_collector_fold(full, False)
    jaxpr_f = jax.make_jaxpr(fold_f)(carries_f, pos, infos)
    assert _max_dim(jaxpr_f.jaxpr) >= num_samples  # the detector is real


def test_collectors_only_trace_fields_are_none(alg):
    tr = api.sample(
        alg, jax.random.key(11), 20, collectors={"m": api.OnlineMoments()}
    )
    assert tr.theta is None and tr.stats is None
    assert tr.total_queries is None  # no QueryBudget passed
    # final_state still resumable
    again = api.sample(
        alg, jax.random.key(12), 10, init_state=tr.final_state,
        collectors={"m": api.OnlineMoments()},
    )
    assert int(again.results["m"]["count"][0]) == 10


def test_empty_collectors_dict_collects_nothing(alg):
    tr = api.sample(alg, jax.random.key(13), 10, collectors={})
    assert tr.results == {}
    assert tr.theta is None and tr.total_queries is None


# ---------------------------------------------------------------------------
# Protocol validation & misc
# ---------------------------------------------------------------------------


def test_validate_collectors_rejects_bad_inputs(alg):
    with pytest.raises(TypeError, match="dict"):
        api.sample(alg, jax.random.key(0), 5, collectors=[api.RHat()])
    with pytest.raises(TypeError, match="strings"):
        api.sample(alg, jax.random.key(0), 5, collectors={1: api.RHat()})
    with pytest.raises(TypeError, match="protocol"):
        api.sample(alg, jax.random.key(0), 5, collectors={"x": object()})
    with pytest.raises(ValueError, match="x_eval"):
        api.PosteriorPredictive()
    with pytest.raises(ValueError, match="num_batches"):
        api.BatchMeansESS(num_batches=1)


def test_collectors_work_with_regular_mcmc(model):
    """The protocol is algorithm-agnostic: the full-data baseline streams
    through the same collectors (overflow always False, n_bright = N)."""
    alg = api.regular_mcmc(model, kernel="rwmh", step_size=0.1)
    tr = api.sample(
        alg, jax.random.key(14), 40, chunk_size=20,
        collectors={"m": api.OnlineMoments(cov=False), "q": api.QueryBudget()},
    )
    assert tr.results["q"] == 40 * N
    assert tr.results["m"]["mean"].shape == (1, D)
    assert "cov" not in tr.results["m"]


# ---------------------------------------------------------------------------
# Chunk-boundary peeks (the serve streaming contract)
# ---------------------------------------------------------------------------


def _eq_trees(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("num_chains", [1, 2])
def test_peek_then_continue_is_bitwise(model, alg, num_chains):
    """Peeking EVERY built-in collector at EVERY chunk boundary leaves the
    run bitwise identical to one that never peeked — peek finalizes a deep
    copy, so neither the carry values nor the donated-buffer aliasing are
    disturbed. This is what makes serve-side streaming free."""
    num_samples, cs = 48, 16
    ref = api.sample(
        alg, jax.random.key(3), num_samples, chunk_size=cs,
        num_chains=num_chains, collectors=_all_builtins(model),
    )
    peeked = {}

    def hook(ev):
        peeked[ev.committed] = {n: ev.peek(n) for n in _all_builtins(model)}
        return False

    tr = api.sample(
        alg, jax.random.key(3), num_samples, chunk_size=cs,
        num_chains=num_chains, collectors=_all_builtins(model),
        on_chunk=hook,
    )
    assert sorted(peeked) == [16, 32, 48]  # every boundary peeked
    for name in ref.results:
        _eq_trees(ref.results[name], tr.results[name])


def test_final_boundary_peek_matches_finalize(model, alg):
    """At the last boundary a peek IS the result: identical values for
    every collector (R̂'s mid-run monitor pools full-length splits there,
    so even its guarded path lands on the finalize value)."""
    num_samples, cs = 48, 16
    last = {}

    def hook(ev):
        if ev.committed == num_samples:
            last.update({n: ev.peek(n) for n in _all_builtins(model)})
        return False

    tr = api.sample(
        alg, jax.random.key(3), num_samples, chunk_size=cs,
        collectors=_all_builtins(model), on_chunk=hook,
    )
    for name, res in tr.results.items():
        got = last[name]
        if isinstance(res, dict) and isinstance(got, dict):
            common = set(res) & set(got)
            assert common  # peek may add keys (e.g. splits_used), not drop
            res = {k: res[k] for k in common if res[k] is not None}
            got = {k: got[k] for k in common if got[k] is not None}
        _eq_trees(res, got)


def test_peek_result_never_aliases_live_carry(model, alg):
    """Mutating a peeked FullTrace buffer in place must not leak into the
    run's final results — the peek contract is copy-on-read."""
    num_samples, cs = 32, 16
    grabbed = []

    def hook(ev):
        if ev.committed == cs:
            pk = ev.peek("full")
            pk["theta"].block_until_ready()
            # numpy view of the device buffer would be unsafe to write; the
            # contract is stronger: the peeked arrays are fresh buffers, so
            # even deleting them cannot perturb the carry.
            grabbed.append(jax.tree.map(np.asarray, pk))
        return False

    ref = api.sample(
        alg, jax.random.key(5), num_samples, chunk_size=cs,
        collectors={"full": api.FullTrace()},
    )
    tr = api.sample(
        alg, jax.random.key(5), num_samples, chunk_size=cs,
        collectors={"full": api.FullTrace()}, on_chunk=hook,
    )
    _eq_trees(ref.results["full"], tr.results["full"])
    # the peek saw exactly the first chunk's committed prefix
    np.testing.assert_array_equal(
        grabbed[0]["theta"][:, :cs],
        np.asarray(ref.results["full"]["theta"][:, :cs]),
    )
