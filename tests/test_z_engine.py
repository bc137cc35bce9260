"""The fused z-update engine (``FlyMCSpec.z_backend = "fused"``).

Four layers of guarantee, cheapest to strongest:
  * RNG/compaction parity: the streaming candidate kernel (interpret mode)
    must reproduce the pure-jnp reference's per-datum counter draws and
    cumsum compaction bit-for-bit, across capacities and overflow;
  * cost model: the fused step's jaxpr contains NO length-N uniform
    generation and NO full-N cumsum re-partition — the O(N) work the
    engine exists to kill — while the jnp engine's jaxpr (sanity check)
    trips both detectors;
  * exactness mechanics: the fused trajectory is bitwise invariant to
    buffer capacity and driver chunk size, including across mid-chain
    capacity-doubling re-runs, and maintains the partition invariants;
  * chain law: fused vs jnp engines produce statistically equivalent
    bright-count trajectories and posterior moments (they follow different
    — law-equal — uniform streams, so only distributions can match).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis, api
from repro.analysis import rules as analysis_rules
from repro.core import brightness, numerics
from repro.data import logistic_data
from repro.models.bayes_glm import GLMModel

jax.config.update("jax_platform_name", "cpu")

N, D = 400, 4


@pytest.fixture(scope="module")
def model():
    data = logistic_data(jax.random.key(0), n=N, d=D, separation=1.5)
    return GLMModel.logistic(data, prior_scale=2.0, xi=1.5)


# ---------------------------------------------------------------------------
# In-kernel RNG & compaction parity (interpret mode vs per-datum reference)
# ---------------------------------------------------------------------------


def test_threefry_matches_jax_prng_bits():
    """The shared counter cipher is bit-compatible with jax's Threefry-2x32,
    so the in-kernel stream has exactly the PRNG quality of jax.random."""
    # jax._src is not a stable API: skip (not fail) if the reference cipher
    # moves — every other z-engine guarantee is pinned by the public-surface
    # tests below, this one only cross-checks the cipher constants.
    prng = pytest.importorskip("jax._src.prng")
    threefry_2x32 = prng.threefry_2x32

    k = jnp.array([123456789, 987654321], dtype=jnp.uint32)
    x = jnp.arange(64).astype(jnp.uint32)
    ours, _ = numerics.threefry2x32(
        jnp.int32(123456789),
        jnp.int32(987654321),
        jnp.zeros(64, jnp.int32),
        jnp.arange(64).astype(jnp.int32),
    )
    theirs = threefry_2x32(k, jnp.concatenate([jnp.zeros(64, jnp.uint32), x]))
    np.testing.assert_array_equal(
        np.asarray(ours).view(np.uint32), np.asarray(theirs[:64])
    )


@pytest.mark.parametrize("n,num_frac,q_db,cap", [
    (1000, 0.2, 0.05, 256),   # typical
    (1000, 0.2, 0.05, 8),     # candidate overflow (count ≫ cap)
    (1000, 0.0, 0.02, 64),    # all dark
    (1000, 1.0, 0.5, 64),     # all bright — no candidates
    (997, 0.3, 0.1, 128),     # N not a multiple of the tile
    (64, 0.5, 0.3, 16),       # N smaller than one tile
    (5000, 0.0, 0.5, 3000),   # ≈ 512 candidates a tile: several output rows
    (3000, 0.1, 0.1, 512),    # a tile's candidates straddle an output row
    (12214, 0.1, 0.01, 256),  # derived tile of 32 rows
    (16000, 0.05, 0.02, 512),  # derived tile of 64 rows
    (5000, 0.0, 0.5, 300),    # buffer overflow inside a many-row tile
])
def test_z_candidates_kernel_matches_ref(n, num_frac, q_db, cap):
    from repro.kernels.z_update.ops import z_candidates
    from repro.kernels.z_update.ref import z_candidates_ref

    z0 = jax.random.bernoulli(jax.random.key(1), num_frac, (n,))
    st = brightness.from_z(z0)
    kw = numerics.key_words_of(jax.random.key(7))
    c_k, n_k = z_candidates(st.arr, st.num, kw, q_db, cap, interpret=True)
    c_r, n_r = z_candidates_ref(st.arr, st.num, kw, q_db, cap)
    assert int(n_k) == int(n_r)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))


@pytest.mark.parametrize("n,rows", [
    (1_800_000, 64), (16000, 64), (12214, 32), (6000, 16), (5000, 8),
    (1000, 8), (64, 8),
])
def test_z_tile_rows_follow_n(n, rows):
    """The largest tile of up to 64 rows that pads N by at most N/16."""
    from repro.kernels.common import pad_to
    from repro.kernels.z_update.ops import tile_rows

    assert tile_rows(n) == rows
    block = rows * 128
    assert rows == 8 or pad_to(n, block) - n <= n // 16


def test_z_candidates_parity_under_jit_and_capacity():
    """Same (key, partition) ⇒ same candidate SET at every capacity: the
    counter RNG keys on datum ids, so capacity only truncates, never
    re-randomizes."""
    from repro.kernels.z_update.ops import z_candidates

    z0 = jax.random.bernoulli(jax.random.key(2), 0.1, (1000,))
    st = brightness.from_z(z0)
    kw = numerics.key_words_of(jax.random.key(3))
    f = jax.jit(
        lambda a, num, kw: z_candidates(a, num, kw, 0.05, 128, interpret=True)
    )
    c128, n128 = f(st.arr, st.num, kw)
    c512, n512 = z_candidates(st.arr, st.num, kw, 0.05, 512, interpret=True)
    assert int(n128) == int(n512)
    k = int(n128)
    np.testing.assert_array_equal(np.asarray(c128)[:k], np.asarray(c512)[:k])


def test_q_threshold_never_rounds_positive_q_to_zero():
    """A sub-grid q_db (< 2⁻²⁵) must still propose with the smallest
    representable probability, never zero — a zero threshold would stop all
    dark→bright moves and break irreducibility while the jnp engine keeps
    proposing."""
    from repro.kernels.z_update.ref import q_threshold_bits

    assert q_threshold_bits(1e-9) == 1
    assert q_threshold_bits(0.0) == 0
    assert q_threshold_bits(1.0) == 1 << 24
    assert q_threshold_bits(0.01) == round(0.01 * (1 << 24))


def test_counter_uniforms_are_per_datum_functions():
    """u(key, draw, datum) gathered on any buffer equals the corresponding
    slice of the full per-datum array — the capacity/chunk-invariance
    contract of flymc._implicit_z_update, without the (N,) materialization."""
    kw = numerics.key_words_of(jax.random.key(11))
    full = numerics.counter_uniform(kw, numerics.DRAW_DARKEN, jnp.arange(500))
    idx = jnp.asarray([3, 499, 0, 17, 256], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(numerics.counter_uniform(kw, numerics.DRAW_DARKEN, idx)),
        np.asarray(full)[np.asarray(idx)],
    )
    # distinct draw streams really are distinct
    other = numerics.counter_uniform(kw, numerics.DRAW_BRIGHT, jnp.arange(500))
    assert not np.array_equal(np.asarray(full), np.asarray(other))
    # crude uniformity sanity on the 24-bit grid
    assert abs(float(full.mean()) - 0.5) < 0.05
    assert 0.0 <= float(full.min()) and float(full.max()) < 1.0


# ---------------------------------------------------------------------------
# Cost model: no (N,) uniforms, no full-N cumsum in the fused step
# ---------------------------------------------------------------------------

# The ad-hoc _walk_eqns/_subjaxprs/_max_eqn_size helpers that used to live
# here are now repro.analysis.walker — the one shared jaxpr-inspection
# substrate (the analyzer's cost-model rule runs the same sweep over the
# registered step entry points in CI).
_RNG_PRIMS = analysis_rules.RNG_PRIMS
_max_eqn_size = analysis.walker.max_eqn_size


def _step_jaxpr(z_backend, n=4096, capacity=256):
    data = logistic_data(jax.random.key(0), n=n, d=D, separation=1.5)
    model = GLMModel.logistic(data, prior_scale=2.0, xi=1.5)
    alg = api.firefly(
        model, kernel="rwmh", capacity=capacity, cand_capacity=capacity,
        q_db=0.01, step_size=0.1, z_backend=z_backend,
    )
    state = jax.eval_shape(alg.init, jax.random.key(1), alg.default_position)
    return jax.make_jaxpr(alg.step)(jax.random.key(2), state), n


def test_fused_step_has_no_length_n_rng_or_cumsum():
    """Acceptance criterion: the fused engine's per-step non-likelihood work
    contains no length-N uniform materialization and no full-N cumsum
    re-partition, verified on the step's jaxpr (pallas inner jaxprs
    included — the kernel's tile-shaped threefry lanes are ≪ N)."""
    jaxpr, n = _step_jaxpr("fused")
    assert _max_eqn_size(jaxpr.jaxpr, _RNG_PRIMS) < n
    assert _max_eqn_size(jaxpr.jaxpr, ("cumsum",)) < n


def test_jnp_step_trips_both_detectors():
    """Sanity: the detectors are real — the jnp engine's (N,) uniforms and
    from_z cumsum must be visible to the same inspection."""
    jaxpr, n = _step_jaxpr("jnp")
    assert _max_eqn_size(jaxpr.jaxpr, _RNG_PRIMS) >= n
    assert _max_eqn_size(jaxpr.jaxpr, ("cumsum",)) >= n


# ---------------------------------------------------------------------------
# Exactness mechanics: capacity / chunk / overflow invariance
# ---------------------------------------------------------------------------


def test_fused_chain_capacity_and_chunk_invariant(model):
    def run(cap, chunk):
        alg = api.firefly(
            model, kernel="rwmh", capacity=cap, cand_capacity=cap,
            q_db=0.05, step_size=0.12, z_backend="fused",
        )
        return api.sample(alg, jax.random.key(9), 120, chunk_size=chunk)

    t_ref = run(N, 120)  # full capacity, single chunk
    for cap, chunk in ((64, 30), (64, 7), (128, 120)):
        t = run(cap, chunk)
        np.testing.assert_array_equal(
            np.asarray(t.theta), np.asarray(t_ref.theta)
        )
        np.testing.assert_array_equal(
            np.asarray(t.stats.n_bright), np.asarray(t_ref.stats.n_bright)
        )


def test_fused_chain_overflow_rerun_is_exact(model):
    """Mid-chain capacity overflow (tiny initial buffers) must re-run the
    chunk at doubled capacity and land bitwise on the ample-capacity
    trajectory — apply_flips' arr is capacity-invariant, so the fused
    engine keeps the driver's exactness contract."""
    def run(cap, cand_cap):
        alg = api.firefly(
            model, kernel="rwmh", capacity=cap, cand_capacity=cand_cap,
            q_db=0.02, step_size=0.1, z_backend="fused",
        )
        return api.sample(alg, jax.random.key(9), 300, chunk_size=32)

    # Overflow by construction: the initial bright set (2·q_db·N = 16
    # expected) exceeds capacity 8, and after init growth the candidate
    # buffer (2–4 slots) is below the q_db·N ≈ 8 candidates a step proposes.
    t_small = run(8, 1)
    assert t_small.algorithm.spec.capacity > 8, "must exercise an overflow"
    t_big = run(N, N)
    np.testing.assert_array_equal(
        np.asarray(t_small.theta), np.asarray(t_big.theta)
    )


def test_fused_chain_preserves_partition_invariants(model):
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1, z_backend="fused",
    )
    trace = api.sample(alg, jax.random.key(14), 25)
    assert brightness.check_invariants(trace.final_state.bright)


# ---------------------------------------------------------------------------
# Chain law: fused vs jnp engines target the same posterior
# ---------------------------------------------------------------------------


def test_fused_chain_statistically_equivalent(model):
    """Acceptance: fused vs jnp z-engine chain-law equivalence — posterior
    moments and bright-count trajectories match in distribution (the
    engines follow different, law-equal uniform streams)."""
    key = jax.random.key(5)
    moments, brights = {}, {}
    for zb in ("jnp", "fused"):
        # Slice θ-kernel: low autocorrelation, so the comparison between two
        # independent uniform streams resolves the moments without a huge
        # run; 4 chains also exercise the fused step vmapped.
        alg = api.firefly(
            model, kernel="slice", capacity=128, cand_capacity=128,
            q_db=0.05, step_size=0.5, z_backend=zb,
        )
        trace = api.sample(alg, key, 800, num_chains=4, chunk_size=200)
        s = np.asarray(trace.theta)[:, 200:].reshape(-1, D)
        moments[zb] = (s.mean(0), s.std(0))
        brights[zb] = np.asarray(trace.stats.n_bright)[:, 200:]
        assert np.all(np.isfinite(np.asarray(trace.stats.joint_lp)))
    mean_j, std_j = moments["jnp"]
    mean_f, std_f = moments["fused"]
    np.testing.assert_allclose(mean_f, mean_j, atol=4.0 * std_j.max() / 10)
    np.testing.assert_allclose(std_f, std_j, rtol=0.5)
    # bright-count trajectory law: same stationary occupancy
    np.testing.assert_allclose(
        brights["fused"].mean(), brights["jnp"].mean(), rtol=0.25
    )


def test_fused_with_pallas_backend_covers_whole_step(model):
    """backend='pallas' + z_backend='fused': candidate δ routes through the
    fused bright-GLM kernel and gradients (MALA) flow through its VJP."""
    alg = api.firefly(
        model, kernel="mala", capacity=128, cand_capacity=128, q_db=0.05,
        step_size=0.05, backend="pallas", z_backend="fused",
    )
    trace = api.sample(alg, jax.random.key(6), 60, chunk_size=30)
    assert np.all(np.isfinite(np.asarray(trace.theta)))
    assert np.all(np.isfinite(np.asarray(trace.stats.joint_lp)))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_z_other_families_smoke(backend):
    """Candidate δ dispatch handles the matrix-θ softmax and the Student-t
    bound on both likelihood backends."""
    from repro.data import robust_data, softmax_data

    cases = []
    sm = softmax_data(jax.random.key(2), n=300, d=16, k=3)
    cases.append(GLMModel.softmax(sm, n_classes=3))
    rd, _ = robust_data(jax.random.key(3), n=300, d=8)
    cases.append(GLMModel.robust(rd, nu=4.0, sigma=1.0, prior_scale=2.0))
    for m in cases:
        alg = api.firefly(
            m, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
            step_size=0.05, backend=backend, z_backend="fused",
        )
        trace = api.sample(alg, jax.random.key(4), 25, chunk_size=25)
        assert np.all(np.isfinite(np.asarray(trace.theta)))
        assert np.all(np.isfinite(np.asarray(trace.stats.joint_lp)))
        assert brightness.check_invariants(trace.final_state.bright)


# ---------------------------------------------------------------------------
# API contract
# ---------------------------------------------------------------------------


def test_unknown_z_backend_rejected(model):
    with pytest.raises(ValueError, match="z_backend"):
        api.firefly(model, z_backend="cuda")


def test_fused_requires_implicit_mode(model):
    with pytest.raises(ValueError, match="implicit"):
        api.firefly(model, mode="explicit", z_backend="fused")
