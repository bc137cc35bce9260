"""The repro.api surface: chain-law equivalence, multi-chain, sync counts.

The driver's contract (ISSUE 1 acceptance criteria):
  * zero host syncs inside a chunk — ≤ 1 device_get per chunk_size iters;
  * the realized chain is bitwise independent of chunk size and of buffer
    capacity, including across mid-chain capacity-doubling re-runs;
  * the legacy ``run_chain`` shim reproduces ``sample()`` exactly;
  * ``num_chains > 1`` vmaps chains and feeds split-R̂ diagnostics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import brightness, diagnostics, samplers
from repro.core import bounds as bounds_lib
from repro.data import logistic_data
from repro.models.bayes_glm import GLMModel

jax.config.update("jax_platform_name", "cpu")

N, D = 400, 4


@pytest.fixture(scope="module")
def model():
    data = logistic_data(jax.random.key(0), n=N, d=D, separation=1.5)
    return GLMModel.logistic(data, prior_scale=2.0, xi=1.5)


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------


def test_kernel_registry_uniform_interface():
    for name in ("rwmh", "mala", "slice", "hmc"):
        ks = samplers.get_kernel(name)
        assert callable(ks.step_fn)
        assert ks.scale_param in ("step_size", "width")
    with pytest.raises(KeyError, match="unknown θ-kernel"):
        samplers.get_kernel("nuts")


def test_bound_registry_resolves_names_and_instances():
    assert isinstance(bounds_lib.get_bound("logistic"), bounds_lib.LogisticBound)
    assert isinstance(
        bounds_lib.get_bound("jaakkola-jordan"), bounds_lib.LogisticBound
    )
    b = bounds_lib.StudentTBound(nu=3.0)
    assert bounds_lib.get_bound(b) is b
    with pytest.raises(KeyError, match="unknown bound"):
        bounds_lib.get_bound("no-such-bound")
    with pytest.raises(TypeError, match="Bound protocol"):
        bounds_lib.get_bound(object())


def test_firefly_rejects_unknown_kernel(model):
    with pytest.raises(KeyError, match="unknown θ-kernel"):
        api.firefly(model, kernel="not-a-kernel")


# ---------------------------------------------------------------------------
# Chain-law equivalence
# ---------------------------------------------------------------------------


def test_sample_matches_explicit_step_loop(model):
    """sample() == a hand-rolled host loop over alg.step with the same keys."""
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    key = jax.random.key(11)
    trace = api.sample(alg, key, 40, chunk_size=16)

    k_init, k_steps = jax.random.split(key)
    state = jax.jit(alg.init)(k_init, alg.default_position)
    step = jax.jit(alg.step)  # jit: eager op-by-op float fusion differs
    thetas = []
    for i in range(40):
        state, _ = step(jax.random.fold_in(k_steps, i), state)
        thetas.append(np.asarray(state.sampler.theta))
    np.testing.assert_array_equal(np.asarray(trace.theta[0]), np.stack(thetas))


def test_chunk_size_invariance(model):
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    key = jax.random.key(3)
    t1 = api.sample(alg, key, 60, chunk_size=7)
    t2 = api.sample(alg, key, 60, chunk_size=60)
    np.testing.assert_array_equal(np.asarray(t1.theta), np.asarray(t2.theta))
    np.testing.assert_array_equal(
        np.asarray(t1.stats.n_bright), np.asarray(t2.stats.n_bright)
    )


def test_capacity_overflow_mid_chain_is_exact(model):
    """A chain that overflows at init and mid-run (capacity below the
    initial bright set, candidate buffer below a step's proposals) must
    bitwise match one run at ample capacity throughout:
    per-datum RNG makes the trajectory capacity-invariant, and the driver
    re-runs the overflowed chunk from the saved pre-chunk state."""
    key = jax.random.key(9)

    def run(cap, cand_cap):
        alg = api.firefly(
            model, kernel="rwmh", capacity=cap, cand_capacity=cand_cap,
            q_db=0.02, step_size=0.1,
        )
        return api.sample(alg, key, 300, chunk_size=32)

    # Overflow by construction: the initial bright set (2·q_db·N = 16
    # expected) exceeds capacity 8, and after init growth the candidate
    # buffer (2–4 slots) is below the q_db·N ≈ 8 candidates a step proposes.
    t_small = run(8, 1)
    grown = t_small.algorithm.spec.capacity
    assert grown > 8, "test must exercise a mid-chain capacity overflow"
    t_big = run(N, N)  # full capacity: can never overflow
    np.testing.assert_array_equal(
        np.asarray(t_small.theta), np.asarray(t_big.theta)
    )


def test_legacy_run_chain_shim_matches_sample(model):
    spec = model.flymc_spec(
        kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1
    )
    state, _, spec = model.init_chain(
        spec, jnp.zeros(D), jax.random.key(5), step_size=0.1
    )
    samples, trace_dicts, total_q, _ = model.run_chain(spec, state, 30)

    alg = api.algorithm_from_spec(spec, model.data, model.stats)
    trace = api.sample(alg, state.rng, 30, init_state=state)
    np.testing.assert_array_equal(np.stack(samples), np.asarray(trace.theta[0]))
    assert total_q == int(trace.total_queries)
    assert [t["n_bright"] for t in trace_dicts] == list(
        np.asarray(trace.stats.n_bright[0])
    )


def test_thinning(model):
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    key = jax.random.key(4)
    full = api.sample(alg, key, 40, chunk_size=20)
    thinned = api.sample(alg, key, 40, chunk_size=20, thin=4)
    assert thinned.theta.shape == (1, 10, D)
    np.testing.assert_array_equal(
        np.asarray(thinned.theta[0]), np.asarray(full.theta[0][3::4])
    )
    # stats stay per-iteration
    assert thinned.stats.lik_queries.shape == (1, 40)


# ---------------------------------------------------------------------------
# Host-sync accounting
# ---------------------------------------------------------------------------


def test_at_most_one_device_get_per_chunk(model, monkeypatch):
    alg = api.firefly(
        model, kernel="rwmh", capacity=256, cand_capacity=256, q_db=0.05,
        step_size=0.1,
    )
    api.sample(alg, jax.random.key(2), 8, chunk_size=8)  # warm / pre-grow
    calls = {"n": 0}
    orig = jax.device_get

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)
    num_samples, chunk_size = 128, 32
    api.sample(alg, jax.random.key(2), num_samples, chunk_size=chunk_size)
    n_chunks = num_samples // chunk_size
    # one overflow check per chunk + one init-overflow check + one final
    # stats transfer for the int64 query total (post-sampling)
    assert calls["n"] <= n_chunks + 2, calls["n"]


# ---------------------------------------------------------------------------
# Multi-chain
# ---------------------------------------------------------------------------


def test_multi_chain_shapes_and_rhat(model):
    alg = api.firefly(
        model, kernel="rwmh", capacity=256, cand_capacity=256, q_db=0.05,
        step_size=0.12, adapt_target="auto",
    )
    n_chains, iters = 4, 400
    trace = api.sample(
        alg, jax.random.key(8), iters, num_chains=n_chains, chunk_size=100
    )
    assert trace.theta.shape == (n_chains, iters, D)
    assert trace.stats.lik_queries.shape == (n_chains, iters)
    # chains differ (independent keys) ...
    assert not np.allclose(trace.theta[0], trace.theta[1])
    # ... but target the same posterior: split-R̂ sane on each coordinate
    s = np.asarray(trace.theta)[:, iters // 2 :, :]
    rhats = [diagnostics.split_r_hat(s[:, :, j]) for j in range(D)]
    assert all(r < 1.5 for r in rhats), rhats
    # single chain is reproduced exactly by chain 0 of the vmapped run
    one = api.sample(alg, jax.random.key(8), iters, num_chains=1)
    assert one.theta.shape == (1, iters, D)


def test_multi_chain_distinct_positions(model):
    alg = api.firefly(
        model, kernel="rwmh", capacity=256, cand_capacity=256, q_db=0.05,
        step_size=0.1,
    )
    pos = jnp.stack([jnp.zeros(D), 0.5 * jnp.ones(D)])
    trace = api.sample(
        alg, jax.random.key(1), 10, num_chains=2, init_position=pos,
        chunk_size=10,
    )
    assert trace.theta.shape == (2, 10, D)


# ---------------------------------------------------------------------------
# Regular-MCMC baseline through the same driver
# ---------------------------------------------------------------------------


def test_regular_mcmc_through_driver(model):
    alg = api.regular_mcmc(model, kernel="rwmh", step_size=0.1,
                           adapt_target="auto")
    trace = api.sample(alg, jax.random.key(6), 50, chunk_size=25)
    assert trace.theta.shape == (1, 50, D)
    # full-data cost model: every iteration queries all N likelihoods
    assert np.all(np.asarray(trace.stats.lik_queries) == N)
    assert int(trace.total_queries) == 50 * N


def test_regular_mcmc_slice_kernel(model):
    """Slice kernel through the registry: no width/step_size special-casing."""
    alg = api.regular_mcmc(model, kernel="slice", step_size=0.5)
    trace = api.sample(alg, jax.random.key(6), 20, chunk_size=10)
    # slice makes a variable number of evaluations per iteration, all ≥ 2
    assert np.all(np.asarray(trace.stats.lik_queries) >= 2 * N)


def test_trace_resume(model):
    """final_state + algorithm allow seamless continuation."""
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    key = jax.random.key(12)
    t1 = api.sample(alg, key, 30, chunk_size=15)
    t2 = api.sample(
        t1.algorithm, jax.random.key(13), 20, init_state=t1.final_state
    )
    assert t2.theta.shape == (1, 20, D)
    # resumed chain continues from where t1 ended
    state = t1.final_state
    assert np.allclose(
        np.asarray(t1.theta[0, -1]), np.asarray(state.sampler.theta)
    )


def test_bright_state_invariants_preserved(model):
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    trace = api.sample(alg, jax.random.key(14), 25)
    assert brightness.check_invariants(trace.final_state.bright)


# ---------------------------------------------------------------------------
# Exactness regressions: warmup-only adaptation & resume key stream
# ---------------------------------------------------------------------------


def test_flymc_step_size_frozen_after_warmup(model):
    """Step-size adaptation must be warmup-only: adapting forever means the
    post-warmup chain never follows a fixed Markov kernel. log_step moves
    during warmup and is bitwise constant afterward."""
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.05,
        step_size=0.1, adapt_target=0.234, num_warmup=20,
    )
    key = jax.random.key(21)

    def log_step_after(iters):
        return np.asarray(api.sample(alg, key, iters).final_state.log_step)

    ls5, ls20, ls60 = log_step_after(5), log_step_after(20), log_step_after(60)
    assert not np.array_equal(ls5, ls20), "must adapt during warmup"
    np.testing.assert_array_equal(ls20, ls60)  # bitwise frozen after warmup


def test_regular_mcmc_step_size_frozen_after_warmup(model):
    alg = api.regular_mcmc(
        model, kernel="rwmh", step_size=0.1, adapt_target=0.234, num_warmup=10
    )
    key = jax.random.key(22)

    def log_step_after(iters):
        return np.asarray(api.sample(alg, key, iters).final_state.log_step)

    ls3, ls10, ls40 = log_step_after(3), log_step_after(10), log_step_after(40)
    assert not np.array_equal(ls3, ls10)
    np.testing.assert_array_equal(ls10, ls40)


def test_resume_continues_key_stream_not_replays_it(model):
    """sample(..., init_state=s) must offset the per-iteration fold-in
    counter by s.iteration: two 20-step segments resumed with the same key
    are bitwise one contiguous 40-step run, instead of the second segment
    replaying the first segment's exact key stream."""
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    key = jax.random.key(23)
    state0 = jax.jit(alg.init)(jax.random.key(24), alg.default_position)

    contiguous = api.sample(alg, key, 40, init_state=state0, chunk_size=16)
    seg1 = api.sample(alg, key, 20, init_state=state0, chunk_size=16)
    seg2 = api.sample(alg, key, 20, init_state=seg1.final_state, chunk_size=16)
    np.testing.assert_array_equal(
        np.concatenate(
            [np.asarray(seg1.theta[0]), np.asarray(seg2.theta[0])]
        ),
        np.asarray(contiguous.theta[0]),
    )
    # ... which in particular means the resumed segment is not a replay:
    # replaying seg1's keys from seg1's final state would re-use its
    # uniforms; pin the counter offset explicitly via a hand-rolled loop.
    state, thetas = seg1.final_state, []
    step = jax.jit(alg.step)
    for i in range(20, 40):
        state, _ = step(jax.random.fold_in(key, i), state)
        thetas.append(np.asarray(state.sampler.theta))
    np.testing.assert_array_equal(np.asarray(seg2.theta[0]), np.stack(thetas))


def test_multi_chain_resume_split_equals_contiguous(model):
    """init_state with a leading (num_chains,) axis: two resumed 20-step
    segments must be bitwise one contiguous 40-step run, per chain (the
    vmap'd step already supported it; the driver now accepts the state and
    offsets every chain's fold-in counter by the shared iteration)."""
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    key = jax.random.key(31)
    init_keys = jax.random.split(jax.random.key(30), 2)
    pos = jnp.broadcast_to(
        alg.default_position, (2,) + alg.default_position.shape
    )
    state0 = jax.jit(jax.vmap(alg.init))(init_keys, pos)

    contiguous = api.sample(
        alg, key, 40, num_chains=2, init_state=state0, chunk_size=16
    )
    seg1 = api.sample(
        alg, key, 20, num_chains=2, init_state=state0, chunk_size=16
    )
    seg2 = api.sample(
        alg, key, 20, num_chains=2, init_state=seg1.final_state, chunk_size=16
    )
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(seg1.theta), np.asarray(seg2.theta)], 1),
        np.asarray(contiguous.theta),
    )


def test_multi_chain_resume_from_final_state(model):
    """A previous multi-chain run's final_state resumes directly."""
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    t1 = api.sample(alg, jax.random.key(32), 30, num_chains=3, chunk_size=15)
    t2 = api.sample(
        alg, jax.random.key(33), 20, num_chains=3, init_state=t1.final_state
    )
    assert t2.theta.shape == (3, 20, D)
    np.testing.assert_array_equal(  # continues where t1 ended
        np.asarray(t1.final_state.iteration), np.full(3, 30)
    )


def test_multi_chain_resume_rejects_bad_states(model):
    alg = api.firefly(
        model, kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1,
        step_size=0.1,
    )
    single = jax.jit(alg.init)(jax.random.key(34), alg.default_position)
    with pytest.raises(ValueError, match="leading"):
        api.sample(
            alg, jax.random.key(0), 10, num_chains=2, init_state=single
        )
    two = api.sample(alg, jax.random.key(35), 10, num_chains=2).final_state
    with pytest.raises(ValueError, match="leading"):
        api.sample(alg, jax.random.key(0), 10, num_chains=3, init_state=two)
    skewed = two._replace(iteration=jnp.asarray([10, 7], jnp.int32))
    with pytest.raises(ValueError, match="different iterations"):
        api.sample(alg, jax.random.key(0), 10, num_chains=2, init_state=skewed)


def test_resume_offset_also_fixes_legacy_host_loop(model):
    """run_chain's collect= host-loop fallback shares the resume contract."""
    from repro.core import flymc

    spec = model.flymc_spec(
        kernel="rwmh", capacity=128, cand_capacity=128, q_db=0.1
    )
    state, _, spec = model.init_chain(
        spec, jnp.zeros(D), jax.random.key(25), step_size=0.1
    )
    collect = lambda s: np.asarray(s.sampler.theta)
    full, *_ = flymc.run_chain(
        spec, model.data, model.stats, state, 30, collect=collect
    )
    first, *_ = flymc.run_chain(
        spec, model.data, model.stats, state, 15, collect=collect
    )
    # state after 15 steps, then resume 15 more through the host loop
    mid = state
    step = jax.jit(api.algorithm_from_spec(spec, model.data, model.stats).step)
    for i in range(15):
        mid, _ = step(jax.random.fold_in(state.rng, i), mid)
    rest, *_ = flymc.run_chain(
        spec, model.data, model.stats, mid._replace(rng=state.rng), 15,
        collect=collect,
    )
    np.testing.assert_array_equal(
        np.stack(full), np.concatenate([np.stack(first), np.stack(rest)])
    )
