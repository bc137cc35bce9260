"""FlyMC exactness and mechanics (the paper's central claim, §2).

The money test: the FlyMC chain's θ-marginal must match the full-data
posterior. We check it on a small logistic problem by comparing posterior
moments against a long full-data MCMC run, for both implicit (Alg. 2) and
explicit (Alg. 1) z-kernels, untuned and MAP-tuned bounds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bounds, brightness, flymc
from repro.data import logistic_data
from repro.models.bayes_glm import GLMModel, run_regular_mcmc

jax.config.update("jax_platform_name", "cpu")

N, D = 400, 4


@pytest.fixture(scope="module")
def model():
    data = logistic_data(jax.random.key(0), n=N, d=D, separation=1.5)
    return GLMModel.logistic(data, prior_scale=2.0, xi=1.5)


@pytest.fixture(scope="module")
def reference_moments(model):
    """Long full-data RWMH chain — the ground-truth posterior moments."""
    theta0 = jnp.zeros(D)
    samples, _ = run_regular_mcmc(
        model, theta0, jax.random.key(1), 6000, kernel="rwmh", step_size=0.12
    )
    s = np.stack(samples)[1500:]
    return s.mean(0), s.std(0)


def _flymc_moments(model, kernel, mode, tuned, key, iters=6000, burn=1500):
    from repro.core import samplers

    m = model
    if tuned:
        theta_map = m.map_estimate(jax.random.key(9), steps=400)
        m = m.map_tuned(theta_map)
    spec = m.flymc_spec(
        kernel=kernel,
        capacity=128,
        cand_capacity=128,
        q_db=0.05 if tuned else 0.1,
        mode=mode,
        resample_fraction=0.2,
        adapt_target=(
            None if kernel == "slice" else samplers.TARGET_ACCEPT[kernel]
        ),
    )
    step0 = 0.03 if kernel == "mala" else 0.12
    state, _, spec = m.init_chain(spec, jnp.zeros(D), key, step_size=step0)
    samples, trace, total_q, spec = m.run_chain(spec, state, iters)
    s = np.stack(samples)[burn:]
    return s.mean(0), s.std(0), trace, total_q


@pytest.mark.parametrize("mode", ["implicit", "explicit"])
def test_flymc_matches_full_posterior(model, reference_moments, mode):
    ref_mean, ref_std = reference_moments
    mean, std, trace, _ = _flymc_moments(
        model, "rwmh", mode, tuned=False, key=jax.random.key(2)
    )
    np.testing.assert_allclose(mean, ref_mean, atol=3.5 * ref_std.max() / 10)
    np.testing.assert_allclose(std, ref_std, rtol=0.5)


def test_map_tuned_flymc_matches_and_is_cheap(model, reference_moments):
    ref_mean, ref_std = reference_moments
    mean, std, trace, total_q = _flymc_moments(
        model, "rwmh", "implicit", tuned=True, key=jax.random.key(3)
    )
    np.testing.assert_allclose(mean, ref_mean, atol=3.5 * ref_std.max() / 10)
    np.testing.assert_allclose(std, ref_std, rtol=0.5)
    # Tuned bounds ⇒ few bright points after burn-in (paper §4.1).
    brights = [t["n_bright"] for t in trace[1500:]]
    assert np.mean(brights) < 0.25 * N
    # Each iteration must query far fewer than N likelihoods on average.
    assert total_q / len(trace) < 0.6 * N


def test_mala_flymc_matches(model, reference_moments):
    ref_mean, ref_std = reference_moments
    mean, std, _, _ = _flymc_moments(
        model, "mala", "implicit", tuned=True, key=jax.random.key(4),
        iters=4000, burn=1000,
    )
    np.testing.assert_allclose(mean, ref_mean, atol=3.5 * ref_std.max() / 10)
    np.testing.assert_allclose(std, ref_std, rtol=0.5)


def test_slice_flymc_matches(model, reference_moments):
    ref_mean, ref_std = reference_moments
    mean, std, _, _ = _flymc_moments(
        model, "slice", "implicit", tuned=True, key=jax.random.key(5),
        iters=3000, burn=800,
    )
    np.testing.assert_allclose(mean, ref_mean, atol=3.5 * ref_std.max() / 10)
    np.testing.assert_allclose(std, ref_std, rtol=0.5)


def test_explicit_z_update_law_without_replacement(model):
    """Pin the explicit (Alg. 1) resampling law: the subset is a permutation
    slice — no duplicate indices, so the z/δ scatters are deterministic —
    and the realized z follows p(z=1) = -expm1(-δ) under the split keys."""
    spec = model.flymc_spec(mode="explicit", resample_fraction=0.2)
    n = model.data.x.shape[0]
    r = max(1, int(round(n * spec.resample_fraction)))
    theta = 0.1 * jnp.ones(D)
    key = jax.random.key(42)
    z0 = jax.random.bernoulli(jax.random.key(1), 0.3, (n,))
    bright = brightness.from_z(z0)
    delta_full = jnp.zeros(n)
    z_new, delta_new, queries, overflow = flymc._explicit_z_update(
        spec, model.data, key, theta, bright, delta_full
    )
    # Law re-derivation with the same key splits (this IS the pinned law:
    # change the sampling scheme and this fails).
    k_idx, k_z = jax.random.split(key)
    idx = np.asarray(
        jax.random.permutation(k_idx, jnp.arange(n, dtype=jnp.int32))[:r]
    )
    assert len(np.unique(idx)) == r  # without replacement
    # δ by the engines' own route (the bound's cancellation-free formulas).
    delta = bounds.delta(model.bound, theta, model.data)
    p_bright = -jnp.expm1(-jnp.maximum(delta[idx], 1e-10))
    z_exp = np.asarray(z0).copy()
    z_exp[idx] = np.asarray(
        jax.random.uniform(k_z, (r,), p_bright.dtype) < p_bright
    )
    np.testing.assert_array_equal(np.asarray(z_new), z_exp)
    np.testing.assert_allclose(
        np.asarray(delta_new)[idx], np.asarray(delta[idx]), rtol=1e-6
    )
    assert int(queries) == r and not bool(overflow)
    # Determinism: same inputs, same realized update.
    z2, d2, _, _ = flymc._explicit_z_update(
        spec, model.data, key, theta, bright, delta_full
    )
    np.testing.assert_array_equal(np.asarray(z_new), np.asarray(z2))
    np.testing.assert_array_equal(np.asarray(delta_new), np.asarray(d2))


def test_capacity_overflow_is_exact(model):
    """A chain run at tiny capacity (forcing growth) must equal one run at
    large capacity with the same keys — overflow handling may not change the
    realized chain."""
    theta0 = jnp.zeros(D)
    out = {}
    for cap in (16, 256):
        spec = model.flymc_spec(
            kernel="rwmh", capacity=cap, cand_capacity=cap, q_db=0.2
        )
        state, _, spec2 = model.init_chain(
            spec, theta0, jax.random.key(7), step_size=0.1
        )
        samples, trace, _, _ = model.run_chain(spec2, state, 60)
        out[cap] = np.stack(samples)
    np.testing.assert_allclose(out[16], out[256], rtol=1e-4, atol=1e-5)


def test_queries_counted(model):
    spec = model.flymc_spec(kernel="rwmh", capacity=256, cand_capacity=256)
    state, n0, spec = model.init_chain(
        spec, jnp.zeros(D), jax.random.key(8), step_size=0.1
    )
    _, trace, total_q, _ = model.run_chain(spec, state, 20)
    assert total_q > 0
    assert total_q == sum(t["lik_queries"] for t in trace)
    # implicit mode: per-iter queries ≤ bright evals + candidates ≤ N + N
    assert all(t["lik_queries"] <= 2 * N for t in trace)


def test_joint_lp_consistent_with_dense_eval(model):
    """The padded-buffer joint lp must equal a dense masked evaluation."""
    spec = model.flymc_spec(kernel="rwmh", capacity=256, cand_capacity=256)
    state, _, spec = model.init_chain(
        spec, 0.1 * jnp.ones(D), jax.random.key(10), step_size=0.1
    )
    z = brightness.z_of(state.bright)
    theta = state.sampler.theta
    delta = model.bound.log_lik(theta, model.data) - model.bound.log_bound(
        theta, model.data
    )
    dense = (
        model.log_prior(theta)
        + model.bound.collapsed(theta, model.stats)
        + jnp.sum(jnp.where(z, flymc.log_expm1(delta), 0.0))
    )
    np.testing.assert_allclose(
        float(state.sampler.lp), float(dense), rtol=1e-4, atol=1e-4
    )
