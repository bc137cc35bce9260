"""Backend dispatch: the fused Pallas θ-update vs the jnp reference path.

Three layers of guarantee, cheapest to strongest:
  * joint-log-posterior parity (value, δ cache, and ∇θ) at fixed θ for
    every fused family, including the matrix-θ softmax;
  * chain-level equivalence: ``backend="pallas"`` (interpret off-TPU) run
    through ``repro.api.sample`` produces statistically equivalent
    posteriors to ``backend="jnp"`` on the quickstart problem;
  * API contract: unknown backends and non-fused bounds are rejected
    up front.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import brightness, flymc
from repro.data import logistic_data, softmax_data
from repro.models.bayes_glm import GLMModel

jax.config.update("jax_platform_name", "cpu")

N, D = 400, 4


@pytest.fixture(scope="module")
def tuned_model():
    data = logistic_data(jax.random.key(0), n=N, d=D, separation=1.5)
    model = GLMModel.logistic(data, prior_scale=2.0, xi=1.5)
    theta_map = model.map_estimate(jax.random.key(9), steps=300)
    return model.map_tuned(theta_map)


def _joint_pair(model, capacity=128, kernel="rwmh"):
    """(f_jnp, f_pallas) over the same bright buffer, plus a θ to probe."""
    fs = {}
    for backend in ("jnp", "pallas"):
        alg = api.firefly(model, kernel=kernel, capacity=capacity,
                          backend=backend)
        state = jax.jit(alg.init)(jax.random.key(1), alg.default_position)
        idx, mask = brightness.bright_buffer(state.bright, capacity)
        fs[backend] = flymc.make_joint_logpost(
            alg.spec, alg.data, model.stats, idx, mask
        )
    return fs["jnp"], fs["pallas"], mask


def test_joint_logpost_parity_logistic(tuned_model):
    f_jnp, f_pallas, mask = _joint_pair(tuned_model)
    theta = 0.3 * jnp.ones(D)
    (lp_j, d_j) = f_jnp(theta)
    (lp_p, d_p) = f_pallas(theta)
    np.testing.assert_allclose(float(lp_j), float(lp_p), rtol=1e-5)
    m = np.asarray(mask)
    np.testing.assert_allclose(
        np.where(m, d_j, 0.0), np.where(m, d_p, 0.0), rtol=1e-4, atol=1e-5
    )
    g_j = jax.grad(lambda t: f_jnp(t)[0])(theta)
    g_p = jax.grad(lambda t: f_pallas(t)[0])(theta)
    np.testing.assert_allclose(g_j, g_p, rtol=1e-3, atol=1e-4)


def test_joint_logpost_parity_softmax():
    data = softmax_data(jax.random.key(2), n=300, d=16, k=3)
    model = GLMModel.softmax(data, n_classes=3)
    f_jnp, f_pallas, mask = _joint_pair(model, capacity=256)
    theta = 0.1 * jnp.ones((3, 16))
    lp_j, _ = f_jnp(theta)
    lp_p, _ = f_pallas(theta)
    np.testing.assert_allclose(float(lp_j), float(lp_p), rtol=1e-4)
    g_j = jax.grad(lambda t: f_jnp(t)[0])(theta)
    g_p = jax.grad(lambda t: f_pallas(t)[0])(theta)
    np.testing.assert_allclose(g_j, g_p, rtol=1e-3, atol=1e-4)


def test_joint_logpost_parity_student_t():
    from repro.data import robust_data

    data, _ = robust_data(jax.random.key(3), n=300, d=8)
    model = GLMModel.robust(data, nu=4.0, sigma=1.0, prior_scale=2.0)
    f_jnp, f_pallas, _ = _joint_pair(model, capacity=256)
    theta = 0.05 * jnp.ones(8)
    lp_j, _ = f_jnp(theta)
    lp_p, _ = f_pallas(theta)
    np.testing.assert_allclose(float(lp_j), float(lp_p), rtol=1e-4)


def test_pallas_chain_statistically_equivalent(tuned_model):
    """Acceptance: the full quickstart chain through the fused kernel
    (interpret off-TPU) matches the jnp backend's posterior."""
    key = jax.random.key(5)
    moments = {}
    for backend in ("jnp", "pallas"):
        alg = api.firefly(
            tuned_model, kernel="rwmh", capacity=128, cand_capacity=128,
            q_db=0.05, step_size=0.12, adapt_target="auto", backend=backend,
        )
        trace = api.sample(alg, key, 800, chunk_size=200)
        s = np.asarray(trace.theta[0])[200:]
        moments[backend] = (s.mean(0), s.std(0))
        assert np.all(np.isfinite(np.asarray(trace.stats.joint_lp)))
    mean_j, std_j = moments["jnp"]
    mean_p, std_p = moments["pallas"]
    # Same key → same proposals; fp-level lp differences can flip an accept
    # decision, so compare posteriors statistically, not trajectories.
    np.testing.assert_allclose(mean_p, mean_j, atol=4.0 * std_j.max() / 10)
    np.testing.assert_allclose(std_p, std_j, rtol=0.5)


def test_pallas_chain_mala_grads():
    """Gradient kernels drive the chain through the custom VJP."""
    data = logistic_data(jax.random.key(11), n=200, d=3, separation=1.5)
    model = GLMModel.logistic(data, prior_scale=2.0, xi=1.5)
    alg = api.firefly(model, kernel="mala", capacity=128, cand_capacity=128,
                      q_db=0.1, step_size=0.05, backend="pallas")
    trace = api.sample(alg, jax.random.key(6), 60, chunk_size=30)
    assert np.all(np.isfinite(np.asarray(trace.theta)))
    assert np.all(np.isfinite(np.asarray(trace.stats.joint_lp)))


def test_unknown_backend_rejected(tuned_model):
    with pytest.raises(ValueError, match="backend"):
        api.firefly(tuned_model, backend="cuda")


def test_pallas_requires_fused_bound(tuned_model):
    class MinimalBound:
        """Implements Bound but not the fused hook."""

        name = "minimal"

        def log_lik(self, theta, data):
            return jnp.zeros(data.x.shape[0])

        def log_bound(self, theta, data):
            return jnp.full(data.x.shape[0], -0.1)

        def suffstats(self, data):
            from repro.core.bounds import CollapsedStats

            d = data.x.shape[1]
            return CollapsedStats(
                jnp.zeros((d, d)), jnp.zeros(d), jnp.zeros(())
            )

        def collapsed(self, theta, stats):
            return jnp.zeros(())

        def tighten(self, theta_map, data):
            return data

    with pytest.raises(ValueError, match="FusedBound"):
        api.firefly(
            tuned_model, bound=MinimalBound(), backend="pallas"
        )
    # ...and the same bound is fine on the jnp path.
    api.firefly(tuned_model, bound=MinimalBound(), backend="jnp")


def test_pallas_rejects_inherited_hook_with_overridden_math(tuned_model):
    """A subclass changing log_lik must not silently inherit the parent's
    fused kernel — the kernel hard-codes the parent's math."""
    from repro.core.bounds import LogisticBound, fused_family_of

    class TemperedLogistic(LogisticBound):
        @staticmethod
        def log_lik(theta, data):
            return 0.5 * LogisticBound.log_lik(theta, data)

    assert fused_family_of(TemperedLogistic()) is None
    with pytest.raises(ValueError, match="FusedBound"):
        api.firefly(tuned_model, bound=TemperedLogistic(), backend="pallas")

    # Re-declaring the hook is an explicit opt-in and is honored.
    class RenamedLogistic(LogisticBound):
        name = "renamed"
        fused_family = "logistic"

    assert fused_family_of(RenamedLogistic()) == "logistic"
    api.firefly(tuned_model, bound=RenamedLogistic(), backend="pallas")


def test_pallas_rejects_mixin_supplied_math(tuned_model):
    """A sibling mixin ahead of the declarer in the MRO changes the math
    without subclassing it — the guard must catch that route too, not just
    direct subclass overrides."""
    from repro.core.bounds import LogisticBound, fused_family_of

    class TemperedMixin:
        @staticmethod
        def log_lik(theta, data):
            return 0.5 * LogisticBound.log_lik(theta, data)

    class MixedIn(TemperedMixin, LogisticBound):
        pass

    assert fused_family_of(MixedIn()) is None
    with pytest.raises(ValueError, match="FusedBound"):
        api.firefly(tuned_model, bound=MixedIn(), backend="pallas")
