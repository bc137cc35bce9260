"""The softmax FlyMC joint density against the benchmark's plain reference.

``api.firefly``'s softmax model (Böhning bound, the fused bright-GLM
kernel interpreted, the fused z-engine) gives, at seeded θ and bright sets,
the joint log density of (θ, z), the δ it caches for the bright rows and
the gradient MALA moves along. ``bench/reference/softmax.py`` computes the
same from the paper's formulas in NumPy float64, importing nothing of the
program.
"""

import importlib
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api
from repro.core import brightness, flymc
from repro.data import softmax_data
from repro.models.bayes_glm import GLMModel

jax.config.update("jax_platform_name", "cpu")

ROOT = Path(__file__).resolve().parents[1]
N, D, K = 300, 16, 3
CFG = {"classes": K, "prior_scale": 1.0}
# f32 against float64. The joint density sums ≈ N bound terms of a few
# nats each through the collapsed statistics (Σ xxᵀ, Σ x rᵀ, a constant):
# f32 keeps ≈ 7 digits of that ≈ 10³-nat total (2-3e-4 seen).
LP_ATOL = 1e-3
# δ ≤ 0.1 here, formed by the kernel without cancelling two O(1) log terms
# (numerics.softmax_delta_padded): f32 rounding of η, ≈ 6e-8 seen.
DELTA_ATOL = 1e-6
# The central difference of the float64 reference at h = 1e-4 is exact to
# O(h²·third derivative), which log(e^δ - 1) makes large where a bright
# row's δ is ≈ 1e-4; the program's gradient is f32 (the backward
# re-evaluates the rows in jnp, the prior and collapsed terms in closed
# form). Differences up to 6e-4 were seen on directional derivatives of
# 5 to 120.
GRAD_RTOL, GRAD_ATOL, H = 1e-4, 2e-3, 1e-4


def _reference():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    ref = importlib.import_module("bench.reference.softmax")
    common = importlib.import_module("bench.reference.common")
    return ref, common.Arith("f64"), common.log_expm1


@pytest.fixture(scope="module")
def problem():
    data = softmax_data(jax.random.key(3), n=N, d=D, k=K, sharpness=1.0)
    model = GLMModel.softmax(data, n_classes=K, prior_scale=1.0)
    theta_star = model.map_estimate(jax.random.key(4), steps=300)
    tuned = model.map_tuned(theta_star)
    alg = api.firefly(tuned, kernel="mala", capacity=128, cand_capacity=128,
                      q_db=0.05, step_size=0.01, backend="pallas",
                      z_backend="fused")
    return data, theta_star, alg


def _state(alg, theta, seed):
    # A seeded bright set of about a fifth of the rows.
    z0 = jax.random.bernoulli(jax.random.key(100 + seed), 0.2, (N,))
    return jax.jit(lambda th: flymc.init_chain_state(
        alg.spec, alg.data, alg.stats, th, jax.random.key(seed), z0=z0))(
            theta)


def _reference_joint(ref, ar, log_expm1, x, t, xi, bright):
    def joint(theta):
        _, log_b, delta = ref.rows(theta, x, t, xi, CFG, ar)
        return (ref.log_prior(theta, CFG, ar) + log_b.sum()
                + log_expm1(delta[bright]).sum())
    return joint


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_joint_density_and_delta_match_the_reference(problem, seed):
    ref, ar, log_expm1 = _reference()
    data, theta_star, alg = problem
    x, t = np.asarray(data.x, np.float64), np.asarray(data.t)
    xi = ref.tune(x, t, np.asarray(theta_star).reshape(-1), CFG)
    theta = theta_star + 0.05 * jax.random.normal(jax.random.key(seed),
                                                  theta_star.shape)
    st = _state(alg, theta, seed)
    num = int(st.bright.num)
    assert 0 < num <= alg.spec.capacity
    bright = np.asarray(st.bright.arr)[:num]
    flat = np.asarray(theta, np.float64).reshape(-1)
    want = _reference_joint(ref, ar, log_expm1, x, t, xi, bright)(flat)
    np.testing.assert_allclose(float(st.sampler.lp), want, rtol=0,
                               atol=LP_ATOL)
    _, _, delta = ref.rows(flat, x, t, xi, CFG, ar)
    np.testing.assert_allclose(np.asarray(st.delta_full)[bright],
                               delta[bright], rtol=0, atol=DELTA_ATOL)


def test_mala_gradient_matches_reference_differences(problem):
    ref, ar, log_expm1 = _reference()
    data, theta_star, alg = problem
    x, t = np.asarray(data.x, np.float64), np.asarray(data.t)
    xi = ref.tune(x, t, np.asarray(theta_star).reshape(-1), CFG)
    theta = theta_star + 0.05 * jax.random.normal(jax.random.key(7),
                                                  theta_star.shape)
    st = _state(alg, theta, 7)
    bright = np.asarray(st.bright.arr)[:int(st.bright.num)]
    joint = _reference_joint(ref, ar, log_expm1, x, t, xi, bright)
    flat = np.asarray(theta, np.float64).reshape(-1)
    grad = np.asarray(st.sampler.grad, np.float64).reshape(-1)
    rng = np.random.default_rng(8)
    for _ in range(8):
        v = rng.standard_normal(flat.size)
        v /= np.linalg.norm(v)
        fd = (joint(flat + H * v) - joint(flat - H * v)) / (2 * H)
        np.testing.assert_allclose(grad @ v, fd, rtol=GRAD_RTOL,
                                   atol=GRAD_ATOL)


def test_the_gradient_is_the_one_a_mala_step_uses(problem):
    """The refresh after a step and a fresh evaluation at the step's θ and
    bright set give the same lp and gradient: the state MALA proposes
    from is the density's own."""
    _, theta_star, alg = problem
    st = _state(alg, theta_star, 11)
    new, _ = jax.jit(lambda s: flymc.flymc_step(
        alg.spec, alg.data, alg.stats, s))(st)
    idx, mask = brightness.bright_buffer(new.bright, alg.spec.capacity)
    f = flymc.make_joint_logpost(alg.spec, alg.data, alg.stats, idx, mask)
    (lp, _), grad = jax.jit(jax.value_and_grad(f, has_aux=True))(
        new.sampler.theta)
    np.testing.assert_allclose(float(new.sampler.lp), float(lp), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new.sampler.grad),
                               np.asarray(grad), rtol=1e-5, atol=1e-4)
