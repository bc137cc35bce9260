"""Chain-batched megakernels: batched-vs-vmap parity, bitwise.

``num_chains`` is a leading kernel-grid dimension: under ``jax.vmap`` over
the chain axis, the ``bright_glm`` and ``z_candidates`` wrappers dispatch
ONE ``pallas_call`` covering every chain (``custom_vmap`` rules in
``kernels/*/ops``), instead of jax's default per-chain pallas batching.
``repro.kernels.common.chain_batching(False)`` restores the default
lowering — the baseline every test here pins the megakernels against:

  * op level: vmapped ``bright_glm`` (all three GLM families, values and
    grads) and ``z_candidates`` are bitwise identical between the two
    dispatches AND to a per-chain python loop over the single-chain entry
    points;
  * chain level: a multi-chain fused trajectory (``backend="pallas"`` +
    ``z_backend="fused"``) through ``api.sample`` is bitwise identical
    batched vs vmap for all three families, including a mid-chunk
    capacity-doubling overflow re-run;
  * driver: the committed-chunk fold is keyed capacity-independently, so
    an overflow retry reuses the compiled fold instead of recompiling it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import numerics
from repro.data import logistic_data, robust_data, softmax_data
from repro.kernels import common
from repro.kernels.bright_glm.ops import bright_glm
from repro.kernels.z_update.ops import z_candidates
from repro.models.bayes_glm import GLMModel

jax.config.update("jax_platform_name", "cpu")

N, D, K = 400, 4, 3


# ---------------------------------------------------------------------------
# Op level: one megakernel launch ≡ per-chain dispatch, bitwise
# ---------------------------------------------------------------------------


def _family_operands(family):
    key = jax.random.key(0)
    x = jax.random.normal(key, (N, D))
    if family == "softmax":
        k_cls = 3
        t = jax.random.randint(jax.random.key(1), (N,), 0, k_cls)
        xi = 0.5 * jax.random.normal(jax.random.key(2), (N, k_cls))
        theta = 0.1 * jax.random.normal(jax.random.key(3), (K, k_cls, D))
    else:
        t = jnp.sign(jax.random.normal(jax.random.key(1), (N,)))
        xi = 1.5 * jnp.ones(N)
        theta = 0.1 * jax.random.normal(jax.random.key(3), (K, D))
    idx = jax.random.randint(jax.random.key(4), (K, 40), 0, N)
    nb = jnp.asarray([40, 17, 0], jnp.int32)
    return x, t, xi, idx, nb, theta


@pytest.mark.parametrize("family", ["logistic", "student_t", "softmax"])
def test_bright_glm_batched_matches_vmap_and_loop(family):
    x, t, xi, idx, nb, theta = _family_operands(family)
    xr = common.gather_layout(x)
    f = lambda i, n, th: bright_glm(xr, t, xi, i, n, th, family=family,
                                    interpret=True)
    with common.chain_batching(True):
        d_b, t_b = jax.vmap(f)(idx, nb, theta)
    with common.chain_batching(False):
        d_v, t_v = jax.vmap(f)(idx, nb, theta)
    np.testing.assert_array_equal(np.asarray(d_b), np.asarray(d_v))
    np.testing.assert_array_equal(np.asarray(t_b), np.asarray(t_v))
    for c in range(K):  # ... and to the single-chain entry point
        d_1, t_1 = f(idx[c], nb[c], theta[c])
        np.testing.assert_array_equal(np.asarray(d_b[c]), np.asarray(d_1))
        np.testing.assert_array_equal(np.asarray(t_b[c]), np.asarray(t_1))


def test_bright_glm_batched_grads_match():
    """MALA/HMC path: grads through the custom VJP under vmap are identical
    whichever dispatch the forward used (the backward is the shared jnp
    reference either way)."""
    x, t, xi, idx, nb, theta = _family_operands("logistic")
    xr = common.gather_layout(x)
    f = lambda th, i, n: bright_glm(xr, t, xi, i, n, th, family="logistic",
                                    interpret=True)[1]
    with common.chain_batching(True):
        g_b = jax.vmap(jax.grad(f))(theta, idx, nb)
    with common.chain_batching(False):
        g_v = jax.vmap(jax.grad(f))(theta, idx, nb)
    np.testing.assert_array_equal(np.asarray(g_b), np.asarray(g_v))


def test_z_candidates_batched_matches_vmap_and_loop():
    """At N = 997 (one 8-row tile) and N = 12,214 (tiles of 32 rows)."""
    from repro.core import brightness
    from repro.kernels.z_update.ops import tile_rows

    assert tile_rows(12214) > 8
    for size in (997, 12214):
        arrs, nums, kws = [], [], []
        for c in range(K):
            z0 = jax.random.bernoulli(jax.random.key(c), 0.15 * (c + 1),
                                      (size,))
            st = brightness.from_z(z0)
            arrs.append(st.arr)
            nums.append(st.num)
            kws.append(numerics.key_words_of(jax.random.key(40 + c)))
        arrs, nums, kws = jnp.stack(arrs), jnp.stack(nums), jnp.stack(kws)
        f = lambda a, n, k: z_candidates(a, n, k, 0.05, 64, interpret=True)
        with common.chain_batching(True):
            c_b, n_b = jax.vmap(f)(arrs, nums, kws)
        with common.chain_batching(False):
            c_v, n_v = jax.vmap(f)(arrs, nums, kws)
        np.testing.assert_array_equal(np.asarray(c_b), np.asarray(c_v))
        np.testing.assert_array_equal(np.asarray(n_b), np.asarray(n_v))
        for c in range(K):
            c_1, n_1 = f(arrs[c], nums[c], kws[c])
            np.testing.assert_array_equal(np.asarray(c_b[c]),
                                          np.asarray(c_1))
            assert int(n_b[c]) == int(n_1)


# ---------------------------------------------------------------------------
# Chain level: fused multi-chain trajectories, batched ≡ vmap, bitwise
# ---------------------------------------------------------------------------


def _fused_model(family):
    if family == "softmax":
        sm = softmax_data(jax.random.key(2), n=300, d=8, k=3)
        return GLMModel.softmax(sm, n_classes=3)
    if family == "student_t":
        rd, _ = robust_data(jax.random.key(3), n=300, d=6)
        return GLMModel.robust(rd, nu=4.0, sigma=1.0, prior_scale=2.0)
    data = logistic_data(jax.random.key(0), n=N, d=D, separation=1.5)
    return GLMModel.logistic(data, prior_scale=2.0, xi=1.5)


def _run_fused(model, batched, *, capacity=96, cand_capacity=None, iters=40,
               chunk=20, q_db=0.05, kernel="rwmh"):
    if cand_capacity is None:
        cand_capacity = capacity
    with common.chain_batching(batched):
        alg = api.firefly(
            model, kernel=kernel, capacity=capacity,
            cand_capacity=cand_capacity,
            q_db=q_db, step_size=0.08, backend="pallas", z_backend="fused",
        )
        return api.sample(alg, jax.random.key(11), iters, num_chains=K,
                          chunk_size=chunk)


@pytest.mark.parametrize("family", ["logistic", "student_t", "softmax"])
def test_fused_multichain_batched_matches_vmap(family):
    model = _fused_model(family)
    t_b = _run_fused(model, True)
    t_v = _run_fused(model, False)
    np.testing.assert_array_equal(np.asarray(t_b.theta), np.asarray(t_v.theta))
    np.testing.assert_array_equal(
        np.asarray(t_b.stats.n_bright), np.asarray(t_v.stats.n_bright)
    )
    np.testing.assert_array_equal(
        np.asarray(t_b.stats.lik_queries), np.asarray(t_v.stats.lik_queries)
    )
    # chains genuinely differ (independent keys), so the equality is not
    # comparing K copies of one chain
    assert not np.array_equal(np.asarray(t_b.theta[0]),
                              np.asarray(t_b.theta[1]))


# Overflow by construction, whatever the seed: at q_db = 0.02 the initial
# bright set holds 2·q_db·N = 16 points per chain in expectation, twice the
# bright capacity of 8, so init grows to 16–32; candidates grow in lockstep
# only to 2–4, while a step proposes q_db·N ≈ 8 of them, so the first chunk
# overflows and is re-run at doubled capacities.
_OVERFLOW = dict(capacity=8, cand_capacity=1, q_db=0.02)


def test_fused_multichain_overflow_rerun_batched_matches_vmap():
    """Mid-chunk capacity-doubling re-run through the megakernel path lands
    bitwise on the vmap path's trajectory (and both grew)."""
    model = _fused_model("logistic")
    t_b = _run_fused(model, True, iters=120, chunk=24, **_OVERFLOW)
    assert t_b.algorithm.spec.capacity > 8, "must exercise an overflow"
    t_v = _run_fused(model, False, iters=120, chunk=24, **_OVERFLOW)
    assert t_v.algorithm.spec.capacity == t_b.algorithm.spec.capacity
    np.testing.assert_array_equal(np.asarray(t_b.theta), np.asarray(t_v.theta))


def test_mala_multichain_batched_matches_vmap():
    """Gradient kernel end-to-end: the θ-update differentiates through the
    megakernel forward under vmap."""
    model = _fused_model("logistic")
    t_b = _run_fused(model, True, kernel="mala", iters=20, chunk=10)
    t_v = _run_fused(model, False, kernel="mala", iters=20, chunk=10)
    np.testing.assert_array_equal(np.asarray(t_b.theta), np.asarray(t_v.theta))


# ---------------------------------------------------------------------------
# Driver: overflow retries reuse the compiled committed-chunk fold
# ---------------------------------------------------------------------------


def test_overflow_rerun_reuses_fold_executable():
    from repro.api import driver as driver_lib

    model = _fused_model("logistic")
    driver_lib._JIT_CACHE.clear()
    trace = _run_fused(model, True, iters=120, chunk=24, **_OVERFLOW)
    assert trace.algorithm.spec.capacity > 8  # the run really overflowed
    folds = [k for k in driver_lib._JIT_CACHE if k[0] == "fold"]
    scans = [k for k in driver_lib._JIT_CACHE if k[0] == "scan"]
    assert len(folds) == 1, folds  # one fold serves every capacity
    # the scan re-traced per grown capacity (shape change), keyed on it
    assert len({k[6] for k in scans}) == len(scans) and len(scans) >= 2, scans
