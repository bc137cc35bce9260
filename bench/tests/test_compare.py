"""``harness.compare`` on fixed states: a vector θ reads as it always has,
and a (K, D) θ is compared in its reference's coordinates."""

import math
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.ess import ess_per_coord
from bench.reference.common import Arith, log_expm1

# What compare gave on _fixed(family) before θ of any shape was taken
# (reference, bound): (checks, answers). Every number must read the same.
GOLDEN = {
    ("logistic", True): ({"lp_gap": 0.029114103688527848,
                          "delta_gap": 2.9033858650606703e-11,
                          "bright_gap": 3.913716873009914,
                          "mean_z": 43.83533634512427,
                          "sd_gap": 0.08781727498474874}, 20),
    ("robust", False): ({"lp_gap": 0.03215891859417752,
                         "mean_z": 341.9204378649112,
                         "sd_gap": 0.5100808531679776}, 14),
}
CFG = {
    "logistic": {"classes": 1, "prior_scale": 1.0, "reference_draws": 200},
    "robust": {"classes": 1, "prior_scale": 1.0, "reference_draws": 0,
               "nu": 4.0, "sigma": 1.0},
}


def _states(ref, cfg, x, t, theta_star, is_flymc, rng, chains=2, count=2):
    """Kept states near θ*, with lp and cached δ as a float32 program
    would hold them: the reference's, a little off."""
    xi = ref.tune(x, t, theta_star, cfg)
    f64 = Arith("f64")
    kept = []
    for _ in range(count):
        theta = theta_star + 0.05 * rng.standard_normal(
            (chains,) + theta_star.shape)
        st = {"theta": theta, "lp": np.zeros(chains), "bright": [],
              "delta": []}
        for c in range(chains):
            log_l, log_b, delta = ref.rows(theta[c], x, t, xi, cfg, f64)
            lp = ref.log_prior(theta[c], cfg, f64)
            if is_flymc:
                ids = np.sort(rng.choice(x.shape[0], 10, replace=False))
                lp = lp + log_b.sum() + log_expm1(delta[ids]).sum()
                st["bright"].append(ids)
                st["delta"].append(delta[ids].astype(np.float32)
                                   .astype(np.float64))
            else:
                lp = lp + log_l.sum()
            st["lp"][c] = lp + 0.01 * rng.standard_normal()
        if not is_flymc:
            del st["bright"], st["delta"]
        kept.append(st)
    return kept


def _fixed(family):
    """compare's arguments for a vector θ of ``family``, all from one seed."""
    ref = harness._module("reference", family)
    cfg = CFG[family]
    is_flymc = family == "logistic"
    rng = np.random.default_rng(7)
    n, d = 400, 5
    x = rng.standard_normal((n, d))
    x[:, -1] = 1.0
    if family == "logistic":
        t = np.where(rng.random(n) < 0.5, 1.0, -1.0)
    else:
        t = x @ rng.standard_normal(d) + rng.standard_t(4.0, n)
    theta_star = 0.3 * rng.standard_normal(d)
    kept = _states(ref, cfg, x, t, theta_star, is_flymc, rng)
    draws = theta_star + 0.1 * rng.standard_normal((2, 300, d))
    bright = None
    if is_flymc:
        bright = (draws[:, [0, 100, 200]],
                  rng.integers(0, 40, (2, 3)))
    return (ref, cfg, x, t, theta_star, kept, draws, ess_per_coord(draws),
            is_flymc, np.random.default_rng(5)), {"bright": bright}


@pytest.mark.parametrize("family,is_flymc", sorted(GOLDEN))
def test_vector_theta_reads_as_before(family, is_flymc):
    args, kw = _fixed(family)
    assert args[8] is is_flymc
    assert harness.compare(*args, **kw) == GOLDEN[family, is_flymc]


# ---- a (K, D) θ -------------------------------------------------------------

K, D = 3, 4


def _classes(theta):
    """Flat θs (P, ...) -> (K, D, ...)."""
    theta = np.asarray(theta, np.float64)
    return theta.reshape(K, D, *theta.shape[1:])


def _contrasts(theta, cfg):
    """(..., K·D) -> (..., (K-1)·D): θ_k - θ_K, what the likelihood sees."""
    th = np.asarray(theta).reshape(*np.shape(theta)[:-1], K, D)
    return (th[..., :-1, :] - th[..., -1:, :]).reshape(
        *np.shape(theta)[:-1], (K - 1) * D)


def _rows(theta, x, t, xi, cfg, ar):
    """A softmax likelihood and a bound δ below it; θ flat, (P,) or (P, M)."""
    s = np.einsum("nd,kd...->nk...", x, _classes(theta))
    t = np.asarray(t).reshape(-1).astype(int)
    idx = t.reshape((-1, 1) + (1,) * (s.ndim - 2))
    picked = np.take_along_axis(s, np.broadcast_to(
        idx, (s.shape[0], 1) + s.shape[2:]), axis=1)[:, 0]
    m = s.max(axis=1)
    log_l = picked - m - np.log(np.exp(s - m[:, None]).sum(axis=1))
    delta = 0.01 * (1.0 + s.var(axis=1))
    return log_l, log_l - delta, delta


MU = np.arange(K * D, dtype=np.float64) / 10.0
SD = 0.1


def _softmax_ref():
    """A stand-in reference of a (K, D) θ: its posterior is known, and
    only the contrasts between classes are compared."""
    return SimpleNamespace(
        tune=lambda x, t, theta_star, cfg: np.zeros(x.shape[0]),
        rows=_rows,
        log_prior=lambda theta, cfg, ar: -0.5 * np.sum(
            np.asarray(theta) ** 2, axis=-1),
        posterior=lambda x, t, cfg, rng: (
            _contrasts(MU, cfg), np.full((K - 1) * D, SD * math.sqrt(2.0)),
            np.zeros((K - 1) * D), math.inf),
        coords=_contrasts)


def _matrix_run(shift=0.0):
    """compare on (chains, iterations, K, D) draws, flattened as the
    harness flattens them. Each draw is MU plus noise of sd SD per
    coordinate plus a walk common to every class, which no likelihood of
    contrasts can see."""
    ref, cfg = _softmax_ref(), {"classes": K}
    rng = np.random.default_rng(11)
    n = 300
    x = rng.standard_normal((n, D))
    t = rng.integers(0, K, n).astype(np.float64)
    kept = _states(ref, cfg, x, t, MU, True, rng)
    chains, iters = 2, 600
    walk = np.cumsum(rng.standard_normal((chains, iters, 1, D)), axis=1)
    draws = (MU.reshape(K, D) + SD * rng.standard_normal(
        (chains, iters, K, D)) + walk)
    draws[..., 0, 0] += shift
    flat = harness.flat_theta(draws, 2)
    ess = ess_per_coord(harness.coordinates(ref, cfg, flat))
    bright = (flat[:, [0, 200, 400]], rng.integers(0, 40, (chains, 3)))
    return harness.compare(ref, cfg, x, t, MU, kept, flat, ess, True,
                           np.random.default_rng(5), bright=bright)


def test_matrix_theta_is_compared_in_its_references_coordinates():
    checks, answers = _matrix_run()
    assert set(checks) == {"lp_gap", "delta_gap", "bright_gap", "mean_z",
                           "sd_gap"}
    assert checks["lp_gap"] < 0.1 and checks["delta_gap"] < 1e-6
    # the common walk is out of the contrasts: the sound run reads sound
    assert checks["mean_z"] < 5.0 and checks["sd_gap"] < 0.1
    assert answers == 2 * 2 + 2 * 3 + 2 * (K - 1) * D


def test_matrix_theta_shift_of_one_flat_coordinate_is_caught():
    # 0.1 is about 24 standard errors of the first contrast's window mean
    assert _matrix_run(shift=0.1)[0]["mean_z"] > 15.0


def test_shift_fault_moves_flat_coordinate_0():
    import dataclasses

    import jax
    import jax.numpy as jnp

    @dataclasses.dataclass
    class Alg:
        position: object = None
        step_data: object = None

        def position_of(self, state):
            return state if self.position is None else self.position(state)

    bad = harness.Fault("shift").plant(Alg(), 25, theta_ndim=2)
    got = jax.vmap(bad.position_of)(jnp.zeros((2, K, D)))
    want = np.zeros((2, K, D))
    want[:, 0, 0] = 1.0
    np.testing.assert_array_equal(got, want)
    bad = harness.Fault("shift").plant(Alg(), 25, theta_ndim=1)
    np.testing.assert_array_equal(bad.position_of(jnp.zeros(D)),
                                  [1.0, 0.0, 0.0, 0.0])
