"""The program's own instrumentation: named scopes on the compiled chunk
and fold programs, host spans in a profile of ``api.sample``, and the
driver's counters (``ChunkEvent.driver``, ``Trace.driver``)."""

import glob
import importlib
import os
import re
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.api import driver
from repro.data import logistic_data, softmax_data
from repro.models.bayes_glm import GLMModel

jax.config.update("jax_platform_name", "cpu")

N, D = 400, 4
K = 3  # classes of the softmax model
ROOT = Path(__file__).resolve().parents[1]
_WRAPPED = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\((.*)\)")
FLYMC_SCOPES = ("flymc.theta", "flymc.z", "flymc.z.candidates",
                "flymc.z.delta", "flymc.z.flips", "flymc.refresh",
                "driver.outputs")


@pytest.fixture(scope="module")
def model():
    data = logistic_data(jax.random.key(0), n=N, d=D, separation=1.5)
    return GLMModel.logistic(data, prior_scale=2.0, xi=1.5)


def _scopes(op_name):
    """The components of an op-name path, transform wrappers removed:
    "a/vmap(flymc.z)/flymc.z.flips/scatter" -> [a, flymc.z, flymc.z.flips,
    scatter]."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.fullmatch(part)) is not None:
            part = m.group(1)
        out.append(part)
    return out


def _chunk_op_names(alg, chains, theta_shape=(D,)):
    keys = jax.random.split(jax.random.key(0), chains)
    pos = jnp.zeros((chains, *theta_shape))
    state = jax.vmap(alg.init_data, in_axes=(0, 0, None, None))(
        keys, pos, alg.data, alg.stats)
    if chains == 1:
        state, keys = jax.tree.map(lambda l: l[0], (state, keys))
    fn = driver._make_scan_fn(alg, chains, 2)
    text = fn.lower(state, keys, jnp.int32(0), alg.data,
                    alg.stats).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _innermost(op_name, before, scopes):
    """The innermost of ``scopes`` in the path before component ``before``."""
    parts = _scopes(op_name)
    found = [p for p in parts[:parts.index(before)] if p in scopes]
    return found[-1] if found else None


@pytest.mark.parametrize("chains", [1, 2])
def test_fused_chunk_program_carries_the_step_scopes(model, chains):
    alg = api.firefly(model, kernel="rwmh", capacity=64, cand_capacity=64,
                      q_db=0.05, step_size=0.1, backend="pallas",
                      z_backend="fused")
    names = _chunk_op_names(alg, chains)
    parts = {p for n in names for p in _scopes(n)}
    assert set(FLYMC_SCOPES) <= parts
    # The streamed candidate selection sits under its own scope, and the
    # likelihood kernel under the θ-update's and the candidates' δ.
    kernel = [n for n in names if "z_candidates" in _scopes(n)]
    assert kernel
    assert {_innermost(n, "z_candidates", FLYMC_SCOPES) for n in kernel} == {
        "flymc.z.candidates"}
    glm = [n for n in names if "bright_glm" in _scopes(n)]
    assert {_innermost(n, "bright_glm", FLYMC_SCOPES) for n in glm} == {
        "flymc.theta", "flymc.z.delta"}


def test_jnp_engine_and_regular_chunk_programs_carry_their_scopes(model):
    jnp_alg = api.firefly(model, kernel="rwmh", capacity=64,
                          cand_capacity=64, q_db=0.05, step_size=0.1)
    parts = {p for n in _chunk_op_names(jnp_alg, 2) for p in _scopes(n)}
    assert {"flymc.theta", "flymc.z", "flymc.refresh"} <= parts
    assert not parts & {"flymc.z.candidates", "flymc.z.flips"}
    reg = api.regular_mcmc(model, kernel="slice", step_size=0.5)
    parts = {p for n in _chunk_op_names(reg, 2) for p in _scopes(n)}
    assert {"regular.theta", "driver.outputs"} <= parts
    assert not any(p.startswith("flymc.") for p in parts)


GRAD_SCOPES = {"flymc.theta.grad": "flymc.theta",
               "flymc.refresh.grad": "flymc.refresh"}


def _scope_of():
    """``bench.scopes.scope_of``: how the benchmark names an op's scope."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    return importlib.import_module("bench.scopes").scope_of


@pytest.fixture(scope="module")
def softmax_model():
    data = softmax_data(jax.random.key(1), n=N, d=D, k=K, sharpness=1.0)
    return GLMModel.softmax(data, n_classes=K, prior_scale=1.0)


@pytest.mark.parametrize("chains", [1, 4])
def test_gradient_backward_carries_the_grad_scopes(softmax_model, chains):
    """MALA through the fused kernel: the custom VJP's backward is named
    by the phase that differentiates it, and no forward op is."""
    scope_of = _scope_of()
    alg = api.firefly(softmax_model, kernel="mala", capacity=64,
                      cand_capacity=64, q_db=0.05, step_size=0.01,
                      backend="pallas", z_backend="fused")
    names = _chunk_op_names(alg, chains, (K, D))
    graded = [n for n in names if scope_of(n) in GRAD_SCOPES]
    assert {scope_of(n) for n in graded} == set(GRAD_SCOPES)
    for n in graded:
        # A backward op, under the phase that asked for the gradient.
        assert "transpose(" in n, n
        assert GRAD_SCOPES[scope_of(n)] in _scopes(n), n
    # The backward's work, the rows' gather and their logits, is named.
    kinds = {n.rsplit("/", 1)[-1] for n in graded}
    assert {"gather", "dot_general"} <= kinds, kinds
    # No forward op carries a grad scope.
    forward = [n for n in names if "transpose(" not in n]
    assert forward
    assert not {p for n in forward for p in _scopes(n)} & set(GRAD_SCOPES)


def test_no_grad_scope_without_a_gradient(softmax_model):
    scope_of = _scope_of()
    alg = api.firefly(softmax_model, kernel="rwmh", capacity=64,
                      cand_capacity=64, q_db=0.05, step_size=0.01,
                      backend="pallas", z_backend="fused")
    names = _chunk_op_names(alg, 4, (K, D))
    assert "flymc.theta" in {scope_of(n) for n in names}
    parts = {p for n in names for p in _scopes(n)}
    assert not parts & set(GRAD_SCOPES)
    assert not any(scope_of(n) in GRAD_SCOPES for n in names)


def test_fold_program_carries_its_scope(model):
    alg = api.regular_mcmc(model, kernel="rwmh", step_size=0.1)
    state = alg.init(jax.random.key(0), jnp.zeros(D))
    pos_s, stats_s = alg.output_structs(jax.eval_shape(lambda: state))
    col = api.FullTrace()
    carries = {"trace": col.init(4, pos_s, stats_s)}
    zeros = lambda s: jnp.zeros((2,) + s.shape, s.dtype)
    fold = driver.make_collector_fold({"trace": col}, False)
    text = fold.lower(carries, zeros(pos_s),
                      jax.tree.map(zeros, stats_s)).compile().as_text()
    assert any("driver.fold" in _scopes(n)
               for n in re.findall(r'op_name="([^"]*)"', text))


def _overflowing(model):
    # The initial bright set (2·q_db·N = 16 expected) exceeds capacity 8,
    # and after init growth a step's ≈ 8 candidates overflow the candidate
    # buffer: chunks overflow mid-run and are re-run at doubled capacity.
    return api.firefly(model, kernel="rwmh", capacity=8, cand_capacity=1,
                       q_db=0.02, step_size=0.1)


@pytest.mark.parametrize("chains", [1, 2])
def test_counters_count_reruns_and_the_iterations_they_threw_away(
        model, chains):
    seen = []
    out = api.sample(_overflowing(model), jax.random.key(9), 300,
                     num_chains=chains, chunk_size=32,
                     on_chunk=lambda e: seen.append((e.size, e.driver)))
    final = out.driver
    assert final.chunks == len(seen) == 10
    assert final.reruns >= 1
    # Each chunk's re-runs happen before its boundary: the snapshots say
    # which chunk (of which length) was run again, and how often.
    rerun = [(size, d.reruns - prev.reruns) for (size, d), prev in zip(
        seen, [driver.DriverCounters()] + [d for _, d in seen])]
    assert sum(n for _, n in rerun) == final.reruns
    assert final.rerun_iters == sum(chains * size * n for size, n in rerun)
    assert [d.chunks for _, d in seen] == list(range(1, 11))
    for name in ("dispatch_s", "wait_s", "regrow_s", "fold_s", "hook_s"):
        assert getattr(final, name) > 0


def test_counters_without_a_hook_or_an_overflow(model):
    alg = api.firefly(model, kernel="rwmh", capacity=N, cand_capacity=N,
                      q_db=0.02, step_size=0.1)
    out = api.sample(alg, jax.random.key(1), 64, chunk_size=16)
    d = out.driver
    assert (d.chunks, d.reruns, d.rerun_iters, d.regrow_s, d.hook_s) == (
        4, 0, 0, 0.0, 0.0)
    assert d.dispatch_s > 0 and d.wait_s > 0


def test_profile_records_the_driver_spans(model, tmp_path):
    alg = _overflowing(model)
    key = jax.random.key(9)
    api.sample(alg, key, 96, chunk_size=32)  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = api.sample(alg, key, 96, chunk_size=32, on_chunk=lambda e: None)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    counts = Counter(e.name for plane in data.planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines for e in line.events
                     if e.name.startswith("repro.sample."))
    d = out.driver
    assert d.chunks == 3 and d.reruns >= 1
    init, dispatch, wait, regrow, fold, on_chunk, finalize = driver.SPANS
    assert counts == {
        init: 1, finalize: 1,
        dispatch: d.chunks + d.reruns, wait: d.chunks + d.reruns,
        regrow: d.reruns, fold: d.chunks, on_chunk: d.chunks,
    }
