"""The kernel-level static verifier (repro.analysis.kernels).

Same both-sides discipline as ``tests/test_analysis.py``: every analysis
is exercised on a known-BAD fixture it must catch AND the known-good twin
it must pass — a verifier whose detectors go quiet is worse than none.
The fixtures encode the failure classes the kernel analyses exist for:

  kernel-bounds    an unclamped scalar-prefetch index driving a ref read
                   (what ``kernels.common.clamp_index`` exists to prevent)
  kernel-padding   an unmasked reduction over ``pad_to`` sentinel lanes
  kernel-race      a revisited-block accumulator under ``parallel``
                   dimension semantics, and an undeclared accumulator
                   (the sequential-grid contract in ``kernels/common.py``)
  kernel-bytes     an expected-total drift between the BlockSpec-derived
                   traffic model and the pinned number

Plus the expected-pass pins for the repo's real kernels: every
``kernel.*`` registry entry stays green, the race classifications match
the declared accumulator contracts, and the derived bytes model
reproduces the hand-written ``_bytes_model`` formulas it replaced in
``benchmarks/bright_glm.py`` and ``benchmarks/z_update.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import analysis
from repro.analysis import registry
from repro.analysis.kernels import (
    BytesModelRule,
    GridRaceRule,
    derive,
    derive_traffic,
    find_kernel_calls,
    kernel_rules,
)
from repro.analysis.kernels.intervals import check_bounds
from repro.analysis.kernels.race import classify_outputs
from repro.analysis.kernels.taint import check_taint
from repro.kernels import common

jax.config.update("jax_platform_name", "cpu")


def _first_call(fn, *args):
    (call, *rest) = find_kernel_calls(jax.make_jaxpr(fn)(*args))
    assert not rest
    return call


# ---------------------------------------------------------------------------
# kernel-bounds: interval abstract interpretation of ref indices
# ---------------------------------------------------------------------------


def _gather_fn(clamp: bool):
    """One row gathered by a scalar-prefetch index into an (8, 128) block.

    The bad twin indexes with the raw prefetched scalar — nothing bounds
    it below the 8-row block — exactly the bug class
    ``kernels.common.clamp_index`` guards the real kernels against.
    """

    def kernel(s_ref, x_ref, o_ref):
        i = s_ref[0]
        if clamp:
            i = jnp.clip(i, 0, 7)
        o_ref[0, :] = x_ref[i, :]

    def fn(s, x):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(1,),
                in_specs=[pl.BlockSpec((8, 128), lambda g, s: (0, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda g, s: (0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            interpret=True,
        )(s, x)

    return fn


def _gather_args():
    return jnp.zeros((4,), jnp.int32), jnp.zeros((8, 128), jnp.float32)


def test_bounds_catches_unclamped_prefetch_index():
    call = _first_call(_gather_fn(clamp=False), *_gather_args())
    findings = check_bounds(call)
    assert findings, "unclamped dynamic index must be flagged"
    assert any(f.ref == "x_ref" and f.dim == 8 for f in findings)


def test_bounds_passes_clamped_index():
    call = _first_call(_gather_fn(clamp=True), *_gather_args())
    assert check_bounds(call) == []


def test_bounds_rule_through_engine():
    report = analysis.check(
        _gather_fn(False), *_gather_args(),
        rules=kernel_rules(), name="fixture.bounds",
    )
    assert report.rule_status("kernel-bounds") == "fail"


# ---------------------------------------------------------------------------
# kernel-padding: sentinel taint through unmasked reductions
# ---------------------------------------------------------------------------


def _pad_reduce_fn(masked: bool):
    """Sum over a lane axis padded 100 → 128 with sentinel 7.0."""

    def kernel(v_ref, o_ref):
        v = v_ref[...]
        if masked:
            lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            v = jnp.where(lane < 100, v, 0.0)
        o_ref[0, 0] = jnp.sum(v)

    def fn(vals):
        padded = jnp.pad(vals, ((0, 0), (0, 28)), constant_values=7.0)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
            interpret=True,
        )(padded)

    return fn


def test_taint_catches_unmasked_padded_reduction():
    call = _first_call(_pad_reduce_fn(masked=False),
                       jnp.zeros((8, 100), jnp.float32))
    findings = check_taint(call)
    assert findings and any(1 in f.axes for f in findings)


def test_taint_passes_iota_masked_reduction():
    call = _first_call(_pad_reduce_fn(masked=True),
                       jnp.zeros((8, 100), jnp.float32))
    assert check_taint(call) == []


# ---------------------------------------------------------------------------
# kernel-race: revisited output blocks vs grid semantics
# ---------------------------------------------------------------------------


def _accum_fn(parallel: bool):
    """Classic revisited-block accumulator over a 4-step grid."""

    def kernel(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += x_ref[...]

    params = {}
    if parallel:
        params["compiler_params"] = pltpu.TPUCompilerParams(
            dimension_semantics=("parallel",)
        )

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=True,
            **params,
        )(x)

    return fn


_ACCUM_X = jnp.zeros((32, 128), jnp.float32)


def test_race_classifies_revisited_output():
    call = _first_call(_accum_fn(parallel=False), _ACCUM_X)
    (cls,) = classify_outputs(call)
    assert cls.dep_axes == () and cls.revisited == (0,)


def test_race_flags_undeclared_accumulator():
    report = analysis.check(
        _accum_fn(False), _ACCUM_X,
        rules=kernel_rules(), name="fixture.race",
    )
    assert report.rule_status("kernel-race") == "fail"
    assert any(f.details.get("kind") == "undeclared-accumulator"
               for f in report.findings)


def test_race_passes_declared_accumulator():
    report = analysis.check(
        _accum_fn(False), _ACCUM_X,
        rules=kernel_rules(accumulators={0: (0,)}), name="fixture.race",
    )
    assert report.rule_status("kernel-race") == "pass"


def test_race_flags_parallel_accumulator_even_when_declared():
    """Declaring an accumulator never excuses parallel semantics — the
    write-write race is real regardless of intent (see the
    sequential-grid-accumulator contract in ``kernels/common.py``)."""
    report = analysis.check(
        _accum_fn(True), _ACCUM_X,
        rules=kernel_rules(accumulators={0: (0,)}), name="fixture.race",
    )
    assert report.rule_status("kernel-race") == "fail"
    assert any(f.details.get("kind") == "parallel-race"
               for f in report.findings)


# ---------------------------------------------------------------------------
# kernel-bytes: BlockSpec-derived traffic model
# ---------------------------------------------------------------------------


def test_bytes_model_accumulator_fixture():
    call = _first_call(_accum_fn(False), _ACCUM_X)
    model = derive(call)
    # input: 4 distinct (8,128) f32 blocks; output: ONE revisited block.
    assert model["per_operand"]["x_ref"]["bytes"] == 4 * 8 * 128 * 4
    assert model["per_operand"]["outputs"]["bytes"] == 8 * 128 * 4
    assert model["total"] == 5 * 8 * 128 * 4


def test_bytes_rule_catches_expected_total_drift():
    report = analysis.check(
        _accum_fn(False), _ACCUM_X,
        rules=kernel_rules(accumulators={0: (0,)},
                           expected_bytes={"kernel": 123}),
        name="fixture.bytes",
    )
    assert report.rule_status("kernel-bytes") == "fail"


def test_bytes_rule_records_metrics():
    report = analysis.check(
        _accum_fn(False), _ACCUM_X,
        rules=kernel_rules(accumulators={0: (0,)},
                           expected_bytes={"kernel": 5 * 8 * 128 * 4}),
        name="fixture.bytes",
    )
    assert report.ok
    assert report.metrics["kernel_bytes"]["kernel"]["total"] == 5 * 8 * 128 * 4


# ---------------------------------------------------------------------------
# derived model == the retired hand-written benchmark models
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,c", [(5000, 21, 1024), (2000, 21, 512)])
def test_bright_derived_bytes_reproduce_hand_model(n, d, c):
    """PR 8 deleted the hand pallas term from benchmarks/bright_glm.py;
    the derived model must reproduce it exactly at the benchmark shapes:
    C·D·4 row DMAs + lane-padded θ + 3 C-vectors + the scalar total."""
    from benchmarks.bright_glm import _bytes_model

    dp = common.pad_to(d, 128)
    model = _bytes_model(n, d, c)
    assert model["pallas"] == c * d * 4 + dp * 4 + 3 * c * 4 + 4


@pytest.mark.parametrize("n,c", [(4096, 1024), (2048, 512)])
def test_z_derived_bytes_reproduce_hand_model_when_tiled(n, c):
    """benchmarks/z_update.py's retired hand terms, at exactly-tiled N
    (the hand model ignored tile padding; the derived model charges the
    real padded stream, so they agree only when pad_to is a no-op):
    arr streams once (4N), the candidate writeback + count is 4·Cp + 4."""
    from benchmarks.z_update import _bytes_model

    assert common.pad_to(max(n, 1024), 1024) == n  # tiled: models comparable
    terms = _bytes_model(n, c, 0.01)["fused"]["terms"]
    assert terms["kernel_arr_ref"] == 4 * n
    candp = common.pad_to(max(c, 8), 8)
    assert (terms["kernel_outputs[0]"] + terms["kernel_outputs[1]"]
            == 4 * candp + 4)
    # the retired 10·4·C O(C) term = derived cand writeback + retained glue
    assert 4 * candp + terms["bright_buffers_O(C)"] == 10 * 4 * c


def test_z_derived_bytes_charge_real_padding():
    """At the benchmark's untiled N=5000 the kernel streams the padded
    (5120,) array — the derived model says so; the hand model lied by
    120 rows. This is the point of deriving from BlockSpecs."""
    from benchmarks.z_update import _bytes_model

    terms = _bytes_model(5000, 1024, 0.01)["fused"]["terms"]
    assert terms["kernel_arr_ref"] == 4 * common.pad_to(5000, 1024)


# ---------------------------------------------------------------------------
# expected-pass pins: the repo's real kernels stay green
# ---------------------------------------------------------------------------

_KERNEL_ENTRIES = [n for n in registry.REGISTRY if n.startswith("kernel.")]


def test_every_pallas_entry_point_is_registered():
    assert len(_KERNEL_ENTRIES) == 10


@pytest.mark.parametrize("name", _KERNEL_ENTRIES)
def test_kernel_entry_point_passes(name):
    report = registry.REGISTRY[name]()
    assert report.ok, [str(f) for f in report.unexpected_failures]
    for rule in ("kernel-bounds", "kernel-race",
                 "kernel-padding", "kernel-bytes"):
        assert rule in report.rules_run


def test_bright_race_classification_pin():
    """bright-GLM: δ follows the row axis; the total accumulates over it
    (output 1 revisits grid axis 1 — the declared accumulator)."""
    call = _first_call(registry._bright_fn("logistic"),
                       *registry._bright_args("logistic"))
    classes = classify_outputs(call)
    by_io = {c.io_index: c for c in classes}
    assert by_io[1].revisited == (1,)
    assert not by_io[0].revisited


def test_z_race_classification_pin():
    """z-update: candidate buffer AND count both accumulate across the
    row-block sweep (grid axis 1), at an N of two tiles."""
    call = _first_call(registry._z_fn(),
                       registry._s((registry._ZN,), jnp.int32),
                       registry._s((), jnp.int32),
                       registry._s((2,), jnp.int32))
    classes = classify_outputs(call)
    assert {c.io_index: c.revisited for c in classes} == {0: (1,), 1: (1,)}


def test_chain_megakernel_bytes_scale_linearly():
    """The chain-batched dispatch must cost exactly K× one chain — the
    shared operands are re-streamed per chain step, nothing is K²."""
    one = registry.REGISTRY["kernel.bright_glm.logistic"]()
    k = registry.REGISTRY["kernel.bright_glm.chains"]()
    assert (k.metrics["kernel_bytes"]["kernel"]["total"]
            == 4 * one.metrics["kernel_bytes"]["kernel"]["total"])


def test_derive_traffic_names_every_pallas_call():
    models = derive_traffic(registry._bright_fn("logistic"),
                            *registry._bright_args("logistic"))
    assert list(models) == ["kernel"]


# ---------------------------------------------------------------------------
# sweep integration: coverage + xpass discipline
# ---------------------------------------------------------------------------


def test_sweep_stays_green_and_covers_kernels():
    summary = registry.run_registry()
    assert summary.ok, summary.format_table()
    names = [r.entry_point for r in summary.reports]
    assert len(names) >= 17
    assert all(n in names for n in _KERNEL_ENTRIES)
    # the jnp z-engine's O(N) xfail must still be observed, not quiet
    step_jnp = next(r for r in summary.reports if r.entry_point == "step.jnp")
    assert step_jnp.rule_status("cost-model") == "xfail"


def test_kernel_xpass_fails_report():
    """An expected-fail kernel rule that passes is a blind detector."""
    report = analysis.check(
        _gather_fn(True), *_gather_args(),
        rules=kernel_rules(), name="fixture.xpass",
        expect_fail={"kernel-bounds"},
    )
    assert not report.ok
    assert report.rule_status("kernel-bounds") == "xpass"
