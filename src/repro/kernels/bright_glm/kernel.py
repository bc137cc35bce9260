"""Pallas TPU kernel: fused gather + bound-corrected likelihood (FlyMC core).

TPU adaptation of the paper's "loop over bright data" (DESIGN.md §3.1): the
bright index buffer arrives as a *scalar-prefetch* operand and the feature
matrix stays in HBM (``memory_space=ANY``). Each grid step DMAs a
(block_rows, Dp) tile — ``block_rows`` independent row copies issued
back-to-back and awaited together, so the gather overlaps instead of
serializing one (1, Dp) pipeline slot per row — and then fuses:

    tile · θᵀ  (VPU; MXU for softmax)  →  log L, log B  →  δ
    →  Σ masked log(expm1 δ)  (the Alg.-1 line-19 factor, reduced in-kernel)

Outputs: per-row δ (reused as the z-kernel's cache, Alg. 2) and a single
running total per chain, an SMEM scalar accumulated across the sequential
TPU grid — the O(C) reduction never leaves the kernel.

Chain batching: the grid's LEADING dimension is ``num_chains``. One launch
walks ``(chain, tile)`` in row-major order, so each chain's ≤capacity
workload — far too small to fill the VPU/MXU on its own — coalesces into
one long pipeline over the shared HBM-resident dataset. All per-chain
operands (bright indices, bright counts, θ) index by ``program_id(0)``;
the feature matrix is the one operand every chain shares.
:func:`bright_glm_pallas` is the single-chain entry point — literally the
``num_chains == 1`` case of :func:`bright_glm_pallas_chains`.

Families: logistic (Jaakkola–Jordan), student_t (tangent bound), softmax
(Böhning, matrix θ). All δ formulas come from :mod:`repro.core.numerics` —
the same code the jnp reference path uses, so kernel and reference cannot
drift.

Layout: θ (and K for softmax) padded to a multiple of 128 lanes; the
feature matrix arrives in its gather layout, one zero-padded (1, Dp) tile
per row (:func:`repro.kernels.common.gather_layout`), built once per
dataset by the caller. BR rows per grid step. VMEM per step: BR (1, Dp)
row tiles plus the θ block — independent of ``num_chains``.

The O(C) per-row operands (t, ξ) are pre-gathered by the ops wrapper —
they are 4–Kp·4 bytes/row next to the Dp·4 bytes/row feature gather that
this kernel exists to fuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import (
    log_expm1,
    logistic_delta,
    softmax_delta_padded,
    student_t_delta,
)

FAMILIES = ("logistic", "student_t", "softmax")


def bright_glm_pallas_chains(
    x_rows: jax.Array,  # (N, 1, Dp) gather layout, SHARED by all chains; HBM
    t: jax.Array | None,  # (K, C, 1) f32 labels; None for softmax
    xi: jax.Array,  # (K, C, 1) f32, or (K, C, Kp) tangency logits (softmax)
    idx: jax.Array,  # (K, C) int32 bright row ids, clamped to [0, N); C % BR == 0
    n_bright: jax.Array,  # (K, 1) int32 per-chain bright counts
    theta: jax.Array,  # (K, 1, Dp), or (K, Kp, Dp) zero-padded (softmax)
    family: str = "logistic",
    nu: float = 4.0,
    sigma: float = 1.0,
    n_classes: int = 0,
    block_rows: int = 128,
    interpret: bool = False,
):
    """Returns (delta (K, C, 1) f32, total (K, 1) f32).

    ``x_rows`` is the dataset in its gather layout
    (:func:`repro.kernels.common.gather_layout`): one (1, Dp) tile per row,
    zero past column D. Mosaic only DMAs whole tiles, so a one-row copy
    out of a plain (N, D) array is refused (its rows share (8, 128)
    tiles), and so is any copy whose lane extent is not a multiple of 128.
    Each grid step copies its ``block_rows`` rows tile for tile; the
    dataset is never broadcast per chain (which is what jax's default
    pallas batching rule would materialize at (K, N, D)).

    The bright ids reach SMEM one grid step's block at a time, as
    (1, 1, block_rows) blocks: SMEM holds 1 MiB on a v5e, and
    scalar-prefetching all K·C ids overflows it at the paper's capacities
    (8 chains × 90,000 slots is 2.9 MB).
    """
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; expected {FAMILIES}")
    k_chains, c = idx.shape
    dp = x_rows.shape[2]
    kt = theta.shape[1]
    assert x_rows.shape[1] == 1 and dp % 128 == 0, x_rows.shape
    assert theta.shape[2] == dp, (theta.shape, dp)
    assert c % block_rows == 0, (c, block_rows)
    br = block_rows

    softmax = family == "softmax"
    if softmax != (t is None):
        raise ValueError("t is None exactly for the softmax family")

    def kernel(nb_ref, idx_ref, x_hbm, *refs):
        if softmax:  # the Böhning δ needs no labels
            xi_ref, theta_ref, delta_ref, total_ref, rows, sems = refs
        else:
            t_ref, xi_ref, theta_ref, delta_ref, total_ref, rows, sems = refs
        ch = pl.program_id(0)
        i = pl.program_id(1)
        base = i * br

        def row_dma(r):
            return pltpu.make_async_copy(
                x_hbm.at[idx_ref[0, 0, r]], rows.at[r], sems.at[r]
            )

        def start(r, carry):
            row_dma(r).start()
            return carry

        def wait(r, carry):
            row_dma(r).wait()
            return carry

        jax.lax.fori_loop(0, br, start, 0)
        jax.lax.fori_loop(0, br, wait, 0)

        tile = rows[...].reshape(br, dp)
        theta_v = theta_ref[0]  # (kt, Dp) — this chain's θ block
        if softmax:
            eta = jax.lax.dot_general(
                tile, theta_v, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )  # (BR, Kp), f32 passes on the MXU
            delta = softmax_delta_padded(eta, xi_ref[0], n_classes)
            delta = delta[:, None]
        else:
            # One output column: a VPU multiply and lane reduction, exact
            # in f32 (the MXU would round the operands to bf16 passes).
            s = jnp.sum(tile * theta_v, axis=1, keepdims=True)  # (BR, 1)
            t_v = t_ref[0]
            xi_v = xi_ref[0]
            if family == "logistic":
                delta = logistic_delta(t_v * s, xi_v)
            else:
                delta = student_t_delta(t_v - s, xi_v, nu, sigma)

        row_id = base + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
        mask = row_id < nb_ref[ch, 0]
        delta_ref[0] = delta
        part = jnp.sum(jnp.where(mask, log_expm1(delta), 0.0))

        # TPU grid steps run sequentially in row-major (chain, tile) order,
        # so each chain's SMEM total slot is a race-free accumulator.
        @pl.when(i == 0)
        def _init():
            total_ref[ch, 0] = 0.0

        total_ref[ch, 0] += part

    kp = xi.shape[2] if softmax else 1
    tiles = c // br
    row_spec = lambda w: pl.BlockSpec((1, br, w), lambda ch, i, *_: (ch, i, 0))
    per_row = [xi] if softmax else [t, xi]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # n_bright
        grid=(k_chains, tiles),
        in_specs=[
            pl.BlockSpec(  # idx: this step's br ids, in SMEM
                (1, 1, br), lambda ch, i, *_: (ch * tiles + i, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),  # x_rows: gathered by DMA
            *([] if softmax else [row_spec(1)]),  # t
            row_spec(kp),  # xi
            pl.BlockSpec((1, kt, dp), lambda ch, i, *_: (ch, 0, 0)),  # theta
        ],
        out_specs=[
            pl.BlockSpec((1, br, 1), lambda ch, i, *_: (ch, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # total: whole (K, 1)
        ],
        scratch_shapes=[
            pltpu.VMEM((br, 1, dp), jnp.float32),
            pltpu.SemaphoreType.DMA((br,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="bright_glm",
        out_shape=(
            jax.ShapeDtypeStruct((k_chains, c, 1), jnp.float32),
            jax.ShapeDtypeStruct((k_chains, 1), jnp.float32),
        ),
        interpret=interpret,
    )(n_bright, idx.reshape(k_chains * tiles, 1, br), x_rows, *per_row, theta)


def bright_glm_pallas(
    x_rows: jax.Array,  # (N, 1, Dp) gather layout; stays in HBM
    t: jax.Array | None,  # (C, 1) f32 labels/responses; None for softmax
    xi: jax.Array,  # (C, 1) f32, or (C, Kp) tangency logits (softmax)
    idx: jax.Array,  # (C,) int32 bright row ids, clamped to [0, N); C % BR == 0
    n_bright: jax.Array,  # (1,) int32
    theta: jax.Array,  # (1, Dp), or (Kp, Dp) zero-padded (softmax)
    family: str = "logistic",
    nu: float = 4.0,
    sigma: float = 1.0,
    n_classes: int = 0,
    block_rows: int = 128,
    interpret: bool = False,
):
    """Single-chain entry point: the ``num_chains == 1`` case of
    :func:`bright_glm_pallas_chains`. Returns (delta (C, 1), total (1,))."""
    delta, total = bright_glm_pallas_chains(
        x_rows, None if t is None else t[None], xi[None], idx[None], n_bright[None], theta[None],
        family=family, nu=nu, sigma=sigma, n_classes=n_classes,
        block_rows=block_rows, interpret=interpret,
    )
    return delta[0], total[0]
