"""Pallas TPU kernel: streamed dark-set candidate selection (FlyMC z-update).

Algorithm 2's dark→bright proposal is a Bernoulli(q_db) per dark datum —
the only part of the z-update whose work is inherently Ω(N). The jnp
engine pays for it with three materialized (N,) uniform arrays, an (N,)
boolean z, and a full cumsum compaction; this kernel replaces all of that
with ONE streamed pass over the partition array:

  * ``arr`` (reshaped to (P/128, 128) int32 lane tiles) is the only
    length-N operand that moves — 4 bytes per datum, delivered by the
    pipelined grid in ``(block_rows, 128)`` tiles;
  * per-datum uniforms are generated *in-kernel* with counter-based
    Threefry-2x32 bits keyed on (step_key, DRAW_CAND, datum_index)
    (:mod:`repro.core.numerics` — the same function the jnp reference
    evaluates, so the streams are bit-identical). Keying on the datum
    index, not the buffer slot, keeps the realized chain bitwise invariant
    to capacity and chunk size, exactly like the jnp engine's per-datum
    draws;
  * candidate selection compares the 24-bit lanes against a static integer
    threshold ``q_bits = round(q_db · 2²⁴)`` — pure int compare, no float
    round-trip;
  * selected datum ids are compacted in-kernel into a lane-dense
    ``(cand_rows, 128)`` output buffer: TPU grid steps run sequentially,
    so the buffer and an SMEM running count are race-free accumulators
    (the same trick as ``bright_glm``'s total). Within a tile
    the expected candidate count is ``q_db · block`` (≈ 10 for the default
    tile), so extraction loops ``fori_loop``-many times over a masked
    argmin — O(candidates) reductions, not O(block²) scatter matrices.

Chain batching: the grid's LEADING dimension is ``num_chains`` — one
launch streams every chain's partition array back to back, and the
counter-RNG keying gains its chain lane through the per-chain
``(num, key_word0, key_word1)`` rows of the scalar-prefetched ``meta``
operand: each chain keeps the exact per-chain key words the vmap path
derived from its own chain key, so trajectories stay bitwise identical to
per-chain dispatch. :func:`z_candidates_pallas` is the single-chain entry
point — the ``num_chains == 1`` case of :func:`z_candidates_pallas_chains`.

The kernel emits only the compacted candidate ids + total count; the δ
evaluation for those candidates is the job of the *existing* FusedBound
machinery (``kernels/bright_glm``) on the O(cand_capacity) buffer, and the
darken/brighten accept decisions are O(C) jnp math on the same counter RNG
(:func:`repro.core.flymc._fused_z_update`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import DRAW_CAND, threefry2x32

_LANES = 128
_UNIFORM_SHIFT = 8  # int32 >> 8 (logical) = 24-bit uniform lanes


def z_candidates_pallas_chains(
    arr3d: jax.Array,  # (K, P//128, 128) int32 partition arrays, padded w/ n
    meta: jax.Array,  # (K, 3) int32 rows: [num, key_word0, key_word1]
    n: int,  # true datum count (ids >= n are padding)
    q_bits: int,  # candidate threshold: bits24 < q_bits ⇔ u < q_db
    cand_rows: int,  # output buffer rows of 128 slots (multiple of 8)
    block_rows: int = 8,
    interpret: bool = False,
):
    """Returns (cand (K, cand_rows, 128) int32 padded with n, count (K, 1)).

    Candidates appear in ``arr``-position order per chain (the same order
    the jnp reference's cumsum compaction produces), slot s at
    ``cand[k, s // 128, s % 128]``. Writes past a chain's buffer are
    dropped, and ``count`` keeps each chain's *true* total so the caller
    can raise the overflow flag that triggers the driver's
    capacity-doubling re-run.

    The buffer is lane-dense: a (slots, 1) column would be padded to 128
    lanes in VMEM (≈46 MB at 90,000 slots). The count is an SMEM scalar —
    Mosaic stores no scalars to VMEM.
    """
    k_chains, rows, lanes = arr3d.shape
    assert lanes == _LANES and rows % block_rows == 0, arr3d.shape
    assert meta.shape == (k_chains, 3), meta.shape
    assert cand_rows % 8 == 0, cand_rows
    br = block_rows
    slots = cand_rows * _LANES

    def kernel(meta_ref, arr_ref, cand_ref, count_ref):
        none = jnp.int32(2**30)  # position key of a slot already extracted
        ch = pl.program_id(0)
        i = pl.program_id(1)
        num = meta_ref[ch, 0]

        @pl.when(i == 0)
        def _init():
            cand_ref[...] = jnp.full_like(cand_ref, n)
            count_ref[ch, 0] = 0

        tile = arr_ref[0]  # (br, 128) datum ids of this chain
        row = jax.lax.broadcasted_iota(jnp.int32, (br, _LANES), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (br, _LANES), 1)
        pos = (i * br + row) * _LANES + col  # position in this chain's arr

        x0 = jnp.full((br, _LANES), DRAW_CAND, jnp.int32)
        bits, _ = threefry2x32(meta_ref[ch, 1], meta_ref[ch, 2], x0, tile)
        bits24 = jax.lax.shift_right_logical(bits, _UNIFORM_SHIFT)
        cand = (pos >= num) & (pos < n) & (bits24 < q_bits)

        cnt_tile = jnp.sum(cand.astype(jnp.int32))
        base = count_ref[ch, 0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

        def extract(j, keyed):
            # j-th candidate of this tile = the least live position. The
            # carry is int32 (positions, `none` once taken): Mosaic does
            # not carry boolean vectors through a loop.
            p = jnp.min(keyed)
            hit = keyed == p
            datum = jnp.sum(jnp.where(hit, tile, 0))
            slot = base + j

            @pl.when(slot < slots)
            def _store():
                r = jax.lax.div(slot, _LANES)  # slot ≥ 0: floor division
                old = cand_ref[0, pl.ds(r, 1), :]
                cand_ref[0, pl.ds(r, 1), :] = jnp.where(
                    lane == jax.lax.rem(slot, _LANES), datum, old
                )

            return jnp.where(hit, none, keyed)

        jax.lax.fori_loop(0, cnt_tile, extract, jnp.where(cand, pos, none))
        count_ref[ch, 0] = base + cnt_tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # meta
        grid=(k_chains, rows // br),
        in_specs=[pl.BlockSpec((1, br, _LANES), lambda ch, i, *_: (ch, i, 0))],
        out_specs=[
            pl.BlockSpec((1, cand_rows, _LANES), lambda ch, i, *_: (ch, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # count: whole (K, 1)
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="z_candidates",
        out_shape=(
            jax.ShapeDtypeStruct((k_chains, cand_rows, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((k_chains, 1), jnp.int32),
        ),
        cost_estimate=pl.CostEstimate(
            flops=50 * k_chains * rows * _LANES,  # ~threefry rounds per lane
            bytes_accessed=k_chains * (rows + cand_rows) * _LANES * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(meta, arr3d)


def z_candidates_pallas(
    arr2d: jax.Array,  # (P//128, 128) int32 partition array, padded with n
    meta: jax.Array,  # (3,) int32: [num, key_word0, key_word1]
    n: int,  # true datum count (ids >= n are padding)
    q_bits: int,  # candidate threshold: bits24 < q_bits ⇔ u < q_db
    cand_rows: int,  # output buffer rows of 128 slots (multiple of 8)
    block_rows: int = 8,
    interpret: bool = False,
):
    """Single-chain entry point: the ``num_chains == 1`` case of
    :func:`z_candidates_pallas_chains`. Returns
    (cand (cand_rows, 128) int32 padded with n, count (1,))."""
    cand, count = z_candidates_pallas_chains(
        arr2d[None], meta[None], n=n, q_bits=q_bits,
        cand_rows=cand_rows, block_rows=block_rows,
        interpret=interpret,
    )
    return cand[0], count[0]
