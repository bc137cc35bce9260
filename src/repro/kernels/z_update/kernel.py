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
    (the same trick as ``bright_glm``'s total).

Compaction is vector work whose cost does not depend on how many
candidates a tile holds — no loop over candidates, no reduction to a
scalar per candidate:

  * **rank** — an inclusive prefix count of the candidate mask in
    row-major position order: log-step lane rolls within each row, then
    log-step sublane rolls over the row totals. Candidate k of the tile
    goes to slot ``base + k`` (``base`` = the running count);
  * **move** — the tile is staged below ``_PAD_ROWS`` empty rows whose
    first row stands for output row ``base // 128``, so slot ``base + k``
    is staged position ``base % 128 + k`` and every candidate moves
    toward the front by a non-negative distance. The moves are made bit
    by bit, least significant first (one flat-order roll per bit); since
    the distances never decrease along the tile, two candidates never
    meet, and the candidates leave the network in slot order;
  * **place** — each staged row that holds a candidate is one whole output
    row, stored under a lane mask: ``ceil((base % 128 + cnt) / 128)``
    masked row stores per tile, one or two at q_db · block ≈ 82.

Tile size follows from N (:func:`repro.kernels.z_update.ops.tile_rows`):
a tile's cost no longer scales with its candidates, so the largest tile
of up to 64 rows (8,192 datums) that pads N by at most 1/16 is the
cheapest.

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
_PAD_ROWS = 8  # empty staged rows ahead of the tile: one whole sublane tile


def _pull(x: jax.Array, step: int) -> jax.Array:
    """``y[p] = x[p + step]`` in row-major flat order, wrapping at the end."""
    rows = x.shape[0]
    if step % _LANES == 0:
        return pltpu.roll(x, rows - step // _LANES, 0)
    rot = pltpu.roll(x, _LANES - step, 1)  # rot[r, l] = x[r, (l + step) % 128]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(col >= _LANES - step, pltpu.roll(rot, rows - 1, 0), rot)


def _prefix(x: jax.Array, axis: int) -> jax.Array:
    """Inclusive prefix sum of an int32 2-D array along ``axis``."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < x.shape[axis]:
        x = x + jnp.where(idx >= s, pltpu.roll(x, s, axis), 0)
        s *= 2
    return x


def _inclusive_count(m: jax.Array) -> jax.Array:
    """Row-major inclusive prefix sum of an int32 (rows, 128) array."""
    within = _prefix(m, 1)
    totals = jnp.broadcast_to(within[:, _LANES - 1:], m.shape)
    return within + _prefix(totals, 0) - totals


def z_candidates_pallas_chains(
    arr3d: jax.Array,  # (K, P//128, 128) int32 partition arrays, padded w/ n
    meta: jax.Array,  # (K, 3) int32 rows: [num, key_word0, key_word1]
    n: int,  # true datum count (ids >= n are padding)
    q_bits: int,  # candidate threshold: bits24 < q_bits ⇔ u < q_db
    cand_rows: int,  # output buffer rows of 128 slots (multiple of 8)
    block_rows: int,  # tile rows (multiple of 8)
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
    br = block_rows
    assert lanes == _LANES and br % 8 == 0 and rows % br == 0, (
        arr3d.shape, br)
    assert meta.shape == (k_chains, 3), meta.shape
    assert cand_rows % 8 == 0, cand_rows
    staged_rows = _PAD_ROWS + br
    # Every move distance is below staged_rows · 128: one network pass per bit.
    steps = [1 << b for b in range((staged_rows * _LANES - 1).bit_length())]

    def kernel(meta_ref, arr_ref, cand_ref, count_ref, staged_ref):
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

        m = cand.astype(jnp.int32)
        cnt_tile = jnp.sum(m)
        base = count_ref[ch, 0]
        count_ref[ch, 0] = base + cnt_tile

        @pl.when(cnt_tile > 0)
        def _compact():
            off = jax.lax.rem(base, _LANES)  # base ≥ 0
            staged_pos = (row + _PAD_ROWS) * _LANES + col
            # Distance to the front; -1 marks a staged position with no
            # candidate (every bit set, so the move test checks >= 0).
            dist = jnp.where(cand, staged_pos - off - _inclusive_count(m) + 1,
                             -1)
            empty = jnp.full((_PAD_ROWS, _LANES), -1, jnp.int32)
            dist = jnp.concatenate([empty, dist], axis=0)
            ids = jnp.concatenate([empty, tile], axis=0)
            for step in steps:
                d_in, ids_in = _pull(dist, step), _pull(ids, step)
                move = (d_in >= 0) & ((d_in & step) != 0)
                stay = (dist >= 0) & ((dist & step) == 0)
                dist = jnp.where(move, d_in, jnp.where(stay, dist, -1))
                ids = jnp.where(move, ids_in, ids)
            staged_ref[...] = jnp.where(dist >= 0, ids, -1)

            first = jax.lax.div(base, _LANES)
            n_out = jax.lax.div(off + cnt_tile + _LANES - 1, _LANES)

            def place(g, carry):
                r = first + g

                @pl.when(r < cand_rows)
                def _store():
                    got = staged_ref[pl.ds(g, 1), :]
                    old = cand_ref[0, pl.ds(r, 1), :]
                    cand_ref[0, pl.ds(r, 1), :] = jnp.where(got >= 0, got, old)

                return carry

            jax.lax.fori_loop(0, n_out, place, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # meta
        grid=(k_chains, rows // br),
        in_specs=[pl.BlockSpec((1, br, _LANES), lambda ch, i, *_: (ch, i, 0))],
        out_specs=[
            pl.BlockSpec((1, cand_rows, _LANES), lambda ch, i, *_: (ch, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # count: whole (K, 1)
        ],
        scratch_shapes=[pltpu.VMEM((staged_rows, _LANES), jnp.int32)],
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
    block_rows: int,  # tile rows (multiple of 8)
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
