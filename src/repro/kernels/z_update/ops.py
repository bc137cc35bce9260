"""Wrapper for the z-candidate kernel: layout, padding, interpret fallback.

Entry point for ``FlyMCSpec.z_backend = "fused"``
(:func:`repro.core.flymc._fused_z_update`). The partition array is padded
to a whole number of ``(block_rows, 128)`` tiles with the sentinel id ``N``
(masked in-kernel by ``pos < N``) and handed to the streaming kernel; the
compacted candidate buffer comes back sliced to ``cand_capacity`` with the
true (possibly overflowing) candidate count alongside.

Batching over the chain axis goes through a ``custom_vmap`` rule (the same
scheme as ``kernels/bright_glm/ops``): the driver's multi-chain step
lowers to ONE :func:`~repro.kernels.z_update.kernel
.z_candidates_pallas_chains` launch whose grid leads with ``num_chains``
and whose scalar-prefetched ``meta`` rows carry each chain's
``(num, key_word0, key_word1)`` — the per-chain counter-RNG key lane that
keeps the batched trajectories bitwise identical to per-chain dispatch.

Candidate selection is pure integer work on non-differentiable operands
(indices and RNG bits), so unlike ``bright_glm`` no custom VJP is needed —
gradients never flow through z-moves.
"""

from __future__ import annotations

from functools import lru_cache

import jax  # annotations only (jax.Array); dispatch goes through common
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.z_update.kernel import (
    z_candidates_pallas,
    z_candidates_pallas_chains,
)
from repro.kernels.z_update.ref import q_threshold_bits


def tile_rows(n: int) -> int:
    """Rows of 128 datums per grid step: the largest of 64, 32, 16 and 8
    whose padding of ``n`` is at most ``n // 16`` (8 when none is).

    The kernel's cost per tile no longer grows with the candidates it
    holds, so the fewest, largest tiles win until padding adds work."""
    for rows in (64, 32, 16):
        block = rows * 128
        if common.pad_to(max(n, block), block) - n <= n // 16:
            return rows
    return 8


@lru_cache(maxsize=None)
def _pallas_dispatch(n, q_bits, cand_rows, block_rows, interpret):
    """The pallas_call dispatch as a ``custom_vmap`` function (memoized on
    the static config): plain call = single-chain kernel; vmap over chains
    = one chain-grid megakernel launch
    (:func:`repro.kernels.common.make_chain_dispatch`)."""
    kw = dict(n=n, q_bits=q_bits, cand_rows=cand_rows,
              block_rows=block_rows, interpret=interpret)

    def plain(arr2d, meta):
        return z_candidates_pallas(arr2d, meta, **kw)

    def chains(arr3d, meta):
        return z_candidates_pallas_chains(arr3d, meta, **kw)

    return common.make_chain_dispatch(plain, chains)


def z_candidates(
    arr: jax.Array,  # (N,) int32 partition array (bright prefix first)
    num: jax.Array,  # () int32 bright count
    key_words: jax.Array,  # (2,) int32 counter-RNG key words (step key)
    q_db: float,
    cand_capacity: int,
    interpret: bool | None = None,
):
    """Fused dark→bright candidate selection. Returns (cand_idx, n_cand).

    ``cand_idx`` is (cand_capacity,) int32 in arr-position order, padded
    with the sentinel ``N``; ``n_cand`` is the true candidate count (it may
    exceed ``cand_capacity``, in which case the caller must raise the
    overflow flag). The tile is :func:`tile_rows` of N. ``interpret=None``
    auto-selects interpret mode off-TPU.
    Under ``jax.vmap`` over the chain axis the dispatch batches into a
    single chain-grid megakernel (see :mod:`repro.kernels.common`).
    """
    if interpret is None:
        interpret = common.default_interpret()
    n = arr.shape[0]
    block_rows = tile_rows(n)
    block = block_rows * 128
    p = common.pad_to(max(n, block), block)
    arr2d = jnp.pad(
        arr.astype(jnp.int32), (0, p - n), constant_values=n
    ).reshape(p // 128, 128)
    meta = jnp.concatenate(
        [jnp.reshape(num.astype(jnp.int32), (1,)), key_words.astype(jnp.int32)]
    )
    # Whole (8, 128) tiles of candidate slots.
    cand_rows = 8 * common.pad_to(max(int(cand_capacity), 1), 1024) // 1024
    call = _pallas_dispatch(
        n, q_threshold_bits(q_db), cand_rows, block_rows, bool(interpret)
    )
    cand, count = call(arr2d, meta)
    return cand.reshape(-1)[:cand_capacity], count[0]
