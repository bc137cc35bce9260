"""Utilities shared by every Pallas kernel package.

Extracted from ``kernels/bright_glm/ops.py`` once ``kernels/z_update``
started importing them cross-package: layout helpers (``pad_to``), the
off-TPU interpret-mode policy (``default_interpret``), index clamping for
padded gather buffers (``clamp_index``), and the chain-batching dispatch
switch shared by both kernel wrappers.

Chain batching
--------------
Both kernel entry points (:func:`repro.kernels.bright_glm.ops.bright_glm`
and :func:`repro.kernels.z_update.ops.z_candidates`) carry a
``jax.custom_batching.custom_vmap`` rule: when the driver batches a step
over the chain axis, each kernel lowers to ONE ``pallas_call`` whose grid
gains a leading ``num_chains`` dimension (per-chain scalars ride along as
2-D scalar-prefetch operands), instead of jax's default pallas batching —
which broadcasts every unbatched operand (a per-chain copy of the dataset
for the ANY-space feature matrix) and runs each chain's tiny workload as
its own degenerate launch.

``chain_batching(False)`` disables the rule and restores the default
vmap lowering — that is the baseline ``benchmarks/chain_scaling.py``
measures against, and what the batched-vs-vmap parity tests pin the
megakernels to, bitwise. The flag is read at trace time; callers that
toggle it must not reuse traces across values (the driver's jit cache
keys on it).

The sequential-grid-accumulator contract
----------------------------------------
Every kernel in this repo may use the *revisited-block accumulator*
idiom: an output BlockSpec whose index map ignores one grid axis, so all
steps along that axis address the same block and the kernel accumulates
into it (``pl.when(i == 0)`` init, ``ref[...] += part`` after —
bright's running total, z-update's candidate buffer and count, fused-ce's
``lse``/``tgt``, flash-decode's ``o/m/l``, the scan kernels' final
states). The idiom is exact only because TPU grids execute
**sequentially** (row-major, last axis fastest); under
``dimension_semantics=('parallel', ...)`` — or any future lowering with
parallel grid axes — the same BlockSpec is a write-write race.

Kernels therefore must (a) never mark a revisited output axis
``parallel``, and (b) *declare* each accumulator output when registering
with the analysis sweep (``repro.analysis.kernels.GridRaceRule``,
``accumulators={output_index: (revisited_axes...)}``) — the
``kernel-race`` rule flags undeclared accumulator-style writes and any
parallel-axis revisit, so the contract is checked on every commit rather
than remembered. Scratch initialization follows the same sequencing
assumption: a ``pl.when(first_step)`` init is ordered before every later
read only because the grid is sequential.
"""

from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp


def pad_to(d: int, mult: int) -> int:
    """Smallest multiple of ``mult`` that is >= ``d``."""
    return ((d + mult - 1) // mult) * mult


def gather_layout(x: jax.Array) -> jax.Array:
    """(N, D) features → (N, 1, Dp) f32, Dp = D rounded up to 128 lanes.

    The layout the bright-GLM kernel gathers rows from. Mosaic DMAs whole
    (sublane, lane) tiles only: a plain (N, D) array packs 8 rows per
    (8, 128) tile, so one row cannot be copied out of it, and a lane
    extent that is not a multiple of 128 is refused. Here every row owns
    a (1, Dp) tile, zero past column D. TPU HBM lays an (N, D) f32 array
    out in (8, 128) tiles too, so this copy takes the bytes per row that
    the device already spends on ``x``.
    """
    d = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad_to(d, 128) - d)))
    return xp[:, None, :]


def default_interpret() -> bool:
    """Interpret-mode fallback: compile for real only on TPU backends."""
    return jax.default_backend() != "tpu"


def clamp_index(idx: jax.Array, n: int) -> jax.Array:
    """Clamp gather indices into ``[0, n)`` as int32.

    Padded buffer slots (capacity padding, candidate sentinels ``n``) are
    undefined for an in-kernel row DMA — clamp before every pallas_call,
    never trust the caller; clamped rows are computed and then masked.
    """
    return jnp.clip(idx.astype(jnp.int32), 0, n - 1)


def make_chain_dispatch(plain, chains_fn, n_shared: int = 0):
    """Wrap a single-chain pallas dispatch in the chain-batching rule.

    ``plain(*args)`` is the single-chain kernel call; ``chains_fn`` its
    chain-batched counterpart taking the same operands with a leading
    chain axis on every arg past the first ``n_shared`` (which stay
    UN-broadcast — the HBM-resident operands every chain shares). Returns
    a ``jax.custom_batching.custom_vmap`` function: unbatched calls run
    ``plain``; batching over the chain axis dispatches ONE ``chains_fn``
    launch (unbatched per-chain operands broadcast, shared ones passed
    through). Falls back to jax's default pallas batching — per-chain
    launches with every unbatched operand broadcast — when a shared
    operand is itself batched (per-chain datasets) or when
    :func:`chain_batching_enabled` is off (the benchmarked baseline).

    Shared by ``bright_glm/ops`` and ``z_update/ops`` so the dispatch
    subtleties (flag semantics, broadcast rule, fallback lowering) are
    encoded exactly once.
    """
    call = jax.custom_batching.custom_vmap(plain)

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        flat_batched = jax.tree.leaves(in_batched)
        if any(flat_batched[:n_shared]) or not chain_batching_enabled():
            axes = tuple(0 if b else None for b in flat_batched)
            out = jax.vmap(plain, in_axes=axes)(*args)
        else:
            bcast = lambda a, b: a if b else jnp.broadcast_to(
                a[None], (axis_size,) + a.shape
            )
            out = chains_fn(
                *args[:n_shared],
                *(bcast(a, b) for a, b in zip(args[n_shared:],
                                              flat_batched[n_shared:])),
            )
        return out, jax.tree.map(lambda _: True, out)

    return call


_CHAIN_BATCHING = True


def chain_batching_enabled() -> bool:
    """Whether vmap over chains dispatches the chain-batched megakernels."""
    return _CHAIN_BATCHING


@contextmanager
def chain_batching(enabled: bool):
    """Temporarily enable/disable megakernel dispatch under vmap (trace-time
    flag; used by the chain-scaling benchmark and the parity tests)."""
    global _CHAIN_BATCHING
    prev = _CHAIN_BATCHING
    _CHAIN_BATCHING = bool(enabled)
    try:
        yield
    finally:
        _CHAIN_BATCHING = prev
