"""Bright/dark set data structure (paper §3.3, Fig. 3) as JAX arrays.

The paper's structure is two length-N arrays plus a counter:

  arr : a permutation of 0..N-1 with all *bright* indices before dark ones
  tab : inverse permutation — tab[n] is the position of datum n inside arr
  num : number of bright data points (arr[:num] are bright)

``brighten``/``darken`` are the paper's O(1) swap updates, kept for fidelity
and for host-side use. On TPU the per-round update is batched, two ways:

  * :func:`from_z` — full rebuild from a boolean z via one stable cumsum
    compaction. O(N) memory-bound sweep; the ``z_backend="jnp"`` engine's
    path (and the one-time init path).
  * :func:`apply_flips` — the swap updates *vectorized over a round's
    flips*, no length-N cumsum: fixed-shape vector work over the bright
    capacity C and the candidate buffer, and index traffic into the
    N-sized ``arr``/``tab`` bounded by a static ``pair_capacity`` (C on
    the ``z_backend="fused"`` engine's path). On a TPU every scatter or
    gather index entry costs about one memory tile, live or dropped, so
    the update costs O(``pair_capacity``) tiles however few data flip.
    It is exact while the round's brightened count fits
    ``pair_capacity``, which holds whenever the new bright count fits C;
    past that the result is unspecified, and the engine's step flags the
    overflow that makes the driver discard and re-run it.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class BrightState(NamedTuple):
    arr: jax.Array  # (N,) int32 permutation, bright indices first
    tab: jax.Array  # (N,) int32 inverse permutation
    num: jax.Array  # ()   int32 bright count


def init(n: int, bright: bool = False) -> BrightState:
    """All-dark (default) or all-bright initial state."""
    idx = jnp.arange(n, dtype=jnp.int32)
    num = jnp.asarray(n if bright else 0, jnp.int32)
    return BrightState(arr=idx, tab=idx, num=num)


def from_z(z: jax.Array) -> BrightState:
    """Build the partition from a boolean brightness vector (stable order)."""
    z = z.astype(bool)
    n = z.shape[0]
    num = jnp.sum(z).astype(jnp.int32)
    # Stable partition: bright points keep relative order, then dark points.
    pos_b = jnp.cumsum(z) - 1
    pos_d = num + jnp.cumsum(~z) - 1
    tab = jnp.where(z, pos_b, pos_d).astype(jnp.int32)
    arr = jnp.zeros(n, jnp.int32).at[tab].set(jnp.arange(n, dtype=jnp.int32))
    return BrightState(arr=arr, tab=tab, num=num)


def z_of(state: BrightState) -> jax.Array:
    """Boolean brightness vector: z[n] = (position of n) < num."""
    return state.tab < state.num


def brighten(state: BrightState, n: jax.Array) -> BrightState:
    """Paper-faithful O(1) swap update: set z_n = 1 (no-op if already bright)."""
    pos = state.tab[n]
    already = pos < state.num
    boundary = state.num  # first dark slot
    other = state.arr[boundary]

    def do(s: BrightState) -> BrightState:
        arr = s.arr.at[boundary].set(n).at[pos].set(other)
        tab = s.tab.at[n].set(boundary).at[other].set(pos)
        return BrightState(arr=arr, tab=tab, num=s.num + 1)

    return jax.lax.cond(already, lambda s: s, do, state)


def darken(state: BrightState, n: jax.Array) -> BrightState:
    """Paper-faithful O(1) swap update: set z_n = 0 (no-op if already dark)."""
    pos = state.tab[n]
    already = pos >= state.num
    boundary = state.num - 1  # last bright slot
    other = state.arr[boundary]

    def do(s: BrightState) -> BrightState:
        arr = s.arr.at[boundary].set(n).at[pos].set(other)
        tab = s.tab.at[n].set(boundary).at[other].set(pos)
        return BrightState(arr=arr, tab=tab, num=s.num - 1)

    return jax.lax.cond(already, lambda s: s, do, state)


def batch_update(state: BrightState, z_new: jax.Array) -> BrightState:
    """Replace the whole partition given a new boolean z (vectorized round)."""
    del state
    return from_z(z_new)


def _pull(x: jax.Array, step: int, fill) -> jax.Array:
    """``y[p] = x[p + step]``; the last ``step`` entries read ``fill``."""
    return jnp.pad(x[step:], (0, step), constant_values=fill)


def _compact(mask: jax.Array, size: int, *vals: jax.Array):
    """Move the entries of ``vals`` where ``mask`` holds, in order, to the
    front; return their first ``size`` slots and the live count.

    Dense vector work, no scatter and no sort (the shift network of the
    ``z_candidates`` kernel): a log-step prefix count ranks the live
    entries, and each moves toward the front by its distance, bit by bit,
    least significant first. The distances never decrease along the list,
    so two live entries never meet. Slots past the live count hold stale
    values: callers mask them.
    """
    length = mask.shape[0]
    rank = mask.astype(jnp.int32)
    step = 1
    while step < length:  # inclusive prefix count
        rank = rank + jnp.pad(rank[:-step], (step, 0))
        step *= 2
    count = rank[-1]
    dist = jnp.where(mask, jnp.arange(length, dtype=jnp.int32) - rank + 1, -1)
    vals = list(vals)
    step = 1
    while step < length:
        d_in = _pull(dist, step, -1)
        move = (d_in >= 0) & ((d_in & step) != 0)
        stay = (dist >= 0) & ((dist & step) == 0)
        vals = [jnp.where(move, _pull(v, step, 0), v) for v in vals]
        dist = jnp.where(move, d_in, jnp.where(stay, dist, -1))
        step *= 2
    if length < size:
        vals = [jnp.pad(v, (0, size - length)) for v in vals]
    return [v[:size] for v in vals], count


@partial(jax.jit, static_argnames="pair_capacity")
def apply_flips(
    state: BrightState,
    darken: jax.Array,
    brighten_idx: jax.Array,
    brighten_mask: jax.Array,
    pair_capacity: int | None = None,
) -> BrightState:
    """Batched partition update — the paper's Fig.-3 swap updates vectorized
    over one z-round, replacing the O(N) ``from_z`` cumsum rebuild on the
    fused z-engine path.

    ``darken`` is a (C,) bool over *bright-buffer slots*: slot ``s`` is
    position ``s`` of ``arr`` and darkens datum ``arr[s]`` (entries at
    ``s >= num`` are ignored; ``num <= C`` is required). ``brighten_idx``/
    ``brighten_mask`` name currently-dark data to brighten ((S,) int32 ids,
    distinct; masked entries ignored and may be garbage). The two sets must
    be disjoint, which Algorithm 2 guarantees (darken proposals come from
    the bright set, brighten proposals from the dark set).

    The update is a pairwise swap matching: items that must *enter* the new
    bright region ``[0, num')`` (brightened items stranded at positions
    ``>= num'``, plus still-bright items stranded in a shrinking boundary
    window ``[num', num)``) are paired one-to-one with items that must
    *leave* it (darkened items at positions ``< num'``, plus still-dark
    items overtaken by a growing window ``[num, num')``) — the two lists
    provably have equal length P — and each pair swaps positions.

    Cost. Every list is fixed-shape vector work over ``pair_capacity``
    (default ``max(C, S)``) or C slots: the mover lists are compacted by a
    shift network, the bright slots and the growth window are read as
    slices of ``arr``, and the growth window's brightened positions are
    found by a dense compare. The only index traffic into the N-sized
    arrays is ``pair_capacity`` gathers of ``tab`` and one scatter of
    ``2 * pair_capacity`` entries each into ``arr`` and ``tab``. On a TPU
    each index entry costs about one memory tile whether it is live or
    dropped, so the update costs O(``pair_capacity``) tiles, not O(P).

    Capacity. P is at most the number m of brightened ids in a growing
    round and at most the number of darkened slots (<= num <= C) in a
    shrinking one, so the result is exact whenever m <= ``pair_capacity``.
    The default is therefore exact for any input; ``pair_capacity = C`` is
    exact whenever ``num' <= C`` (m <= num'). Otherwise the swaps are
    skipped: ``arr``/``tab`` stay a valid inverse-permutation pair, ``num``
    is ``num'``, and the contents are unspecified — the fused z-engine's
    step flags ``num' > C`` as overflow and the driver discards and re-runs
    the chunk at a grown capacity.

    Matching order is buffer-slot order, which is ``arr``-position order —
    independent of the buffer capacities — so the resulting partition (and
    hence the realized chain) is bitwise capacity-invariant, matching the
    overflow-re-run contract of the drivers.
    """
    n = state.arr.shape[0]
    sb = brighten_idx.shape[0]
    pc = max(darken.shape[0], sb) if pair_capacity is None else pair_capacity
    c = min(darken.shape[0], n)  # slots past N are never bright
    slots = jnp.arange(c, dtype=jnp.int32)
    darken = darken[:c] & (slots < state.num)
    arr_c = state.arr[:c]
    k = jnp.sum(darken).astype(jnp.int32)
    m = jnp.sum(brighten_mask).astype(jnp.int32)
    num2 = state.num - k + m

    # Brightened ids, in buffer order, in pair_capacity slots (exact while
    # m <= pair_capacity), then their positions: the only gather.
    b_idx = jnp.where(brighten_mask, brighten_idx.astype(jnp.int32), n)
    if sb > pc:
        (b_idx,), _ = _compact(brighten_mask, pc, b_idx)
        b_live = jnp.arange(pc, dtype=jnp.int32) < m
    else:
        b_idx = jnp.pad(b_idx, (0, pc - sb), constant_values=n)
        b_live = jnp.pad(brighten_mask, (0, pc - sb))
    pos_b = state.tab.at[b_idx].get(mode="fill", fill_value=n)
    pos_b = jnp.where(b_live, pos_b, -1)

    # Growth window [num, num'), read from arr[lo : lo + w]: the clamp of
    # lo keeps the slice inside arr, and positions before num are masked.
    w = min(pc, n)
    lo = jnp.clip(state.num, 0, n - w)
    win_item = jax.lax.dynamic_slice_in_dim(state.arr, lo, w)
    win_pos = lo + jnp.arange(w, dtype=jnp.int32)
    brightened = jnp.any(win_pos[:, None] == pos_b[None, :], axis=1)

    # --- movers INTO [0, num') ---------------------------------------------
    # (a) brightened items currently parked at positions >= num'
    # (b) shrink window [num', num): still-bright residents must relocate
    #     (empty when the bright set grows).
    in_mask = jnp.concatenate([
        b_live & (pos_b >= num2),
        (slots >= num2) & (slots < state.num) & ~darken,
    ])
    (bi, bp), n_in = _compact(in_mask, pc,
                              jnp.concatenate([b_idx, arr_c]),
                              jnp.concatenate([pos_b, slots]))

    # --- movers OUT of [0, num') -------------------------------------------
    # (a) darkened items currently inside the new bright region
    # (b) growth window [num, num'): still-dark residents must relocate
    #     (empty when the bright set shrinks).
    out_mask = jnp.concatenate([
        darken & (slots < num2),
        (win_pos >= state.num) & (win_pos < num2) & ~brightened,
    ])
    (di, dp), n_out = _compact(out_mask, pc,
                               jnp.concatenate([arr_c, win_item]),
                               jnp.concatenate([slots, win_pos]))

    # --- swap pairwise ------------------------------------------------------
    # Pair i-th with i-th. Unmatched slots (and every slot when a list did
    # not fit) get distinct out-of-range indices, so the scatters drop them
    # and every index is unique.
    pairs = jnp.where((n_in == n_out) & (n_in <= pc), n_in, 0)
    t = jnp.arange(pc, dtype=jnp.int32)
    live = t < pairs
    bi, bp = jnp.where(live, bi, n + t), jnp.where(live, bp, n + t)
    di, dp = jnp.where(live, di, n + pc + t), jnp.where(live, dp, n + pc + t)
    arr = state.arr.at[jnp.concatenate([dp, bp])].set(
        jnp.concatenate([bi, di]), mode="drop", unique_indices=True)
    tab = state.tab.at[jnp.concatenate([bi, di])].set(
        jnp.concatenate([dp, bp]), mode="drop", unique_indices=True)
    return BrightState(arr=arr, tab=tab, num=num2)


def bright_buffer(state: BrightState, capacity: int):
    """Padded gather buffer over the bright set.

    Returns (idx, mask): idx is arr[:capacity] (static shape), mask marks the
    first ``num`` entries valid. Padding rows index arbitrary dark data whose
    contributions are masked to exactly zero by callers.
    """
    idx = jax.lax.dynamic_slice_in_dim(state.arr, 0, capacity)
    mask = jnp.arange(capacity, dtype=jnp.int32) < state.num
    return idx, mask


def dark_buffer(state: BrightState, capacity: int):
    """Padded gather buffer over the *dark* tail (arr[num : num+capacity]).

    Robust to ``capacity > N``: the slice start is clamped to [0, N - cap]
    (``min(num, n - capacity)`` went negative there, which XLA silently
    re-clamps — masking the bug — and a slice wider than N is a trace
    error), and the buffer is padded out to ``capacity`` with masked slots.
    """
    n = state.arr.shape[0]
    cap = min(capacity, n)
    start = jnp.clip(state.num, 0, n - cap)
    idx = jax.lax.dynamic_slice_in_dim(state.arr, start, cap)
    offset = jnp.arange(cap, dtype=jnp.int32) + start
    mask = offset >= state.num
    if capacity > n:
        idx = jnp.pad(idx, (0, capacity - n))
        mask = jnp.pad(mask, (0, capacity - n))
    return idx, mask


def check_invariants(state: BrightState) -> bool:
    """Host-side invariant check (used by tests & property tests)."""
    arr = jax.device_get(state.arr)
    tab = jax.device_get(state.tab)
    num = int(state.num)
    n = arr.shape[0]
    import numpy as np

    ok = bool(np.all(np.sort(arr) == np.arange(n)))
    ok &= bool(np.all(arr[tab] == np.arange(n)))
    ok &= 0 <= num <= n
    return ok
