"""Tests for the bright/dark partition structure (paper §3.3, Fig. 3)."""

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import brightness

jax.config.update("jax_platform_name", "cpu")


def test_init_all_dark():
    s = brightness.init(10)
    assert int(s.num) == 0
    assert not np.any(np.asarray(brightness.z_of(s)))
    assert brightness.check_invariants(s)


def test_brighten_darken_roundtrip():
    s = brightness.init(8)
    s = brightness.brighten(s, jnp.int32(3))
    s = brightness.brighten(s, jnp.int32(5))
    z = np.asarray(brightness.z_of(s))
    assert z[3] and z[5] and z.sum() == 2
    assert brightness.check_invariants(s)
    s = brightness.darken(s, jnp.int32(3))
    z = np.asarray(brightness.z_of(s))
    assert (not z[3]) and z[5] and z.sum() == 1
    assert brightness.check_invariants(s)


def test_brighten_idempotent():
    s = brightness.init(6)
    s = brightness.brighten(s, jnp.int32(2))
    s2 = brightness.brighten(s, jnp.int32(2))
    assert int(s2.num) == 1
    assert brightness.check_invariants(s2)


def test_darken_idempotent_on_dark():
    s = brightness.init(6)
    s2 = brightness.darken(s, jnp.int32(4))
    assert int(s2.num) == 0
    assert brightness.check_invariants(s2)


@settings(deadline=None, max_examples=50)
@given(st.lists(st.booleans(), min_size=1, max_size=64))
def test_from_z_invariants(bits):
    z = jnp.asarray(np.array(bits))
    s = brightness.from_z(z)
    assert brightness.check_invariants(s)
    np.testing.assert_array_equal(np.asarray(brightness.z_of(s)), np.array(bits))
    assert int(s.num) == sum(bits)


@settings(deadline=None, max_examples=30)
@given(
    st.integers(1, 40),
    st.lists(st.tuples(st.integers(0, 39), st.booleans()), max_size=30),
)
def test_sequential_ops_match_batch(n, ops):
    """O(1) paper ops and the vectorized rebuild yield the same z set."""
    ops = [(i % n, b) for i, b in ops]
    s = brightness.init(n)
    z_ref = np.zeros(n, bool)
    for i, b in ops:
        if b:
            s = brightness.brighten(s, jnp.int32(i))
        else:
            s = brightness.darken(s, jnp.int32(i))
        z_ref[i] = b
    assert brightness.check_invariants(s)
    np.testing.assert_array_equal(np.asarray(brightness.z_of(s)), z_ref)
    s_batch = brightness.from_z(jnp.asarray(z_ref))
    np.testing.assert_array_equal(
        np.asarray(brightness.z_of(s_batch)), z_ref
    )


def test_bright_buffer_padding():
    z = jnp.asarray([True, False, True, False, False, True])
    s = brightness.from_z(z)
    idx, mask = brightness.bright_buffer(s, 4)
    assert idx.shape == (4,) and mask.shape == (4,)
    assert set(np.asarray(idx)[np.asarray(mask)]) == {0, 2, 5}
    assert int(mask.sum()) == 3


@settings(deadline=None, max_examples=50)
@given(st.integers(1, 12), st.integers(1, 20), st.integers(0, 12))
def test_dark_buffer_small_n_edge_cases(n, capacity, n_bright):
    """dark_buffer must stay well-defined for capacity > N and any bright
    count (the old min(num, n - capacity) start went negative there)."""
    n_bright = min(n_bright, n)
    z = np.zeros(n, bool)
    z[:n_bright] = True
    s = brightness.from_z(jnp.asarray(z))
    idx, mask = brightness.dark_buffer(s, capacity)
    assert idx.shape == (capacity,) and mask.shape == (capacity,)
    idx, mask = np.asarray(idx), np.asarray(mask)
    assert np.all((idx >= 0) & (idx < n))
    # Every masked-valid slot is genuinely dark…
    z_of = np.asarray(brightness.z_of(s))
    assert not np.any(z_of[idx[mask]])
    # …and the buffer exposes the whole dark tail whenever it fits.
    n_dark = n - n_bright
    if capacity >= n_dark:
        assert set(idx[mask]) == set(np.arange(n)[~z_of])
    else:
        assert mask.sum() == capacity


def test_dark_buffer_capacity_exceeds_n_under_jit():
    s = brightness.from_z(jnp.asarray([True, False, True]))
    idx, mask = jax.jit(
        lambda st_: brightness.dark_buffer(st_, 8)
    )(s)
    assert idx.shape == (8,)
    assert set(np.asarray(idx)[np.asarray(mask)]) == {1}


def test_bright_buffer_under_jit():
    @jax.jit
    def f(z):
        s = brightness.from_z(z)
        return brightness.bright_buffer(s, 4)

    idx, mask = f(jnp.asarray([False, True, False, True, False, False]))
    assert set(np.asarray(idx)[np.asarray(mask)]) == {1, 3}


# ---------------------------------------------------------------------------
# apply_flips — the fused z-engine's O(changed) incremental partition update
# ---------------------------------------------------------------------------


def _random_flip_case(rng, n):
    """(state, darken, brighten_idx, brighten_mask, expected_z) respecting
    the apply_flips contract: capacity >= num, darken over bright-buffer
    slots, brighten ids dark & distinct (masked tail may be garbage)."""
    z = rng.random(n) < rng.random()
    s = brightness.from_z(jnp.asarray(z))
    num = int(s.num)
    cap = int(rng.integers(max(1, num), n + 3))
    sb = int(rng.integers(1, n + 3))
    darken = rng.random(cap) < 0.4
    dark_ids = np.flatnonzero(~np.asarray(brightness.z_of(s)))
    nb = int(min(len(dark_ids), rng.integers(0, sb + 1)))
    chosen = (
        rng.choice(dark_ids, nb, replace=False).astype(np.int32)
        if nb else np.empty(0, np.int32)
    )
    b_idx = np.full(sb, n + 5, np.int32)  # out-of-range padding on purpose
    b_idx[:nb] = chosen
    b_mask = np.arange(sb) < nb
    expected = np.asarray(brightness.z_of(s)).copy()
    slots = np.arange(cap)
    eff = darken & (slots < num)
    expected[np.asarray(s.arr)[slots[eff]]] = False
    expected[chosen] = True
    return s, darken, b_idx, b_mask, expected


@settings(deadline=None, max_examples=60)
@given(st.integers(0, 10_000), st.integers(4, 48))
def test_apply_flips_matches_from_z_set(seed, n):
    """apply_flips realizes exactly the flipped z (as a set) while keeping
    the permutation/inverse/num invariants — the from_z contract without
    the O(N) rebuild."""
    rng = np.random.default_rng(seed)
    s, darken, b_idx, b_mask, expected = _random_flip_case(rng, n)
    out = brightness.apply_flips(
        s, jnp.asarray(darken), jnp.asarray(b_idx), jnp.asarray(b_mask)
    )
    assert brightness.check_invariants(out)
    np.testing.assert_array_equal(np.asarray(brightness.z_of(out)), expected)
    assert int(out.num) == int(expected.sum())


def test_apply_flips_arr_is_capacity_invariant():
    """The realized partition ARRAY (not just the z set) must not depend on
    the darken/brighten buffer sizes: the fused chain's θ-update sums in
    arr order, so capacity-doubling re-runs stay bitwise exact only if
    apply_flips is order-stable across capacities."""
    rng = np.random.default_rng(7)
    n = 40
    z = rng.random(n) < 0.3
    s = brightness.from_z(jnp.asarray(z))
    num = int(s.num)
    dark_ids = np.flatnonzero(~np.asarray(brightness.z_of(s)))
    chosen = rng.choice(dark_ids, 4, replace=False).astype(np.int32)
    dk = rng.random(num) < 0.5
    outs = []
    for cap, sb in ((num, 4), (num + 7, 9), (n, n)):
        darken = np.zeros(cap, bool)
        darken[:num] = dk
        b_idx = np.full(sb, n, np.int32)
        b_idx[:4] = chosen
        b_mask = np.arange(sb) < 4
        outs.append(
            brightness.apply_flips(
                s, jnp.asarray(darken), jnp.asarray(b_idx),
                jnp.asarray(b_mask),
            )
        )
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0].arr), np.asarray(o.arr))
        np.testing.assert_array_equal(np.asarray(outs[0].tab), np.asarray(o.tab))
        assert int(outs[0].num) == int(o.num)


def test_apply_flips_noop_round():
    s = brightness.from_z(jnp.asarray([True, False, True, False, False]))
    out = brightness.apply_flips(
        s, jnp.zeros(3, bool), jnp.full(2, 5, jnp.int32), jnp.zeros(2, bool)
    )
    np.testing.assert_array_equal(np.asarray(out.arr), np.asarray(s.arr))
    np.testing.assert_array_equal(np.asarray(out.tab), np.asarray(s.tab))
    assert int(out.num) == int(s.num)


# ---------------------------------------------------------------------------
# apply_flips at pair_capacity = C against the (C + S)-slot update it replaced
# ---------------------------------------------------------------------------


@jax.jit
def _apply_flips_reference(state, darken, brighten_idx, brighten_mask):
    """The partition update as it was before ``pair_capacity``: every mover
    list spans C + S slots. Kept verbatim as the bitwise reference."""
    n = state.arr.shape[0]
    sd = darken.shape[0]
    sb = brighten_idx.shape[0]
    slots = jnp.arange(sd, dtype=jnp.int32)
    darken = darken & (slots < state.num)
    k = jnp.sum(darken).astype(jnp.int32)
    m = jnp.sum(brighten_mask).astype(jnp.int32)
    num2 = state.num - k + m

    b_idx = jnp.clip(brighten_idx.astype(jnp.int32), 0, n - 1)
    pos_b = jnp.take(state.tab, b_idx)

    ma_mask = brighten_mask & (pos_b >= num2)
    w = num2 + slots
    w_in = w < state.num
    w_cl = jnp.clip(w, 0, sd - 1)
    wb_mask = w_in & ~jnp.take(darken, w_cl)
    wb_item = jnp.take(state.arr, jnp.clip(w, 0, n - 1))
    in_item = jnp.concatenate([jnp.where(ma_mask, b_idx, n),
                               jnp.where(wb_mask, wb_item, n)])
    in_pos = jnp.concatenate([jnp.where(ma_mask, pos_b, n),
                              jnp.where(wb_mask, w, n)])
    in_mask = jnp.concatenate([ma_mask, wb_mask])

    da_mask = darken & (slots < num2)
    da_item = jnp.take(state.arr, jnp.minimum(slots, n - 1))
    v = state.num + jnp.arange(sb, dtype=jnp.int32)
    v_in = v < num2
    v_brightened = (
        jnp.zeros(sb, bool)
        .at[jnp.where(brighten_mask, pos_b - state.num, sb)]
        .set(True, mode="drop")
    )
    vd_mask = v_in & ~v_brightened
    vd_item = jnp.take(state.arr, jnp.clip(v, 0, n - 1))
    out_item = jnp.concatenate([jnp.where(da_mask, da_item, n),
                                jnp.where(vd_mask, vd_item, n)])
    out_pos = jnp.concatenate([jnp.where(da_mask, slots, n),
                               jnp.where(vd_mask, v, n)])
    out_mask = jnp.concatenate([da_mask, vd_mask])

    def compact(item, pos, mask):
        size = item.shape[0]
        dest = jnp.where(mask, jnp.cumsum(mask) - 1, size)
        pad = jnp.full(size, n, jnp.int32)
        return (pad.at[dest].set(item, mode="drop"),
                pad.at[dest].set(pos, mode="drop"))

    bi, bp = compact(in_item, in_pos, in_mask)
    di, dp = compact(out_item, out_pos, out_mask)
    arr = state.arr.at[dp].set(bi, mode="drop").at[bp].set(di, mode="drop")
    tab = state.tab.at[bi].set(dp, mode="drop").at[di].set(bp, mode="drop")
    return brightness.BrightState(arr=arr, tab=tab, num=num2)


def _bounded_flip_case(rng, n, grow, cap_over_n, s_factor, sorted_ids):
    """A z-round whose new bright count fits the bright capacity C.

    ``grow`` picks a growing (m > k) or shrinking (m < k) round;
    ``cap_over_n`` lets C exceed N; ``s_factor`` sizes the brighten buffer
    S against C; ``sorted_ids`` lists the brightened ids in ``arr``-position
    order, as the candidate kernel emits them, else in random order. Masked
    brighten slots hold garbage, in and out of range."""
    z = rng.random(n) < rng.uniform(0.1, 0.7)
    s = brightness.from_z(jnp.asarray(z))
    arr = np.asarray(s.arr)
    num = int(s.num)
    n_dark = n - num
    cap = n + int(rng.integers(1, 6)) if cap_over_n else int(
        rng.integers(max(1, num), n + 1))
    if grow:  # k < m and num - k + m <= cap
        k = int(rng.integers(0, num + 1))
        hi = min(n_dark, cap - num + k)
        if hi <= k:
            k, hi = 0, min(n_dark, cap - num)
        m = int(rng.integers(min(k + 1, hi), hi + 1))
    else:  # m < k
        k = int(rng.integers(1, num + 1)) if num else 0
        m = int(rng.integers(0, min(k - 1, n_dark) + 1)) if num else 0
    sb = max(m, int(s_factor * cap) + int(rng.integers(0, 4)), 1)
    darken = np.zeros(cap, bool)
    darken[rng.choice(num, k, replace=False)] = True
    darken[num:] = rng.random(cap - num) < 0.5  # ignored: slots >= num
    chosen = rng.choice(arr[num:], m, replace=False).astype(np.int32)
    if sorted_ids:
        chosen = arr[num:][np.isin(arr[num:], chosen)].astype(np.int32)
    slots = rng.choice(sb, m, replace=False)
    slots.sort()
    b_mask = np.zeros(sb, bool)
    b_mask[slots] = True
    b_idx = rng.integers(-3, 2 * n + 5, sb).astype(np.int32)  # garbage
    b_idx[slots] = chosen
    assert num - k + m <= cap
    return s, darken, b_idx, b_mask


@settings(deadline=None, max_examples=40)
@given(st.integers(0, 10_000), st.integers(3, 48), st.booleans(),
       st.booleans(), st.integers(0, 3), st.booleans())
def test_apply_flips_bright_capacity_matches_reference(
    seed, n, grow, cap_over_n, s_size, sorted_ids
):
    """With pair_capacity = C, every round whose new bright count fits C
    gives the reference's arr, tab and num bit for bit: growing and
    shrinking rounds, C > N, S far above C, garbage in masked slots."""
    rng = np.random.default_rng(seed)
    s_factor = (0.0, 0.5, 1.0, 6.0)[s_size]
    s, darken, b_idx, b_mask = _bounded_flip_case(
        rng, n, grow, cap_over_n, s_factor, sorted_ids)
    args = (s, jnp.asarray(darken), jnp.asarray(b_idx), jnp.asarray(b_mask))
    ref = _apply_flips_reference(*args)
    out = brightness.apply_flips(*args, pair_capacity=darken.shape[0])
    np.testing.assert_array_equal(np.asarray(out.arr), np.asarray(ref.arr))
    np.testing.assert_array_equal(np.asarray(out.tab), np.asarray(ref.tab))
    assert int(out.num) == int(ref.num)
    assert brightness.check_invariants(out)


def test_apply_flips_over_capacity_returns_a_permutation():
    """num' > C: the result's contents are unspecified, but the call
    returns, num is num', and arr/tab stay an inverse-permutation pair."""
    n, cap = 40, 6
    z = np.zeros(n, bool)
    z[[3, 9, 17]] = True
    s = brightness.from_z(jnp.asarray(z))
    dark = np.flatnonzero(~z)
    for m in (cap, cap + 3, len(dark)):  # num' = 1 + m > C; m = C fits
        b_idx = np.full(len(dark) + 4, n, np.int32)
        b_idx[:m] = dark[:m]
        darken = np.zeros(cap, bool)
        darken[:2] = True
        out = brightness.apply_flips(
            s, jnp.asarray(darken), jnp.asarray(b_idx),
            jnp.asarray(np.arange(len(b_idx)) < m), pair_capacity=cap,
        )
        assert int(out.num) == 1 + m > cap
        assert brightness.check_invariants(out)
