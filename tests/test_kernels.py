"""Per-kernel validation (brief: sweep shapes/dtypes, assert_allclose vs the
pure-jnp ref.py oracle, interpret=True on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.common import gather_layout

jax.config.update("jax_platform_name", "cpu")

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# bright_glm — the FlyMC hot loop
# ---------------------------------------------------------------------------

_K = 5  # softmax classes for the kernel tests


def _glm_case(family, n, d):
    x = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))
    if family == "logistic":
        t = jnp.asarray(np.where(RNG.random(n) < 0.5, 1.0, -1.0).astype(np.float32))
        xi = jnp.asarray((np.abs(RNG.normal(size=n)) + 0.1).astype(np.float32))
        theta = jnp.asarray(RNG.normal(size=d).astype(np.float32))
    elif family == "student_t":
        t = jnp.asarray((RNG.normal(size=n) * 2).astype(np.float32))
        xi = jnp.asarray((np.abs(RNG.normal(size=n)) + 0.1).astype(np.float32))
        theta = jnp.asarray(RNG.normal(size=d).astype(np.float32))
    else:
        t = jnp.asarray(RNG.integers(0, _K, n).astype(np.int32))
        xi = jnp.asarray((RNG.normal(size=(n, _K)) * 0.5).astype(np.float32))
        theta = jnp.asarray((RNG.normal(size=(_K, d)) * 0.3).astype(np.float32))
    return x, t, xi, theta


@pytest.mark.parametrize("n,d,c,nb", [(64, 51, 16, 12), (128, 57, 32, 32),
                                      (32, 7, 8, 0), (256, 130, 64, 40)])
@pytest.mark.parametrize("family", ["logistic", "student_t", "softmax"])
def test_bright_glm(n, d, c, nb, family):
    from repro.kernels.bright_glm.ops import bright_glm
    from repro.kernels.bright_glm.ref import bright_glm_ref

    x, t, xi, theta = _glm_case(family, n, d)
    idx = jnp.asarray(RNG.choice(n, c, replace=False).astype(np.int32))
    mask = jnp.arange(c) < nb

    delta, total = bright_glm(gather_layout(x), t, xi, idx, jnp.int32(nb), theta, family=family)
    d_ref, c_ref = bright_glm_ref(x, t, xi, idx, mask, theta, family=family)
    np.testing.assert_allclose(delta, d_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(total, c_ref.sum(), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("family", ["logistic", "student_t", "softmax"])
def test_bright_glm_grad_matches_ref(family):
    """MALA/HMC route: ∇_θ of the fused total via the custom VJP."""
    from repro.kernels.bright_glm.ops import bright_glm
    from repro.kernels.bright_glm.ref import bright_glm_ref

    n, d, c, nb = 96, 23, 24, 17
    x, t, xi, theta = _glm_case(family, n, d)
    idx = jnp.asarray(RNG.choice(n, c, replace=False).astype(np.int32))
    mask = jnp.arange(c) < nb

    def f_pallas(th):
        delta, total = bright_glm(gather_layout(x), t, xi, idx, jnp.int32(nb), th,
                                  family=family)
        return total, delta

    def f_ref(th):
        delta, contrib = bright_glm_ref(x, t, xi, idx, mask, th,
                                        family=family)
        return jnp.sum(contrib), delta

    (tot_p, aux_p), g_p = jax.value_and_grad(f_pallas, has_aux=True)(theta)
    (tot_r, aux_r), g_r = jax.value_and_grad(f_ref, has_aux=True)(theta)
    np.testing.assert_allclose(tot_p, tot_r, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(g_p, g_r, rtol=2e-4, atol=1e-5)
    # and under jit, as the samplers call it
    g_jit = jax.jit(jax.grad(lambda th: f_pallas(th)[0]))(theta)
    np.testing.assert_allclose(g_jit, g_r, rtol=2e-4, atol=1e-5)


def test_bright_glm_full_capacity_padded_buffer():
    """Regression: padding slots carrying out-of-range ids (bright_buffer /
    jnp.pad fill, the candidate buffer's N sentinel) must be clamped before
    the in-kernel DMA, at every fill level up to full capacity."""
    from repro.kernels.bright_glm.ops import bright_glm
    from repro.kernels.bright_glm.ref import bright_glm_ref

    n, d, c = 40, 11, 40  # capacity == N: every row bright + ragged padding
    x, t, xi, theta = _glm_case("logistic", n, d)
    perm = RNG.permutation(n).astype(np.int32)
    for nb in (0, 1, 39, 40):
        # invalid tail slots hold the out-of-range sentinel N, as the
        # implicit z-update's candidate buffer does
        idx = jnp.asarray(np.where(np.arange(c) < nb, perm, n))
        mask = jnp.arange(c) < nb
        delta, total = bright_glm(gather_layout(x), t, xi, idx, jnp.int32(nb), theta)
        d_ref, c_ref = bright_glm_ref(x, t, xi, idx, mask, theta)
        assert np.all(np.isfinite(np.asarray(delta)))
        np.testing.assert_allclose(
            np.where(mask, delta, 0.0), np.where(mask, d_ref, 0.0),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(total, c_ref.sum(), rtol=1e-4, atol=1e-5)


def test_bright_glm_ragged_c_not_multiple_of_block_rows():
    from repro.kernels.bright_glm.ops import bright_glm
    from repro.kernels.bright_glm.ref import bright_glm_ref

    n, d, c, nb = 64, 13, 21, 21  # C % block_rows != 0 → internal padding
    x, t, xi, theta = _glm_case("student_t", n, d)
    idx = jnp.asarray(RNG.choice(n, c, replace=False).astype(np.int32))
    mask = jnp.arange(c) < nb
    delta, total = bright_glm(gather_layout(x), t, xi, idx, jnp.int32(nb), theta,
                              family="student_t")
    d_ref, c_ref = bright_glm_ref(x, t, xi, idx, mask, theta,
                                  family="student_t")
    assert delta.shape == (c,)
    np.testing.assert_allclose(delta, d_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(total, c_ref.sum(), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# decode_attention — flash decode over ring cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,h,hk,d,w,t,window",
    [
        (2, 8, 2, 128, 256, 200, None),
        (1, 4, 4, 128, 384, 380, 128),
        (2, 16, 2, 128, 256, 100, None),
        (1, 8, 1, 128, 512, 511, 256),  # MQA + window
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, h, hk, d, w, t, window, dtype):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    q = jnp.asarray(RNG.normal(size=(b, h, d)).astype(np.float32)).astype(dtype)
    k = jnp.asarray(RNG.normal(size=(b, w, hk, d)).astype(np.float32)).astype(dtype)
    v = jnp.asarray(RNG.normal(size=(b, w, hk, d)).astype(np.float32)).astype(dtype)
    pos = jnp.asarray(
        np.where(np.arange(w) < t + 1, np.arange(w), -1).astype(np.int32)
    )
    out, m, l = decode_attention(q, k, v, pos, jnp.int32(t), window=window)
    ref_out, _, ref_l = decode_attention_ref(q, k, v, pos, t, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref_out, rtol=tol, atol=tol)
    np.testing.assert_allclose(l, ref_l, rtol=tol, atol=tol)


def test_decode_attention_ring_wraparound():
    """Ring semantics: only entries with pos in (t-window, t] participate."""
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    b, h, hk, d, w = 1, 2, 1, 128, 128
    t, window = 300, 128
    q = jnp.asarray(RNG.normal(size=(b, h, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, w, hk, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, w, hk, d)).astype(np.float32))
    slots = np.arange(w)
    pos = jnp.asarray(
        (slots + ((t - slots) // w) * w).astype(np.int32)
    )  # wrapped ring positions ≤ t
    out, _, _ = decode_attention(q, k, v, pos, jnp.int32(t), window=window)
    ref_out, _, _ = decode_attention_ref(q, k, v, pos, t, window=window)
    np.testing.assert_allclose(out, ref_out, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# rwkv6_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,h,s,d,chunk", [(2, 3, 64, 16, 16), (1, 2, 128, 64, 64), (2, 1, 96, 32, 32)]
)
def test_rwkv6_scan(b, h, s, d, chunk):
    from repro.kernels.rwkv6_scan.ops import rwkv6_scan
    from repro.kernels.rwkv6_scan.ref import rwkv6_ref

    r = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))
    lw = jnp.asarray(-RNG.uniform(0.01, 0.9, size=(b, h, s, d)).astype(np.float32))
    u = jnp.asarray(RNG.normal(size=(h, d)).astype(np.float32))
    y, st = rwkv6_scan(r, k, v, lw, u, chunk=chunk)
    y_ref, st_ref = rwkv6_ref(r, k, v, lw, u)
    np.testing.assert_allclose(y, y_ref, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(st, st_ref, rtol=3e-4, atol=3e-4)


def test_rwkv6_matches_model_layer_chunking():
    """Kernel agrees with the model's chunked _wkv_chunk implementation."""
    from repro.kernels.rwkv6_scan.ops import rwkv6_scan
    from repro.models.layers import _wkv_chunk

    b, h, s, d, c = 1, 2, 64, 16, 16
    r = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))
    lw = jnp.asarray(-RNG.uniform(0.01, 0.9, size=(b, h, s, d)).astype(np.float32))
    u = jnp.asarray(RNG.normal(size=(h, d)).astype(np.float32))
    y_k, _ = rwkv6_scan(r, k, v, lw, u, chunk=c)
    state = jnp.zeros((b, h, d, d), jnp.float32)
    ys = []
    for i in range(s // c):
        sl = slice(i * c, (i + 1) * c)
        y, state = _wkv_chunk(
            r[:, :, sl], k[:, :, sl], v[:, :, sl], lw[:, :, sl], u, state
        )
        ys.append(y)
    np.testing.assert_allclose(
        y_k, jnp.concatenate(ys, axis=2), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# rglru_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,s,c,chunk", [(2, 64, 96, 16), (1, 128, 256, 64), (3, 96, 130, 32)]
)
def test_rglru_scan(b, s, c, chunk):
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rglru_scan.ref import rglru_ref

    la = jnp.asarray(-RNG.uniform(0.001, 2.0, size=(b, s, c)).astype(np.float32))
    bx = jnp.asarray(RNG.normal(size=(b, s, c)).astype(np.float32))
    y, hf = rglru_scan(la, bx, chunk=chunk)
    y_ref, hf_ref = rglru_ref(la, bx)
    np.testing.assert_allclose(y, y_ref, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(hf, hf_ref, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# fused_ce
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "t,d,v,bt,bv",
    [(16, 64, 512, 8, 128), (24, 128, 1024, 8, 256), (8, 32, 256, 8, 256)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ce(t, d, v, bt, bv, dtype):
    from repro.kernels.fused_ce.ops import fused_ce
    from repro.kernels.fused_ce.ref import fused_ce_ref

    x = jnp.asarray(RNG.normal(size=(t, d)).astype(np.float32)).astype(dtype)
    w = jnp.asarray(
        (RNG.normal(size=(d, v)) / np.sqrt(d)).astype(np.float32)
    ).astype(dtype)
    lab = jnp.asarray(RNG.integers(0, v, t).astype(np.int32))
    nll = fused_ce(x, w, lab, block_t=bt, block_v=bv)
    ref = fused_ce_ref(x, w, lab)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(nll, ref, rtol=tol, atol=tol)
