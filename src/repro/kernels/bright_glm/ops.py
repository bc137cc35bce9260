"""Wrapper for the bright-GLM kernel: padding, layout, clamping, custom VJP.

This is the ``backend="pallas"`` entry point used by
:func:`repro.core.flymc.make_joint_logpost`. It

  * pads θ (and K for softmax) to 128-lane multiples and the index buffer
    to a ``block_rows`` multiple; the feature matrix arrives in its gather
    layout (:func:`repro.kernels.common.gather_layout`), built once per
    dataset by the caller (``GLMData.x_rows``) and the only copy of the
    features this op reads,
  * **clamps** every index into ``[0, N)`` before the ``pallas_call`` —
    padded buffer slots (``bright_buffer`` capacity padding, ``jnp.pad``
    fill, the candidate buffer's out-of-range sentinel ``N``) would
    otherwise reach the in-kernel DMA as reads past the end of ``x``,
    which is undefined; clamped rows are computed and then masked to zero
    by ``n_bright`` exactly like the jnp reference path,
  * pre-gathers the O(C) per-row scalars (t, ξ) so the kernel only fuses
    the O(C·D) feature gather,
  * carries a ``jax.custom_batching.custom_vmap`` rule on the pallas
    dispatch: batching over the chain axis (the driver's multi-chain step)
    lowers to ONE :func:`~repro.kernels.bright_glm.kernel
    .bright_glm_pallas_chains` launch whose grid gains a leading chain
    dimension — instead of jax's default pallas batching, which would
    broadcast the HBM-resident dataset per chain and run each chain's tiny
    workload as a degenerate launch (see :mod:`repro.kernels.common`),
  * defines a ``jax.custom_vjp`` so gradient kernels (MALA/HMC) work
    through the fused forward: the backward pass gathers the C rows from
    the same gather layout, re-evaluates them with the pure-jnp reference
    (same O(C·D) cost class, shared numerics) and scatters row cotangents
    back — Pallas forward speed, reference-exact gradients.
"""

from __future__ import annotations

from contextlib import nullcontext
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.bright_glm.kernel import (
    FAMILIES,
    bright_glm_pallas,
    bright_glm_pallas_chains,
)
from repro.core.bounds import GLMData
from repro.kernels.bright_glm.ref import bright_rows_ref


@lru_cache(maxsize=None)
def _pallas_dispatch(family, nu, sigma, n_classes, block_rows, interpret):
    """The pallas_call dispatch as a ``custom_vmap`` function.

    The plain call is the single-chain kernel; the vmap rule
    (:func:`repro.kernels.common.make_chain_dispatch`) coalesces a
    chain-batched trace into one ``bright_glm_pallas_chains`` launch with
    the dataset shared (never broadcast) across chains. Memoized on the
    static config so repeated traces reuse one custom_vmap object.
    """
    kw = dict(family=family, nu=nu, sigma=sigma, n_classes=n_classes,
              block_rows=block_rows, interpret=interpret)

    if family == "softmax":  # no label operand: the Böhning δ needs none

        def plain(xp, xib, idxp, nb, thetap):
            return bright_glm_pallas(xp, None, xib, idxp, nb, thetap, **kw)

        def chains(xp, xib, idxp, nb, thetap):
            return bright_glm_pallas_chains(xp, None, xib, idxp, nb, thetap,
                                            **kw)

        return common.make_chain_dispatch(plain, chains, n_shared=1)

    def plain(xp, tb, xib, idxp, nb, thetap):
        return bright_glm_pallas(xp, tb, xib, idxp, nb, thetap, **kw)

    def chains(xp, tb, xib, idxp, nb, thetap):
        return bright_glm_pallas_chains(xp, tb, xib, idxp, nb, thetap, **kw)

    return common.make_chain_dispatch(plain, chains, n_shared=1)


def _forward(cfg, x_rows, t, xi, idx, n_bright, theta):
    family, nu, sigma, block_rows, interpret, _ = cfg
    n, _, dp = x_rows.shape
    d = theta.shape[-1]
    c = idx.shape[0]
    cp = common.pad_to(max(c, block_rows), block_rows)

    # Indices ≥ N (buffer padding / candidate sentinels) are undefined for
    # the in-kernel row DMA — clamp, never trust the caller.
    idxp = common.clamp_index(jnp.pad(idx.astype(jnp.int32), (0, cp - c)), n)
    nb = jnp.reshape(n_bright.astype(jnp.int32), (1,))

    if family == "softmax":
        k = theta.shape[0]
        kp = common.pad_to(k, 128)
        per_row = ()
        xib = jnp.pad(
            jnp.take(xi.astype(jnp.float32), idxp, axis=0),
            ((0, 0), (0, kp - k)),
        )  # (cp, Kp)
        thetap = jnp.pad(
            theta.astype(jnp.float32), ((0, kp - k), (0, dp - d))
        )  # (Kp, Dp)
        n_classes = k
    else:
        per_row = (jnp.take(t.astype(jnp.float32), idxp)[:, None],)
        xib = jnp.take(xi.astype(jnp.float32), idxp)[:, None]
        thetap = jnp.pad(theta.astype(jnp.float32), (0, dp - d))[None, :]
        n_classes = 0

    call = _pallas_dispatch(family, nu, sigma, n_classes, block_rows,
                            interpret)
    delta, total = call(x_rows, *per_row, xib, idxp, nb, thetap)
    return delta[:c, 0], total[0]


def _ref_outputs(cfg, x_rows, t, xi, idx, n_bright, theta):
    """(delta, total) via the pure-jnp reference — the VJP's forward."""
    family, nu, sigma = cfg[:3]
    idxc = common.clamp_index(idx, x_rows.shape[0])
    rows = GLMData(
        x=jnp.take(x_rows, idxc, axis=0)[:, 0, : theta.shape[-1]],
        t=jnp.take(t, idxc, axis=0),
        xi=jnp.take(xi, idxc, axis=0),
    )
    mask = jnp.arange(idx.shape[0]) < n_bright
    delta, contrib = bright_rows_ref(rows, mask, theta, family=family, nu=nu,
                                     sigma=sigma)
    return delta, jnp.sum(contrib)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bright_glm_vjp(cfg, x_rows, t, xi, idx, n_bright, theta):
    return _forward(cfg, x_rows, t, xi, idx, n_bright, theta)


def _vjp_fwd(cfg, x_rows, t, xi, idx, n_bright, theta):
    out = _forward(cfg, x_rows, t, xi, idx, n_bright, theta)
    return out, (x_rows, t, xi, idx, n_bright, theta)


def _vjp_bwd(cfg, res, cts):
    x_rows, t, xi, idx, n_bright, theta = res
    grad_scope = cfg[5]
    with jax.named_scope(grad_scope) if grad_scope else nullcontext():
        if jnp.issubdtype(t.dtype, jnp.integer):  # softmax class ids
            fn = lambda x_, xi_, th: _ref_outputs(cfg, x_, t, xi_, idx,
                                                  n_bright, th)
            _, vjp = jax.vjp(fn, x_rows, xi, theta)
            dx, dxi, dth = vjp(cts)
            dt = None
        else:
            fn = lambda x_, t_, xi_, th: _ref_outputs(
                cfg, x_, t_, xi_, idx, n_bright, th
            )
            _, vjp = jax.vjp(fn, x_rows, t, xi, theta)
            dx, dt, dxi, dth = vjp(cts)
    return dx, dt, dxi, None, None, dth


_bright_glm_vjp.defvjp(_vjp_fwd, _vjp_bwd)


def bright_glm(
    x_rows: jax.Array,  # (N, 1, Dp) features in the gather layout
    t: jax.Array,  # (N,) labels / responses / class ids
    xi: jax.Array,  # (N,) bound tightness, or (N, K) tangency logits
    idx: jax.Array,  # (C,) bright row ids (padding slots may be ≥ N)
    n_bright: jax.Array,  # () int — first n_bright slots of idx are valid
    theta: jax.Array,  # (D,), or (K, D) for softmax
    family: str = "logistic",
    nu: float = 4.0,
    sigma: float = 1.0,
    block_rows: int = 128,
    interpret: bool | None = None,
    grad_scope: str | None = None,
):
    """Fused bright-point evaluation. Returns (delta (C,), total scalar).

    ``x_rows`` is the (N, D) feature matrix in the kernel's gather layout,
    :func:`repro.kernels.common.gather_layout` — built once per dataset
    (``bounds.with_gather_layout``), never per call. Forward and backward
    read only it.

    Differentiable (custom VJP); ``grad_scope`` names the backward's ops
    (a ``jax.named_scope``; the forward's are never named by it), so a
    device trace tells the gradient from the forward. ``interpret=None``
    auto-selects interpret mode off-TPU so the same call sites run
    everywhere. Under ``jax.vmap``
    over the chain axis the pallas dispatch batches into a single
    chain-grid megakernel (see :mod:`repro.kernels.common`).
    """
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; expected {FAMILIES}")
    d = theta.shape[-1]
    if x_rows.ndim != 3 or x_rows.shape[1] != 1 or (
        x_rows.shape[2] != common.pad_to(d, 128)
    ):
        raise ValueError(
            f"bright_glm takes the features in their gather layout "
            f"(N, 1, {common.pad_to(d, 128)}) for D = {d}, got "
            f"{tuple(x_rows.shape)}: pass gather_layout(x)"
        )
    if interpret is None:
        interpret = common.default_interpret()
    cfg = (family, float(nu), float(sigma), int(block_rows), bool(interpret),
           grad_scope)
    return _bright_glm_vjp(cfg, x_rows, t, xi, idx, n_bright, theta)
