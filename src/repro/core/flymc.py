"""Firefly Monte Carlo (paper §2–§3): exact MCMC with subsets of data.

The augmented target over (θ, z) is

    p(θ, z | x) ∝ p̃(θ) · ∏_{n: z_n=1} L̃_n(θ)
    p̃(θ)   = p(θ) · ∏_n B_n(θ)            (pseudo-prior; collapsed, O(D²))
    L̃_n(θ) = (L_n(θ) - B_n(θ)) / B_n(θ)    (pseudo-likelihood of bright n)

and marginalizing z recovers the exact posterior (paper Eq. 2). A FlyMC
iteration alternates a θ-kernel on the conditional (any operator from
``core.samplers``) with a z-kernel (implicit MH resampling, Algorithm 2, or
explicit Gibbs resampling, Algorithm 1 lines 3–6).

TPU/XLA adaptation (DESIGN.md §3): the dynamic bright set becomes a
capacity-``C`` padded gather over the Fig.-3 partition array, so a θ-update
costs O(C·D) likelihood work instead of O(N·D). Capacity overflow is detected
*before* a step is committed and the step is deterministically re-run at a
doubled capacity from the same RNG key, so truncation can never bias the
chain. A full-length ``delta_full`` cache holds δ_n = log L_n - log B_n at
the current θ for every point whose likelihood has been evaluated there,
which is exactly the set the z-kernel is allowed to touch for free
(Algorithm 2's "cached from θ update").

Likelihood-query accounting follows Table 1: every per-datum L_n evaluation
is counted; bound evaluations ride along for free (paper §3.1) and the
collapsed bound product is O(D²), independent of N.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bounds as bounds_lib
from repro.core import brightness, samplers
from repro.core.bounds import CollapsedStats, GLMData

# Numerics are single-sourced in repro.core.numerics (shared with the fused
# Pallas kernel); log_expm1 stays re-exported here for backward compat.
from repro.core.numerics import (  # noqa: F401
    _DELTA_FLOOR,
    fixed_order_sum,
    log_expm1,
)


def _tree_gather(data: GLMData, idx: jax.Array) -> GLMData:
    take = lambda a: jnp.take(a, idx, axis=0)
    return GLMData(x=take(data.x), t=take(data.t), xi=take(data.xi))


def _gather_layout_of(data: GLMData) -> jax.Array:
    """``data.x_rows``, which ``backend="pallas"`` needs built beforehand."""
    if data.x_rows is None:
        raise ValueError(
            'backend="pallas" reads the features in their gather layout: '
            "pass the data through bounds.with_gather_layout first "
            "(api.firefly and dist_algorithm do)"
        )
    return data.x_rows


# ---------------------------------------------------------------------------
# Spec / state
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FlyMCSpec:
    """Static configuration of a FlyMC chain (hashable; jit-static)."""

    bound: Any  # bound object from core.bounds
    log_prior: Callable[[jax.Array], jax.Array]
    kernel: str = "rwmh"  # θ-operator: rwmh | mala | slice | hmc
    capacity: int = 1024  # bright-buffer capacity C
    cand_capacity: int = 1024  # dark→bright candidate buffer capacity
    q_db: float = 0.01  # dark→bright proposal probability (Alg. 2)
    mode: str = "implicit"  # z-kernel: implicit (Alg. 2) | explicit (Alg. 1)
    resample_fraction: float = 0.1  # explicit mode: fraction of data per round
    kernel_kwargs: tuple = ()  # extra static kwargs for the θ-kernel
    axis_names: tuple = ()  # mesh axes carrying data shards (psum)
    adapt_target: float | None = None  # accept-rate target during warmup
    backend: str = "jnp"  # θ-update likelihood engine: jnp | pallas
    z_backend: str = "jnp"  # z-update engine: jnp | fused (implicit mode)
    num_warmup: int = 1000  # step-size adaptation window (iterations)

    def needs_grad(self) -> bool:
        return samplers.get_kernel(self.kernel).needs_grad


class FlyMCState(NamedTuple):
    sampler: samplers.SamplerState  # θ, joint lp, grad, δ-buffer aux
    bright: brightness.BrightState
    delta_full: jax.Array  # (N,) δ at current θ; valid for bright & just-evaluated
    log_step: jax.Array  # log step size (adapted during warmup)
    rng: jax.Array
    iteration: jax.Array  # int32


class StepStats(NamedTuple):
    n_bright: jax.Array  # bright count after the step
    lik_queries: jax.Array  # per-datum likelihood evaluations this step
    accept_prob: jax.Array
    overflow: jax.Array  # bool — step must be re-run at larger capacity
    joint_lp: jax.Array


# ---------------------------------------------------------------------------
# Joint log-posterior over the padded bright buffer
# ---------------------------------------------------------------------------


def make_joint_logpost(
    spec: FlyMCSpec,
    data: GLMData,
    stats: CollapsedStats,
    bright_idx: jax.Array,
    bright_mask: jax.Array,
    grad_scope: str | None = None,
) -> samplers.LogDensityFn:
    """f(θ) -> (joint log posterior, δ on the bright buffer).

    Evaluates only the ``C`` gathered rows (the paper's bright minibatch) plus
    the O(D²) collapsed bound product. Under shard_map the bright sum is
    psum'd; prior + collapsed terms are replicated and added once.

    ``bright_mask`` must be a PREFIX mask (first ``k`` slots valid, the rest
    padding) as produced by :func:`repro.core.brightness.bright_buffer`: the
    pallas backend hands the kernel only the valid-slot *count*, so a
    non-prefix mask would be honored by the jnp path but silently
    misinterpreted by the fused one.

    ``spec.backend`` selects the likelihood engine. ``"jnp"`` materializes
    the gathered rows and evaluates the bound in plain XLA; ``"pallas"``
    routes through the fused :func:`repro.kernels.bright_glm.ops.bright_glm`
    kernel (gather + δ + masked log L̃ reduction in one pass, gradient via
    its custom VJP) for bounds exposing the
    :class:`~repro.core.bounds.FusedBound` hook — with interpret-mode
    fallback off-TPU so both paths run everywhere. ``grad_scope`` names
    that VJP's backward ops (see ``bright_glm``).
    """

    if spec.backend == "pallas":
        from repro.core.bounds import fused_family_of

        fam = fused_family_of(spec.bound)
        if fam is None:
            raise ValueError(
                f"backend='pallas' needs a FusedBound, but "
                f"{type(spec.bound).__name__} has no usable fused_family "
                "hook (missing, or log_lik/log_bound overridden without "
                "re-declaring it)"
            )
        kernel_kwargs = spec.bound.fused_kernel_kwargs()
        # Prefix-mask contract (see docstring): count == first-k-valid.
        n_bright = jnp.sum(bright_mask).astype(jnp.int32)

        def f_pallas(theta: jax.Array):
            from repro.kernels.bright_glm.ops import bright_glm

            delta, s = bright_glm(
                _gather_layout_of(data), data.t, data.xi, bright_idx,
                n_bright, theta, family=fam, grad_scope=grad_scope,
                **kernel_kwargs,
            )
            for ax in spec.axis_names:
                s = jax.lax.psum(s, ax)
            lp = spec.log_prior(theta) + spec.bound.collapsed(theta, stats) + s
            return lp, delta

        return f_pallas
    if spec.backend != "jnp":
        raise ValueError(
            f"unknown backend {spec.backend!r}; expected 'jnp' or 'pallas'"
        )

    rows = _tree_gather(data, bright_idx)

    def f(theta: jax.Array):
        delta = bounds_lib.delta(spec.bound, theta, rows)
        s = fixed_order_sum(jnp.where(bright_mask, log_expm1(delta), 0.0))
        for ax in spec.axis_names:
            s = jax.lax.psum(s, ax)
        lp = spec.log_prior(theta) + spec.bound.collapsed(theta, stats) + s
        return lp, delta

    return f


def _refresh_sampler(
    spec: FlyMCSpec,
    data: GLMData,
    stats: CollapsedStats,
    theta: jax.Array,
    bright: brightness.BrightState,
    delta_full: jax.Array,
) -> tuple[samplers.SamplerState, jax.Array]:
    """Rebuild SamplerState after a z-move *without* new likelihood queries
    (gradient kernels excepted — they re-evaluate and the cost is counted).

    Returns (state, extra_queries).
    """
    idx, mask = brightness.bright_buffer(bright, spec.capacity)
    delta = jnp.take(delta_full, idx)
    if spec.needs_grad():
        f = make_joint_logpost(spec, data, stats, idx, mask,
                               grad_scope="flymc.refresh.grad")
        (lp, aux), grad = jax.value_and_grad(f, has_aux=True)(theta)
        return samplers.SamplerState(theta, lp, grad, aux), bright.num
    # lp from cached δ — zero new likelihood queries.
    s = fixed_order_sum(jnp.where(mask, log_expm1(delta), 0.0))
    for ax in spec.axis_names:
        s = jax.lax.psum(s, ax)
    lp = spec.log_prior(theta) + spec.bound.collapsed(theta, stats) + s
    zeros_grad = jnp.zeros_like(theta)
    return samplers.SamplerState(theta, lp, zeros_grad, delta), jnp.int32(0)


# ---------------------------------------------------------------------------
# z-kernels
# ---------------------------------------------------------------------------


def _implicit_z_update(
    spec: FlyMCSpec,
    data: GLMData,
    key: jax.Array,
    theta: jax.Array,
    bright: brightness.BrightState,
    delta_full: jax.Array,
    delta_bright: jax.Array,
):
    """Algorithm 2, vectorized. Returns (z_new, delta_full, queries, overflow).

    Per-datum MH moves are conditionally independent given θ, so the parallel
    sweep simulates exactly the paper's kernel. q_{b→d}=1: every bright point
    proposes to darken, using the δ cached from the θ-update; dark points
    propose to brighten with prob q_{d→b} (geometric thinning) and only those
    *candidates* pay a likelihood evaluation.

    All uniforms are drawn per *datum* (length-N vectors, gathered by index),
    never per buffer slot: jax's counter-based PRNG is not prefix-stable
    across shapes, so capacity-shaped draws would make the realized chain
    depend on the buffer size. Per-datum draws keep the trajectory bitwise
    identical across capacities, which is what lets the driver re-run an
    overflowed chunk at doubled capacity without perturbing the chain.
    (:func:`_fused_z_update` keeps the same per-datum keying — uniforms are
    a pure function of ``(step_key, draw, datum_index)`` — while never
    materializing the length-N arrays this engine pays for.)
    """
    n = data.x.shape[0]
    k_bd, k_cand, k_db = jax.random.split(key, 3)
    z = brightness.z_of(bright)
    log_q = jnp.log(jnp.asarray(spec.q_db, delta_full.dtype))

    # --- bright → dark (free: reuses cached δ) -----------------------------
    idx_b, mask_b = brightness.bright_buffer(bright, spec.capacity)
    u1 = jnp.take(jax.random.uniform(k_bd, (n,), delta_full.dtype), idx_b)
    # accept darkening iff u·L̃ < q_db  ⇔  log u + log L̃ < log q_db
    darken = mask_b & (jnp.log(u1) + log_expm1(delta_bright) < log_q)
    z = z.at[idx_b].set(jnp.where(darken, False, z[idx_b]))

    # --- dark → bright (candidates pay a likelihood query each) ------------
    u2 = jax.random.uniform(k_cand, (n,), delta_full.dtype)
    was_dark = ~brightness.z_of(bright)
    cand = was_dark & (u2 < spec.q_db)
    n_cand = jnp.sum(cand).astype(jnp.int32)
    overflow_c = n_cand > spec.cand_capacity
    pos = jnp.cumsum(cand) - 1
    scatter_to = jnp.where(cand, pos, spec.cand_capacity)  # OOB rows dropped
    # Padding slots index n (out of bounds): their gathers clamp harmlessly
    # and their scatters are dropped, so they can never collide with slot 0.
    cand_idx = (
        jnp.full(spec.cand_capacity, n, jnp.int32)
        .at[scatter_to]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    )
    mask_c = jnp.arange(spec.cand_capacity) < n_cand

    rows = _tree_gather(data, cand_idx)
    delta_c = bounds_lib.delta(spec.bound, theta, rows)
    u3 = jnp.take(
        jax.random.uniform(k_db, (n,), delta_full.dtype), cand_idx, mode="clip"
    )
    # accept brightening iff u·q_db < L̃  ⇔  log u + log q_db < log L̃
    brighten = mask_c & (jnp.log(u3) + log_q < log_expm1(delta_c))
    z = z.at[cand_idx].set(jnp.where(brighten, True, z[cand_idx]), mode="drop")
    delta_full = delta_full.at[cand_idx].set(
        jnp.where(mask_c, delta_c, delta_full[cand_idx]), mode="drop"
    )
    return z, delta_full, n_cand, overflow_c


def _candidate_delta(
    spec: FlyMCSpec,
    data: GLMData,
    theta: jax.Array,
    cand_idx: jax.Array,
    n_cand: jax.Array,
) -> jax.Array:
    """δ = log L - log B on the compacted candidate buffer.

    Dispatches on ``spec.backend`` exactly like the θ-update: with
    ``backend="pallas"`` the candidate rows go through the same fused
    :func:`repro.kernels.bright_glm.ops.bright_glm` kernel (FusedBound
    family), so the pallas backend covers the *whole* step's likelihood
    work; otherwise the jnp gather path. Padded slots (``idx >= N``) clamp
    harmlessly — callers mask them.
    """
    if spec.backend == "pallas":
        from repro.core.bounds import fused_family_of
        from repro.kernels.bright_glm.ops import bright_glm

        fam = fused_family_of(spec.bound)
        delta, _ = bright_glm(
            _gather_layout_of(data), data.t, data.xi, cand_idx, n_cand, theta,
            family=fam, **spec.bound.fused_kernel_kwargs(),
        )
        return delta
    rows = _tree_gather(data, cand_idx)
    return bounds_lib.delta(spec.bound, theta, rows)


def _fused_z_update(
    spec: FlyMCSpec,
    data: GLMData,
    key: jax.Array,
    theta: jax.Array,
    bright: brightness.BrightState,
    delta_full: jax.Array,
    delta_bright: jax.Array,
):
    """Algorithm 2 via the fused z-engine (``spec.z_backend = "fused"``).

    Same per-datum MH law as :func:`_implicit_z_update`, with every O(N)
    non-likelihood intermediate eliminated:

      * uniforms come from the counter-based RNG
        (:func:`repro.core.numerics.counter_uniform`, keyed on
        ``(step_key, draw, datum_index)``) — evaluated on the O(C) bright
        buffer and O(cand) candidate buffer here, and on streamed tiles
        inside the candidate kernel, never as (N,) arrays;
      * dark→bright candidate selection + compaction is one streamed pass
        (:func:`repro.kernels.z_update.ops.z_candidates`);
      * candidate δ routes through :func:`_candidate_delta` (the fused
        bright-GLM kernel under ``backend="pallas"``);
      * the partition is maintained incrementally by
        :func:`repro.core.brightness.apply_flips` — O(changed) swaps, no
        full-N cumsum rebuild.

    Keying on datum indices keeps the trajectory bitwise invariant to
    capacity and chunk size (the same contract as the jnp engine), but the
    realized stream differs from the jnp engine's ``jax.random.uniform``
    draws: the two engines produce *law-equivalent*, not bitwise-equal,
    chains.

    Returns (bright_new, delta_full, queries, overflow).
    """
    from repro.core.numerics import (
        DRAW_BRIGHT,
        DRAW_DARKEN,
        counter_uniform,
        key_words_of,
    )
    from repro.kernels.z_update.ops import z_candidates

    n = data.x.shape[0]
    kw = key_words_of(key)
    log_q = jnp.log(jnp.asarray(spec.q_db, delta_full.dtype))

    # --- bright → dark (free: cached δ + O(C) counter uniforms) ------------
    with jax.named_scope("flymc.z.delta"):
        idx_b, mask_b = brightness.bright_buffer(bright, spec.capacity)
        u1 = counter_uniform(kw, DRAW_DARKEN, idx_b)
        darken = mask_b & (jnp.log(u1) + log_expm1(delta_bright) < log_q)

    # --- dark → bright (streamed selection, then O(cand) work) -------------
    with jax.named_scope("flymc.z.candidates"):
        cand_idx, n_cand = z_candidates(
            bright.arr, bright.num, kw, spec.q_db, spec.cand_capacity
        )
    with jax.named_scope("flymc.z.delta"):
        overflow_c = n_cand > spec.cand_capacity
        mask_c = jnp.arange(spec.cand_capacity, dtype=jnp.int32) < n_cand
        nb = jnp.minimum(n_cand, spec.cand_capacity)
        delta_c = _candidate_delta(spec, data, theta, cand_idx, nb)
        u3 = counter_uniform(kw, DRAW_BRIGHT, jnp.clip(cand_idx, 0, n - 1))
        brighten = mask_c & (jnp.log(u3) + log_q < log_expm1(delta_c))
    with jax.named_scope("flymc.z.flips"):
        delta_full = delta_full.at[cand_idx].set(
            jnp.where(mask_c, delta_c,
                      delta_full[jnp.clip(cand_idx, 0, n - 1)]),
            mode="drop",
        )
        bright_new = brightness.apply_flips(bright, darken, cand_idx,
                                            brighten)
    return bright_new, delta_full, n_cand, overflow_c


def _explicit_z_update(
    spec: FlyMCSpec,
    data: GLMData,
    key: jax.Array,
    theta: jax.Array,
    bright: brightness.BrightState,
    delta_full: jax.Array,
):
    """Algorithm 1 lines 3–6: Gibbs resampling of a random fixed-size subset.

    The subset is drawn WITHOUT replacement (a permutation slice): with
    replacement, a datum appearing twice in ``idx`` makes the
    ``z.at[idx].set`` scatter order-nondeterministic — the realized z (and
    cached δ) for that datum would be whichever duplicate the scatter
    happened to apply last, which XLA does not define.
    """
    n = data.x.shape[0]
    r = max(1, int(round(n * spec.resample_fraction)))
    k_idx, k_z = jax.random.split(key)
    idx = jax.lax.slice_in_dim(
        jax.random.permutation(k_idx, jnp.arange(n, dtype=jnp.int32)), 0, r
    )
    rows = _tree_gather(data, idx)
    delta = bounds_lib.delta(spec.bound, theta, rows)
    # p(z=1) = (L-B)/L = -expm1(-δ)
    p_bright = -jnp.expm1(-jnp.maximum(delta, _DELTA_FLOOR))
    z_idx = jax.random.uniform(k_z, (r,), delta.dtype) < p_bright
    z = brightness.z_of(bright).at[idx].set(z_idx)
    delta_full = delta_full.at[idx].set(delta)
    return z, delta_full, jnp.int32(r), jnp.bool_(False)


# ---------------------------------------------------------------------------
# One FlyMC iteration
# ---------------------------------------------------------------------------


def flymc_step(
    spec: FlyMCSpec,
    data: GLMData,
    stats: CollapsedStats,
    state: FlyMCState,
) -> tuple[FlyMCState, StepStats]:
    """θ-update followed by z-update (paper §2 alternation).

    The phases run under named scopes, which label the compiled ops (HLO
    op-name metadata) so a device trace splits the step's time by phase:
    ``flymc.theta`` (bright buffer, θ-kernel, δ-cache scatter), ``flymc.z``
    (the z-update; the fused engine adds ``flymc.z.candidates``,
    ``flymc.z.delta`` and ``flymc.z.flips`` inside it) and
    ``flymc.refresh`` (sampler refresh and step-size adaptation). A
    gradient θ-kernel on the pallas backend runs the fused likelihood's
    backward under ``flymc.theta.grad`` and ``flymc.refresh.grad``.

    Distributed (spec.axis_names non-empty, inside shard_map): the θ-kernel
    runs replicated with identical keys on every shard (identical proposals
    and accept decisions; likelihood sums are psum'd inside the joint), while
    the z-kernel folds the shard index into its key so per-datum Bernoulli
    decisions are independent across shards.
    """
    key_theta, key_z, key_next = jax.random.split(state.rng, 3)
    for ax in spec.axis_names:
        key_z = jax.random.fold_in(key_z, jax.lax.axis_index(ax))

    # ---- θ | z -------------------------------------------------------------
    with jax.named_scope("flymc.theta"):
        idx, mask = brightness.bright_buffer(state.bright, spec.capacity)
        f = make_joint_logpost(spec, data, stats, idx, mask,
                               grad_scope="flymc.theta.grad")
        kernel = samplers.bind(spec.kernel, f, spec.kernel_kwargs)
        new_sampler, info = kernel(
            key_theta, state.sampler, jnp.exp(state.log_step)
        )
        queries_theta = info.n_evals * state.bright.num
        # δ at (possibly) new θ for the bright buffer, from the kernel's aux
        # cache.
        delta_full = state.delta_full.at[idx].set(
            jnp.where(mask, new_sampler.aux, state.delta_full[idx])
        )

    # ---- z | θ -------------------------------------------------------------
    with jax.named_scope("flymc.z"):
        if spec.mode == "implicit" and spec.z_backend == "fused":
            bright_new, delta_full, queries_z, overflow_c = _fused_z_update(
                spec, data, key_z, new_sampler.theta, state.bright,
                delta_full, new_sampler.aux,
            )
        elif spec.mode == "implicit":
            z_new, delta_full, queries_z, overflow_c = _implicit_z_update(
                spec, data, key_z, new_sampler.theta, state.bright,
                delta_full, new_sampler.aux,
            )
            bright_new = brightness.from_z(z_new)
        elif spec.z_backend == "fused":
            raise ValueError(
                "z_backend='fused' requires mode='implicit' (Algorithm 1's "
                "explicit Gibbs resampling re-evaluates a dense subset, so "
                "there is no sparse candidate stream to fuse)"
            )
        else:
            z_new, delta_full, queries_z, overflow_c = _explicit_z_update(
                spec, data, key_z, new_sampler.theta, state.bright,
                delta_full,
            )
            bright_new = brightness.from_z(z_new)
        overflow = overflow_c | (bright_new.num > spec.capacity)
        if spec.axis_names:
            overflow = jax.lax.pmax(overflow.astype(jnp.int32),
                                    spec.axis_names).astype(bool)

    with jax.named_scope("flymc.refresh"):
        refreshed, extra_q = _refresh_sampler(
            spec, data, stats, new_sampler.theta, bright_new, delta_full
        )
        log_step = state.log_step
        if spec.adapt_target is not None:
            # Adaptation is WARMUP-ONLY: a kernel whose step size keeps
            # moving is not a fixed Markov kernel, so the post-warmup chain
            # would lose detailed balance (diminishing or not). Freeze
            # bitwise after spec.num_warmup iterations.
            adapted = samplers.adapt_step_size(
                log_step, info.accept_prob, spec.adapt_target, state.iteration
            )
            log_step = jnp.where(
                state.iteration < spec.num_warmup, adapted, log_step
            )

    new_state = FlyMCState(
        sampler=refreshed,
        bright=bright_new,
        delta_full=delta_full,
        log_step=log_step,
        rng=key_next,
        iteration=state.iteration + 1,
    )
    n_bright = bright_new.num
    lik_queries = queries_theta + queries_z + extra_q
    if spec.axis_names:
        n_bright = jax.lax.psum(n_bright, spec.axis_names)
        lik_queries = jax.lax.psum(lik_queries, spec.axis_names)
    stats_out = StepStats(
        n_bright=n_bright,
        lik_queries=lik_queries,
        accept_prob=info.accept_prob,
        overflow=overflow,
        joint_lp=refreshed.lp,
    )
    return new_state, stats_out


# ---------------------------------------------------------------------------
# Initialization & host driver (capacity doubling keeps the chain exact)
# ---------------------------------------------------------------------------


def init_chain_state(
    spec: FlyMCSpec,
    data: GLMData,
    stats: CollapsedStats,
    theta0: jax.Array,
    key: jax.Array,
    z0: jax.Array | None = None,
    step_size: float = 0.1,
) -> FlyMCState:
    """Pure chain initialization: no host syncs, no capacity growth.

    If the initial bright set exceeds ``spec.capacity`` the returned state's
    δ buffer is truncated; callers (the repro.api driver, or the legacy
    ``init_chain`` wrapper) detect ``state.bright.num > capacity`` and
    rebuild at a grown capacity from the same key, which is deterministic.
    """
    n = data.x.shape[0]
    k_z, k_chain = jax.random.split(key)
    for ax in spec.axis_names:
        k_z = jax.random.fold_in(k_z, jax.lax.axis_index(ax))
    if z0 is None:
        z0 = jax.random.bernoulli(k_z, min(2.0 * spec.q_db, 1.0), (n,))
    bright = brightness.from_z(z0)
    idx, mask = brightness.bright_buffer(bright, spec.capacity)
    f = make_joint_logpost(spec, data, stats, idx, mask)
    sampler = samplers.init_state(f, theta0, with_grad=spec.needs_grad())
    delta_full = jnp.zeros(n, sampler.lp.dtype).at[idx].set(
        jnp.where(mask, sampler.aux, 0.0)
    )
    return FlyMCState(
        sampler=sampler,
        bright=bright,
        delta_full=delta_full,
        log_step=jnp.log(jnp.asarray(step_size, sampler.lp.dtype)),
        rng=k_chain,
        iteration=jnp.int32(0),
    )


def init_chain(
    spec: FlyMCSpec,
    data: GLMData,
    stats: CollapsedStats,
    theta0: jax.Array,
    key: jax.Array,
    z0: jax.Array | None = None,
    step_size: float = 0.1,
) -> tuple[FlyMCState, int, FlyMCSpec]:
    """Deprecated host-side init; prefer ``repro.api.firefly(...)``.

    Returns (state, setup likelihood queries, spec). The returned spec may
    have grown capacities if the initial bright set did not fit the
    requested buffer.
    """
    n = data.x.shape[0]
    state = init_chain_state(spec, data, stats, theta0, key, z0, step_size)
    if spec.axis_names:
        return state, state.bright.num, spec
    while int(jax.device_get(state.bright.num)) > spec.capacity:
        spec = _grow(spec, n)
        state = init_chain_state(spec, data, stats, theta0, key, z0, step_size)
    return state, int(jax.device_get(state.bright.num)), spec


def _grow(spec: FlyMCSpec, n: int) -> FlyMCSpec:
    return dataclasses.replace(
        spec,
        capacity=min(2 * spec.capacity, n),
        cand_capacity=min(2 * spec.cand_capacity, n),
    )


def resize_state(spec: FlyMCSpec, state: FlyMCState) -> FlyMCState:
    """Rebuild the capacity-shaped δ buffer after a capacity change.

    θ, joint lp, gradient and the bright partition are capacity-independent;
    the (C,)-shaped aux is re-gathered from ``delta_full`` — zero likelihood
    queries, bitwise-identical chain law.
    """
    idx, _ = brightness.bright_buffer(state.bright, spec.capacity)
    aux = jnp.take(state.delta_full, idx)
    return state._replace(sampler=state.sampler._replace(aux=aux))


def run_chain(
    spec: FlyMCSpec,
    data: GLMData,
    stats: CollapsedStats,
    state: FlyMCState,
    num_iters: int,
    collect: Callable[[FlyMCState], Any] | None = None,
):
    """Deprecated shim over the device-resident driver (``repro.api.sample``).

    Preserves the old return shape (samples, trace dicts, total_queries,
    possibly-grown spec). A custom ``collect`` callable needs per-iteration
    host access, so that path falls back to a host-side step loop; the
    default θ-collection runs entirely on device via chunked ``lax.scan``
    with the same exactness-preserving capacity-doubling re-run semantics.
    """
    from repro import api  # local import: api is built on this module

    alg = api.algorithm_from_spec(spec, data, stats)
    if collect is not None:
        return _run_chain_host(alg, state, num_iters, collect)
    trace = api.sample(alg, state.rng, num_iters, init_state=state)
    theta, st = jax.device_get((trace.theta[0], trace.stats))
    samples = list(theta)
    trace_dicts = [
        {
            "n_bright": int(st.n_bright[0, i]),
            "lik_queries": int(st.lik_queries[0, i]),
            "accept_prob": float(st.accept_prob[0, i]),
            "joint_lp": float(st.joint_lp[0, i]),
        }
        for i in range(num_iters)
    ]
    total_queries = int(jax.device_get(trace.total_queries))
    return samples, trace_dicts, total_queries, trace.algorithm.spec


def _run_chain_host(alg, state: FlyMCState, num_iters: int, collect):
    """Host loop fallback for run_chain(collect=...): one sync per iteration."""
    key = state.rng
    samples, trace = [], []
    total_queries = 0
    step = jax.jit(alg.step)
    # Same resume contract as repro.api.sample: the fold-in counter continues
    # from the state's iteration so a resumed segment never replays the
    # prefix's key stream.
    offset = int(jax.device_get(state.iteration))
    for i in range(offset, offset + num_iters):
        prev = state
        new_state, st = step(jax.random.fold_in(key, i), state)
        while bool(jax.device_get(st.overflow)):
            alg = alg.grow()
            prev = alg.resize(prev)
            step = jax.jit(alg.step)
            new_state, st = step(jax.random.fold_in(key, i), prev)
        state = new_state
        total_queries += int(jax.device_get(st.lik_queries))
        samples.append(collect(state))
        trace.append(
            {
                "n_bright": int(jax.device_get(st.n_bright)),
                "lik_queries": int(jax.device_get(st.lik_queries)),
                "accept_prob": float(jax.device_get(st.accept_prob)),
                "joint_lp": float(jax.device_get(st.joint_lp)),
            }
        )
    return samples, trace, total_queries, alg.spec
