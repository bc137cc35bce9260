"""Sampling algorithms as pure ``(init, step)`` pairs.

:func:`firefly` builds the paper's exact-subset chain; :func:`regular_mcmc`
the full-data baseline. Both return a :class:`SamplingAlgorithm` whose
``step`` emits :class:`~repro.core.flymc.StepStats` — the same Info pytree —
so the :mod:`repro.api.driver` treats them identically.

Kernels are resolved through :data:`repro.core.samplers.KERNEL_REGISTRY`
(no stringly-typed special cases) and bounds through
:data:`repro.core.bounds.BOUND_REGISTRY` (explicit :class:`Bound` protocol).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bounds as bounds_lib
from repro.core import flymc, samplers
from repro.core.bounds import CollapsedStats, GLMData
from repro.core.flymc import FlyMCSpec, StepStats


@dataclasses.dataclass(frozen=True)
class SamplingAlgorithm:
    """A pure (init, step) pair plus the hooks the driver needs.

    init(key, position) -> State
    step(key, state)    -> (State, StepStats)

    ``step_chains``/``init_chains`` are the optional chain-batched
    counterparts — ``step_chains(keys (K,), state (K, ...))`` advances all
    K chains in one application. The driver dispatches them directly when
    ``num_chains > 1``; when None it batches the per-chain functions
    itself, which is already optimal for single-device algorithms — the
    Pallas kernels coalesce the chain axis into one leading-grid-dimension
    launch under batching regardless (``custom_vmap`` rules in
    ``kernels/*/ops``). Provide them only when batching must be something
    other than vmap: :func:`repro.distributed.flymc_dist.chain_fleet`
    supplies a pair that shard_maps the chain axis across devices.

    ``grow``/``resize``/``init_overflow`` exist only for algorithms with
    bounded on-device buffers (FlyMC's bright capacity): ``grow()`` returns
    the same algorithm with doubled capacities, ``resize(state)`` re-shapes a
    state for the grown buffers without new likelihood queries, and
    ``init_overflow(state)`` flags an initial state that does not fit. All
    three are None for algorithms that cannot overflow.

    ``step_data``/``data``/``stats`` are the dataset-as-operand form of the
    step: ``step_data(key, state, data, stats)`` is ``step`` with the
    dataset and its sufficient statistics passed as arguments instead of
    closed over, and ``init_data(key, position, data, stats)`` the same
    form of ``init``. When present, the driver threads ``alg.data``/
    ``alg.stats`` through the jitted init and chunks as traced operands
    rather than baking them in as compile-time constants (at the paper's
    N = 1.8M a baked-in dataset is a GB of executable).
    ``step_chains_data`` is the chain-batched counterpart
    (``(keys (K,), state (K, ...), data, stats)``) for algorithms whose
    batching is not vmap — the distributed fleet supplies one that
    shard_maps the chain axis with the dataset replicated as an operand,
    so even a sharded fleet's chunk jit carries no dataset constant (the
    :mod:`repro.analysis` closure-constant rule pins this). This is a
    bitwise-visible choice, not a plumbing detail: XLA's constant folding rounds data-dependent
    reductions differently for a baked-in dataset than for the identical
    values passed as an operand (low-bit ``joint_lp``/``accept_prob``
    differences on CPU, observed at e.g. N=512, D=8). The operand form is
    the ONE form shared by solo runs and the :mod:`repro.serve` group
    engines — whose lanes must take data as operands to pack jobs into a
    shared executable — which is what makes a packed job's trajectory
    bitwise its solo run's.
    """

    init: Callable[[jax.Array, Any], Any]
    step: Callable[[jax.Array, Any], tuple[Any, StepStats]]
    grow: Callable[[], "SamplingAlgorithm"] | None = None
    resize: Callable[[Any], Any] | None = None
    init_overflow: Callable[[Any], jax.Array] | None = None
    position: Callable[[Any], jax.Array] | None = None
    default_position: Any = None
    spec: Any = None  # engine config (e.g. FlyMCSpec), for introspection
    step_chains: Callable[[jax.Array, Any], tuple[Any, StepStats]] | None = None
    init_chains: Callable[[jax.Array, Any], Any] | None = None
    step_data: Callable[..., tuple[Any, StepStats]] | None = None
    init_data: Callable[..., Any] | None = None
    step_chains_data: Callable[..., tuple[Any, StepStats]] | None = None
    data: Any = None
    stats: Any = None

    def position_of(self, state) -> jax.Array:
        if self.position is not None:
            return self.position(state)
        return state.sampler.theta

    def batched_step(self):
        """The chain-batched step: (keys (K,), state (K, ...)) -> same.

        ``step_chains`` when provided, else ``step`` batched over the
        chain axis — the ONE encoding of this fallback (driver and fleet
        wrappers both call it), under which the Pallas kernels coalesce
        into a single chain-grid launch via their custom_vmap rules.
        """
        if self.step_chains is not None:
            return self.step_chains
        return jax.vmap(self.step)

    def batched_init(self):
        """Chain-batched init: ``init_chains`` or ``init`` batched."""
        if self.init_chains is not None:
            return self.init_chains
        return jax.vmap(self.init)

    def output_structs(self, state):
        """Shape/dtype structs of one chain's per-step outputs, no compute.

        Returns ``(position_struct, stats_struct)`` — ``jax.ShapeDtypeStruct``
        pytrees for ``position_of(state)`` and the ``StepStats`` that ``step``
        emits. ``state`` may be a concrete single-chain state or itself a
        struct pytree; everything runs under ``jax.eval_shape``. This is what
        lets :mod:`repro.api.collectors` size their carries before the first
        step executes.
        """
        key = jax.eval_shape(lambda: jax.random.key(0))
        pos = jax.eval_shape(self.position_of, state)
        _, stats = jax.eval_shape(self.step, key, state)
        return pos, stats


def _spec_from(
    model,
    *,
    bound,
    log_prior,
    data,
    stats,
    kernel,
    capacity,
    cand_capacity,
    q_db,
    mode,
    resample_fraction,
    adapt_target,
    kernel_params,
    axis_names,
    backend,
    z_backend,
    num_warmup,
):
    """Normalize (model | explicit pieces) into (FlyMCSpec, data, stats)."""
    if model is not None:
        bound = bound if bound is not None else model.bound
        log_prior = log_prior if log_prior is not None else model.log_prior
        data = data if data is not None else model.data
        stats = stats if stats is not None else getattr(model, "stats", None)
    if data is None or log_prior is None or bound is None:
        raise ValueError(
            "firefly() needs a model, or explicit bound=, log_prior=, data="
        )
    bound = bounds_lib.get_bound(bound)
    if backend not in ("jnp", "pallas"):
        raise ValueError(
            f"unknown backend {backend!r}; expected 'jnp' or 'pallas'"
        )
    if backend == "pallas" and bounds_lib.fused_family_of(bound) is None:
        raise ValueError(
            f"backend='pallas' requires a FusedBound "
            f"(fused_family + fused_kernel_kwargs, not invalidated by "
            f"log_lik/log_bound overrides); "
            f"{type(bound).__name__} only implements the jnp path"
        )
    if z_backend not in ("jnp", "fused"):
        raise ValueError(
            f"unknown z_backend {z_backend!r}; expected 'jnp' or 'fused'"
        )
    if z_backend == "fused" and mode != "implicit":
        raise ValueError(
            "z_backend='fused' requires mode='implicit' (the fused engine "
            "streams Algorithm 2's sparse dark→bright candidate proposals; "
            "Algorithm 1's explicit Gibbs resampling has no such stream)"
        )
    if stats is None:
        stats = bound.suffstats(data)
    samplers.get_kernel(kernel)  # fail fast on unknown kernels
    if adapt_target == "auto":
        adapt_target = samplers.get_kernel(kernel).target_accept
        if adapt_target >= 1.0:  # slice: no accept rate to adapt on
            adapt_target = None
    n = data.x.shape[0]
    spec = FlyMCSpec(
        bound=bound,
        log_prior=log_prior,
        kernel=kernel,
        capacity=min(int(capacity), n),
        cand_capacity=min(int(cand_capacity), n),
        q_db=q_db,
        mode=mode,
        resample_fraction=resample_fraction,
        kernel_kwargs=tuple(kernel_params),
        axis_names=tuple(axis_names),
        adapt_target=adapt_target,
        backend=backend,
        z_backend=z_backend,
        num_warmup=int(num_warmup),
    )
    return spec, data, stats


def firefly(
    model=None,
    *,
    bound=None,
    log_prior=None,
    data: GLMData | None = None,
    stats: CollapsedStats | None = None,
    kernel: str = "rwmh",
    capacity: int = 1024,
    cand_capacity: int = 1024,
    q_db: float = 0.01,
    mode: str = "implicit",
    resample_fraction: float = 0.1,
    step_size: float = 0.1,
    adapt_target: float | str | None = None,
    num_warmup: int = 1000,
    kernel_params=(),
    axis_names=(),
    backend: str = "jnp",
    z_backend: str = "jnp",
) -> SamplingAlgorithm:
    """Build the FlyMC sampling algorithm (paper §2–3) as an (init, step) pair.

    ``model`` is anything carrying ``.bound/.log_prior/.data`` (and optionally
    ``.stats``), e.g. :class:`repro.models.bayes_glm.GLMModel`; individual
    pieces can be overridden by keyword. ``bound`` accepts a
    :class:`~repro.core.bounds.Bound` instance or a registered name
    ("logistic", "softmax", "student-t"). ``kernel`` names a registered
    θ-kernel ("rwmh", "mala", "slice", "hmc"); pass ``adapt_target="auto"``
    to adapt the step size toward the kernel's standard accept rate.
    Adaptation runs for the first ``num_warmup`` iterations only — after
    warmup the step size freezes bitwise, so the sampling-phase chain is a
    fixed Markov kernel (exactness requires it).

    ``backend`` selects the θ-update likelihood engine: ``"jnp"`` (gather +
    bound evaluation in plain XLA) or ``"pallas"`` (the fused
    ``kernels/bright_glm`` gather+δ+reduction kernel; interpret-mode
    fallback off-TPU). All three built-in bounds support ``"pallas"``;
    custom bounds need the :class:`~repro.core.bounds.FusedBound` hook.

    ``z_backend`` selects the z-update engine (implicit mode): ``"jnp"``
    (per-datum length-N uniforms + full cumsum re-partition) or ``"fused"``
    (the ``kernels/z_update`` streaming candidate kernel with in-kernel
    counter RNG + O(changed) incremental partition maintenance). The two
    engines are law-equivalent but follow different uniform streams, so
    their realized trajectories differ bitwise.
    """
    spec, data, stats = _spec_from(
        model,
        bound=bound, log_prior=log_prior, data=data, stats=stats,
        kernel=kernel, capacity=capacity, cand_capacity=cand_capacity,
        q_db=q_db, mode=mode, resample_fraction=resample_fraction,
        adapt_target=adapt_target, kernel_params=kernel_params,
        axis_names=axis_names, backend=backend, z_backend=z_backend,
        num_warmup=num_warmup,
    )
    return _firefly_from_spec(spec, data, stats, step_size)


def _firefly_from_spec(
    spec: FlyMCSpec, data: GLMData, stats: CollapsedStats, step_size: float
) -> SamplingAlgorithm:
    n = data.x.shape[0]
    if spec.backend == "pallas":
        data = bounds_lib.with_gather_layout(data)
    stats = bounds_lib.recenter(spec.bound, stats)

    def init(key, position):
        return init_data(key, position, data, stats)

    def init_data(key, position, data_, stats_):
        return flymc.init_chain_state(
            spec, data_, stats_, position, key, step_size=step_size
        )

    def step(key, state):
        # The chain state's rng slot is overwritten with the driver's key so
        # the kernel stays a pure function of (key, state).
        return flymc.flymc_step(spec, data, stats, state._replace(rng=key))

    def step_data(key, state, data_, stats_):
        # The operand-data form the driver and the serve engines both jit
        # (see the SamplingAlgorithm docstring for why the form matters).
        return flymc.flymc_step(spec, data_, stats_, state._replace(rng=key))

    # Memoized: repeated growth (e.g. across sample() calls that hit the
    # same overflow) must yield the *same* algorithm object so the driver's
    # jit cache keys on a stable step identity and never re-traces.
    grown = []

    def grow():
        if not grown:
            grown.append(
                _firefly_from_spec(flymc._grow(spec, n), data, stats, step_size)
            )
        return grown[0]

    def resize(state):
        return flymc.resize_state(spec, state)

    def init_overflow(state):
        return state.bright.num > spec.capacity

    theta_dim = data.x.shape[-1]
    if isinstance(spec.bound, bounds_lib.SoftmaxBound):
        default_position = jnp.zeros((data.xi.shape[-1], theta_dim))
    else:
        default_position = jnp.zeros((theta_dim,))

    can_grow = spec.capacity < n or spec.cand_capacity < n
    return SamplingAlgorithm(
        init=init,
        step=step,
        grow=grow if can_grow else None,
        resize=resize,
        init_overflow=init_overflow,
        default_position=default_position,
        spec=spec,
        step_data=step_data,
        init_data=init_data,
        data=data,
        stats=stats,
    )


def algorithm_from_spec(
    spec: FlyMCSpec,
    data: GLMData,
    stats: CollapsedStats,
    step_size: float = 0.1,
) -> SamplingAlgorithm:
    """Wrap a legacy FlyMCSpec as a SamplingAlgorithm (shim entry point)."""
    return _firefly_from_spec(spec, data, stats, step_size)


# ---------------------------------------------------------------------------
# Full-data baseline
# ---------------------------------------------------------------------------


class MCMCState(NamedTuple):
    sampler: samplers.SamplerState
    log_step: jax.Array
    iteration: jax.Array


def regular_mcmc(
    model=None,
    *,
    logdensity_fn=None,
    n_data: int | None = None,
    kernel: str = "rwmh",
    step_size: float = 0.1,
    adapt_target: float | str | None = None,
    num_warmup: int = 1000,
    kernel_params=(),
    theta_shape=None,
) -> SamplingAlgorithm:
    """Full-data MCMC baseline as an (init, step) pair.

    ``model`` supplies the exact log posterior and the likelihood-query
    accounting (every density evaluation costs N queries — Table 1's cost
    model); alternatively pass ``logdensity_fn`` (θ -> (lp, aux)) plus
    ``n_data`` directly. Emits the same StepStats as firefly (overflow is
    always False, n_bright = N) so the driver and diagnostics are shared.
    Step-size adaptation (``adapt_target``) is warmup-only, exactly like
    :func:`firefly`: the update freezes after ``num_warmup`` iterations.
    """
    data = None
    if model is not None:
        if logdensity_fn is None:
            # Operand form: the driver passes the rows in, so no jit
            # bakes the dataset into its executable.
            data = model.data
            density_of = model.full_logpdf_fn
            logdensity_fn = density_of(data)
        n_data = n_data if n_data is not None else model.data.x.shape[0]
        theta_shape = theta_shape or model.theta_shape
    if logdensity_fn is None or n_data is None:
        raise ValueError("regular_mcmc() needs a model or logdensity_fn + n_data")
    ks = samplers.get_kernel(kernel)
    if adapt_target == "auto":
        adapt_target = None if ks.target_accept >= 1.0 else ks.target_accept
    n = jnp.int32(n_data)

    def init_with(density, position):
        st = samplers.init_state(density, position, with_grad=ks.needs_grad)
        return MCMCState(
            sampler=st,
            log_step=jnp.log(jnp.asarray(step_size, st.lp.dtype)),
            iteration=jnp.int32(0),
        )

    def step_with(density, key, state):
        # The named scope labels the step's ops for a device trace, like
        # flymc_step's phases.
        with jax.named_scope("regular.theta"):
            kern = samplers.bind(kernel, density, kernel_params)
            new, info = kern(key, state.sampler, jnp.exp(state.log_step))
            log_step = state.log_step
            if adapt_target is not None:
                # Warmup-only (see flymc_step): adapt-forever would mean the
                # post-warmup chain never follows a fixed Markov kernel.
                adapted = samplers.adapt_step_size(
                    log_step, info.accept_prob, adapt_target, state.iteration
                )
                log_step = jnp.where(
                    state.iteration < num_warmup, adapted, log_step
                )
        out = MCMCState(new, log_step, state.iteration + 1)
        stats = StepStats(
            n_bright=n,
            lik_queries=info.n_evals * n,
            accept_prob=info.accept_prob,
            overflow=jnp.bool_(False),
            joint_lp=new.lp,
        )
        return out, stats

    operand_forms = {}
    if data is not None:
        operand_forms = dict(
            init_data=lambda key, pos, d, _: init_with(density_of(d), pos),
            step_data=lambda key, st, d, _: step_with(density_of(d), key, st),
            data=data,
        )
    default_position = (
        jnp.zeros(theta_shape) if theta_shape is not None else None
    )
    return SamplingAlgorithm(
        init=lambda key, position: init_with(logdensity_fn, position),
        step=lambda key, state: step_with(logdensity_fn, key, state),
        default_position=default_position,
        **operand_forms,
    )
