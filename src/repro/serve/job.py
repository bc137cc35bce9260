"""Job: one tenant's posterior-sampling request, and what makes jobs batchable.

A :class:`Job` is everything the service needs to run one FlyMC posterior:
a dataset, a GLM family with its hyperparameters, the FlyMC spec knobs
(kernel, buffer capacities, backends), a seed, a convergence
:class:`TerminationPolicy`, and the requested collectors. Jobs are pure
descriptions — :func:`build_algorithm` turns one into the same
:class:`~repro.api.algorithm.SamplingAlgorithm` a direct
:func:`repro.api.sample` caller would get, which is what makes the service's
exactness contract checkable: a job's trajectory in a packed batch must be
bitwise the trajectory of ``api.sample`` run alone with the same seed.

:func:`group_key` decides which jobs may share a batching group (one slot =
one chain on the chain axis of the PR-5 batched megakernels). The key pins
every *static* property of the traced step — family and its
hyperparameters, (N, D), θ-kernel, q_db, backends, adaptation schedule,
trace length, collector signature — so one compiled chunk executable serves
every member. Deliberately NOT in the key:

  * **capacity / cand_capacity** — trajectories are bitwise
    capacity-invariant (the repo's core exactness property), so the engine
    normalizes members up to one group capacity and grows it on overflow
    without fragmenting groups.
  * **step_size** — the step size lives in the chain state (``log_step``),
    not the trace, so jobs with different step sizes batch together.
  * **the dataset values** — each lane carries its own dataset as a traced
    operand, stacked along the lane axis. Only the shape (N, D) is static.

``num_chains`` IS in the key: a group lane is one whole job (its K chains
stepped by the same vmap-over-K body a solo ``api.sample(num_chains=K)``
run uses), because XLA's low-bit rounding depends on the batched extent —
a K-chain computation is only bitwise reproducible by the identical
K-chain computation, so jobs with different chain counts cannot share a
lane shape (see ``repro.serve.engine``).

:func:`chain_rows` replicates ``api.sample``'s key discipline exactly
(``split(key) → (k_init, k_steps)``, per-chain ``split`` for multi-chain)
so the per-iteration key stream — ``fold_in(chain_key, iteration)`` — is
identical in and out of the service.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.api import collectors as collectors_lib
from repro.api.algorithm import SamplingAlgorithm, firefly
from repro.core.bounds import GLMData


@dataclasses.dataclass(frozen=True)
class TerminationPolicy:
    """When a job's chains stop sampling (checked at chunk boundaries).

    A job retires when ``num_samples >= max_samples`` (always), or — once
    ``min_samples`` have committed — when every enabled convergence
    criterion holds: peeked split-R̂ ``<= target_rhat`` (requires an "rhat"
    collector) and peeked batch-means ESS ``>= min_ess`` (requires an "ess"
    collector). ``check_every`` throttles convergence peeks to every k-th
    chunk; the max_samples stop is checked every chunk regardless.
    """

    max_samples: int = 2000
    min_samples: int = 0
    target_rhat: float | None = None
    min_ess: float | None = None
    check_every: int = 1

    def __post_init__(self):
        if self.max_samples < 1:
            raise ValueError("max_samples must be >= 1")
        if self.check_every < 1:
            raise ValueError("check_every must be >= 1")


def default_collectors() -> dict:
    """The service default: full trace plus streamed R̂ (termination food)."""
    return {"trace": collectors_lib.FullTrace(), "rhat": collectors_lib.RHat()}


@dataclasses.dataclass(eq=False)
class Job:
    """One posterior-sampling request. ``family`` ∈ {logistic, softmax,
    robust}; the family hyperparameters below it apply per family (the rest
    are ignored). ``collectors`` defaults to :func:`default_collectors`;
    instances are sized by the engine (trace buffers get the group's
    ``max_samples`` plus one chunk of slack, so a terminating chunk may
    overshoot without clipping)."""

    job_id: str
    family: str
    data: GLMData
    seed: int = 0
    num_chains: int = 1
    init_position: Any = None
    # family hyperparameters
    prior_scale: float = 1.0
    xi: float = 1.5          # logistic: bound tangency
    n_classes: int = 3       # softmax
    nu: float = 4.0          # robust: Student-t dof
    sigma: float = 1.0       # robust: noise scale
    # FlyMC spec knobs
    kernel: str = "rwmh"
    step_size: float = 0.1
    q_db: float = 0.01
    mode: str = "implicit"
    resample_fraction: float = 0.1
    capacity: int = 256
    cand_capacity: int = 256
    backend: str = "jnp"
    z_backend: str = "jnp"
    adapt_target: Any = None
    num_warmup: int = 1000
    # service-level
    policy: TerminationPolicy = dataclasses.field(default_factory=TerminationPolicy)
    collectors: dict | None = None

    def __post_init__(self):
        if self.family not in ("logistic", "softmax", "robust"):
            raise ValueError(f"unknown family {self.family!r}")
        if self.num_chains < 1:
            raise ValueError("num_chains must be >= 1")
        if self.collectors is None:
            self.collectors = default_collectors()
        self.collectors = collectors_lib.validate_collectors(self.collectors)
        if self.policy.target_rhat is not None and "rhat" not in self.collectors:
            raise ValueError(
                f"job {self.job_id!r}: target_rhat termination needs an "
                f"'rhat' collector (e.g. api.RHat())"
            )
        if self.policy.min_ess is not None and "ess" not in self.collectors:
            raise ValueError(
                f"job {self.job_id!r}: min_ess termination needs an 'ess' "
                f"collector (e.g. api.BatchMeansESS())"
            )


def build_model(job: Job):
    """The job's GLMModel — same constructor path a direct user takes."""
    from repro.models.bayes_glm import GLMModel

    if job.family == "logistic":
        return GLMModel.logistic(job.data, prior_scale=job.prior_scale,
                                 xi=job.xi)
    if job.family == "softmax":
        return GLMModel.softmax(job.data, n_classes=job.n_classes,
                                prior_scale=job.prior_scale)
    return GLMModel.robust(job.data, nu=job.nu, sigma=job.sigma,
                           prior_scale=job.prior_scale)


def build_algorithm(
    job: Job, capacity: int | None = None, cand_capacity: int | None = None
) -> SamplingAlgorithm:
    """The job as a SamplingAlgorithm — bitwise the solo-run construction.

    ``capacity``/``cand_capacity`` override the job's request (the engine
    runs every group member at the group capacity; trajectories don't care).
    """
    return firefly(
        build_model(job),
        kernel=job.kernel,
        capacity=job.capacity if capacity is None else capacity,
        cand_capacity=(job.cand_capacity if cand_capacity is None
                       else cand_capacity),
        q_db=job.q_db,
        mode=job.mode,
        resample_fraction=job.resample_fraction,
        step_size=job.step_size,
        adapt_target=job.adapt_target,
        num_warmup=job.num_warmup,
        backend=job.backend,
        z_backend=job.z_backend,
    )


def collector_sig(colls: dict) -> tuple:
    """Hashable signature of a collector set: type + static config per name.

    Array-valued fields (e.g. ``PosteriorPredictive.x_eval``) contribute
    shape/dtype only — two jobs whose collectors differ solely in array
    *values* still share a compiled fold (the arrays ride in the carry or
    the closure; different values never change the jaxpr... but they DO
    change closure-captured constants, so such collectors also fragment on
    ``id``). Sorted by name so dict order never splits a group.
    """
    out = []
    for name in sorted(colls):
        col = colls[name]
        fields = []
        if dataclasses.is_dataclass(col):
            for f in dataclasses.fields(col):
                v = getattr(col, f.name)
                if hasattr(v, "shape") and hasattr(v, "dtype"):
                    fields.append((f.name, ("array", tuple(v.shape),
                                            str(v.dtype), id(v))))
                elif callable(v):
                    fields.append((f.name, ("fn", id(v))))
                else:
                    fields.append((f.name, v))
        out.append((name, type(col).__name__, tuple(fields)))
    return tuple(out)


def group_key(job: Job) -> tuple:
    """The batching-group key: jobs with equal keys share one engine (and
    its compiled chunk executables). See the module docstring for what is
    deliberately excluded."""
    n, d = job.data.x.shape
    fam = (job.family,)
    if job.family == "logistic":
        fam += (job.prior_scale, job.xi)
    elif job.family == "softmax":
        fam += (job.prior_scale, job.n_classes)
    else:
        fam += (job.prior_scale, job.nu, job.sigma)
    return (
        fam, n, d, job.num_chains,
        job.kernel, job.q_db, job.mode, job.resample_fraction,
        job.backend, job.z_backend, job.adapt_target, job.num_warmup,
        job.policy.max_samples,
        collector_sig(job.collectors),
    )


def chain_rows(job: Job, alg: SamplingAlgorithm):
    """Per-chain initial states and chain keys, ``api.sample``'s discipline.

    Returns ``(states, chain_keys)`` with a leading ``(num_chains,)`` axis
    on both — single-chain jobs replicate the solo path's unsplit
    ``k_steps`` as a length-1 axis (``fold_in`` of the same key by the same
    iteration gives the same per-step keys either way).
    """
    key = jax.random.key(job.seed)
    k_init, k_steps = jax.random.split(key)
    position = (job.init_position if job.init_position is not None
                else alg.default_position)
    if position is None:
        raise ValueError(f"job {job.job_id!r} has no initial position")
    # The driver's init: the operand form, with the rows passed in.
    operands = (alg.data, alg.stats)
    if job.num_chains == 1:
        states = jax.tree.map(
            lambda l: l[None],
            jax.jit(alg.init_data)(k_init, position, *operands),
        )
        chain_keys = k_steps[None]
    else:
        init_keys = jax.random.split(k_init, job.num_chains)
        positions = jax.tree.map(
            lambda l: jnp.broadcast_to(l, (job.num_chains,) + jnp.shape(l)),
            position,
        )
        states = jax.jit(jax.vmap(alg.init_data, in_axes=(0, 0, None, None)))(
            init_keys, positions, *operands
        )
        chain_keys = jax.random.split(k_steps, job.num_chains)
    return states, chain_keys
