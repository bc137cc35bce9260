"""Plain references, one module per model family.

Each is written from the paper's formulas in NumPy and imports nothing of
the program: the same rows and the same tuning point give the log
densities, the bound gaps δ and the posterior that the sampler's output
is compared with. ``prec="f64"`` is the reference; ``prec="bf16"`` is the
control, the same arithmetic one precision below the f32 the
configurations state: rows, θ and every per-row intermediate rounded to
bfloat16, the products of a dot and the sums over rows accumulated in
f32 (a TPU matrix unit's bf16 path).

The contract with ``bench/harness.py``. θ arrives flat: the program's θ,
of any shape, flattened in row-major order to P = prod(θ's shape) values
(a (K, D) θ of K classes to K·D, class by class); the module reshapes it
itself, from ``cfg["classes"]`` where θ is a matrix.

    tune(x, t, theta_star (P,), cfg)        -> ξ (N,)
    rows(theta (P,) or (P, M), x, t, xi, cfg, ar)
                                             -> (log L_n, log B_n, δ_n),
                                                each (N,) or (N, M)
    log_prior(theta (P,), cfg, ar)          -> log prior, constant dropped
    posterior(x, t, cfg, rng)               -> (mean (Q,), sd (Q,),
                                                se of mean (Q,), IS ESS)
    coords(theta (..., P), cfg)             -> (..., Q), optional

``coords`` names the coordinates that ESS, ``mean_z`` and ``sd_gap`` are
taken over, and ``posterior`` gives its moments in them: for a model whose
likelihood cannot see some direction of θ (softmax, where adding one
vector to every class's θ changes nothing) the identified contrasts, so
that the checks measure the sampler and not the prior's walk along that
direction. Without it the coordinates are θ's own (Q = P).
"""
