"""Plain references, one module per model family.

Each is written from the paper's formulas in NumPy and imports nothing of
the program: the same rows and the same tuning point give the log
densities, the bound gaps δ and the posterior that the sampler's output
is compared with. ``prec="f64"`` is the reference; ``prec="bf16"`` is the
control, the same arithmetic one precision below the f32 the
configurations state: rows, θ and every per-row intermediate rounded to
bfloat16, the products of a dot and the sums over rows accumulated in
f32 (a TPU matrix unit's bf16 path).
"""
