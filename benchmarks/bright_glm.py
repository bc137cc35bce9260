"""θ-update backend benchmark: jnp gather vs fused Pallas bright-GLM kernel.

Times the FlyMC θ-update likelihood evaluation (the paper's O(|bright|·D)
hot path, §3.1) on the quickstart problem two ways:

  * ``backend="jnp"``    — plain XLA: materialize the gathered rows, evaluate
    the bound, mask + reduce;
  * ``backend="pallas"`` — ``kernels/bright_glm``: scalar-prefetched row DMAs
    straight into VMEM tiles, δ and the masked log L̃ reduction fused
    in-kernel.

Reports µs per joint-log-posterior evaluation, µs/step for a full chain
through ``repro.api.sample``, and an analytic HBM-traffic model (bytes per
θ-eval) for each backend. Off-TPU the Pallas numbers are interpret-mode —
correctness-path timings, not kernel speed — and are flagged as such in the
record (``interpret: true``). Results merge into ``BENCH_flymc.json`` under
``bright_glm_backend``.
"""

from __future__ import annotations

import jax

import jax.numpy as jnp

from benchmarks._util import BENCH_PATH, best_of, merge_write, quickstart_problem
from repro import api
from repro.analysis.kernels import derive_traffic
from repro.core import brightness, flymc
from repro.kernels.bright_glm.ops import bright_glm
from repro.kernels.common import default_interpret, pad_to


def _bytes_model(n: int, d: int, capacity: int) -> dict:
    """Analytic HBM traffic per θ-eval (f32), C = bright capacity.

    jnp: hand model — the gather materializes a (C, D) row matrix (read +
    write), the bound evaluation streams it again, plus θ and the per-row
    t/ξ/δ vectors; XLA's gather pipeline has no BlockSpecs to derive a
    model from. pallas: derived from the kernel's own BlockSpecs, grid and
    DMAs by ``repro.analysis.kernels.derive_traffic`` — the same model the
    ``kernel-bytes`` sweep rule pins — so this record and the static
    analysis cannot drift apart.
    """
    c = capacity
    s, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    (model,) = derive_traffic(
        lambda *a: bright_glm(*a, interpret=True),
        s((n, 1, pad_to(d, 128)), f32), s((n,), f32), s((n,), f32),
        s((c,), i32), s((), i32), s((d,), f32),
    ).values()
    return {
        "jnp": 3 * c * d * 4 + d * 4 + 4 * c * 4,
        "pallas": model["total"],
        "pallas_terms": {
            name: op["bytes"] for name, op in model["per_operand"].items()
        },
    }


def bench(n=5000, d=21, capacity=1024, iters=300, q_db=0.01, reps=3):
    tuned = quickstart_problem(n, d)
    key = jax.random.key(3)
    interpret = default_interpret()

    record = {"problem": {"name": "quickstart-logistic", "n": n, "d": d,
                          "capacity": capacity, "iters": iters, "q_db": q_db}}
    bmodel = _bytes_model(n, d, capacity)

    for backend in ("jnp", "pallas"):
        alg = api.firefly(
            tuned, kernel="rwmh", capacity=capacity, cand_capacity=capacity,
            q_db=q_db, step_size=0.03, adapt_target="auto", backend=backend,
        )
        state = jax.jit(alg.init)(jax.random.key(1), alg.default_position)
        idx, mask = brightness.bright_buffer(state.bright, capacity)
        f = jax.jit(
            flymc.make_joint_logpost(alg.spec, alg.data, tuned.stats,
                                     idx, mask)
        )
        theta = state.sampler.theta
        n_evals = 50
        f(theta)  # compile
        wall_eval, _ = best_of(
            lambda: [f(theta + 1e-6 * i) for i in range(n_evals)][-1],
            reps=reps,
        )
        us_eval = wall_eval * 1e6 / n_evals

        api.sample(alg, key, 2, chunk_size=2)  # compile chunk
        wall_step, _ = best_of(
            lambda: api.sample(alg, key, iters, chunk_size=iters), reps=reps
        )
        us_step = wall_step * 1e6 / iters

        record[backend] = {
            "us_per_eval": us_eval,
            "us_per_step": us_step,
            "hbm_bytes_per_eval_model": bmodel[backend],
            "interpret": interpret if backend == "pallas" else False,
        }
        if backend == "pallas":
            record[backend]["hbm_bytes_terms"] = bmodel["pallas_terms"]
    # A compiled-vs-interpreted ratio is not a kernel-speed comparison:
    # record it only when the pallas numbers come from a real TPU compile.
    record["us_per_step_ratio"] = (
        None if interpret
        else record["jnp"]["us_per_step"] / record["pallas"]["us_per_step"]
    )
    return record


def main(quick=False):
    record = bench(
        n=2000 if quick else 5000,
        capacity=512 if quick else 1024,
        iters=100 if quick else 300,
    )
    merge_write({"bright_glm_backend": record})
    for backend in ("jnp", "pallas"):
        r = record[backend]
        tag = " (interpret)" if r["interpret"] else ""
        print(f"{backend:>6}{tag}: {r['us_per_eval']:9.1f} us/eval  "
              f"{r['us_per_step']:9.1f} us/step  "
              f"~{r['hbm_bytes_per_eval_model']/1e6:.2f} MB HBM/eval")
    ratio = record["us_per_step_ratio"]
    print(f"us/step ratio (jnp/pallas): "
          f"{'n/a (interpret mode — not kernel speed)' if ratio is None else f'{ratio:.2f}x'} "
          f"(wrote {BENCH_PATH.name})")
    return record


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(quick=ap.parse_args().quick)
