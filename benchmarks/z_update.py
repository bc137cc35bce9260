"""z-update engine benchmark: jnp (length-N) vs fused (streamed) z-phase.

Times ONE z-phase — bright→dark decisions, dark→bright candidate selection
+ δ + decisions, partition maintenance — for the two engines on the
quickstart problem, plus the full chain through ``repro.api.sample``:

  * ``z_backend="jnp"``   — three (N,) ``jax.random.uniform`` draws, (N,)
    boolean scatters, and a full-N cumsum re-partition (``from_z``) every
    step;
  * ``z_backend="fused"`` — the ``kernels/z_update`` streaming candidate
    kernel (in-kernel counter RNG, in-kernel compaction) + O(C) counter
    uniforms on the bright/candidate buffers + O(changed) incremental
    partition swaps (``brightness.apply_flips``).

Reports µs per z-phase, µs per full step, and an analytic HBM-traffic model
(bytes per z-phase) for each engine. Off-TPU the fused numbers run the
kernel in interpret mode — correctness-path timings, not kernel speed — and
are flagged (``interpret: true``), same policy as ``benchmarks/bright_glm``.
Results merge into ``BENCH_flymc.json`` under ``z_update_backend``.
"""

from __future__ import annotations

import jax

import jax.numpy as jnp

from benchmarks._util import BENCH_PATH, best_of, merge_write, quickstart_problem
from repro import api
from repro.analysis.kernels import derive_traffic
from repro.core import brightness, flymc
from repro.kernels.common import default_interpret
from repro.kernels.z_update.ops import z_candidates


def _bytes_model(n: int, capacity: int, q_db: float) -> dict:
    """Analytic HBM traffic per z-phase (4-byte lanes), by term.

    jnp: hand model — every term is length-N: three uniform arrays (write +
    read), two (N,) boolean scatter round-trips for z, and the from_z
    rebuild (read z, two cumsums r+w, write tab, scatter arr); an XLA
    pipeline with no BlockSpecs to derive from. fused: the in-kernel terms
    (``kernel_*`` — the padded partition-array stream, the candidate
    writeback, the count scalar) are derived from the kernel's own
    BlockSpecs and grid by ``repro.analysis.kernels.derive_traffic``, the
    same model the ``kernel-bytes`` sweep rule pins; only the XLA glue
    around the kernel stays hand-modeled: the pad/reshape round-trip
    feeding it, the O(C) counter-uniform/bright buffers outside the
    derived candidate writeback, and the O(changed) ``apply_flips``
    scatters.
    """
    c = capacity
    jnp_terms = {
        "uniform_draws_3xN": 3 * 2 * 4 * n,
        "z_scatters_2xN": 2 * 2 * 4 * n,
        "from_z_rebuild": 8 * 4 * n,  # z + 2 cumsums (r+w) + tab + arr
        "candidate_buffers_O(C)": 6 * 4 * c,
    }
    s, i32 = jax.ShapeDtypeStruct, jnp.int32
    (model,) = derive_traffic(
        lambda arr, num, kw: z_candidates(
            arr, num, kw, q_db=q_db, cand_capacity=c, interpret=True
        ),
        s((n,), i32), s((), i32), s((2,), i32),
    ).values()
    fused_terms = {
        **{f"kernel_{name}": op["bytes"]
           for name, op in model["per_operand"].items()},
        "arr_pad_reshape": 2 * 4 * n,
        "bright_buffers_O(C)": 9 * 4 * c,
        "apply_flips_O(changed)": 8 * 4 * c,
    }
    return {
        "jnp": {"terms": jnp_terms, "total": sum(jnp_terms.values())},
        "fused": {"terms": fused_terms, "total": sum(fused_terms.values())},
    }


def _z_phase_fn(alg, data):
    """jit'd (key, state) -> updated bright state, isolating the z-phase."""
    spec = alg.spec

    def z_phase(key, state):
        theta = state.sampler.theta
        if spec.z_backend == "fused":
            bright, delta_full, q, ov = flymc._fused_z_update(
                spec, data, key, theta, state.bright, state.delta_full,
                state.sampler.aux,
            )
        else:
            z, delta_full, q, ov = flymc._implicit_z_update(
                spec, data, key, theta, state.bright, state.delta_full,
                state.sampler.aux,
            )
            bright = brightness.from_z(z)
        return bright.num, delta_full.sum(), q, ov

    return jax.jit(z_phase)


def bench(n=5000, d=21, capacity=1024, iters=300, q_db=0.01, reps=3):
    tuned = quickstart_problem(n, d)
    key = jax.random.key(3)
    interpret = default_interpret()

    record = {"problem": {"name": "quickstart-logistic", "n": n, "d": d,
                          "capacity": capacity, "iters": iters, "q_db": q_db}}
    bmodel = _bytes_model(n, capacity, q_db)

    for zb in ("jnp", "fused"):
        alg = api.firefly(
            tuned, kernel="rwmh", capacity=capacity, cand_capacity=capacity,
            q_db=q_db, step_size=0.03, adapt_target="auto", z_backend=zb,
        )
        state = jax.jit(alg.init)(jax.random.key(1), alg.default_position)
        z_phase = _z_phase_fn(alg, tuned.data)
        n_evals = 50
        keys = [jax.random.fold_in(key, i) for i in range(n_evals)]
        z_phase(keys[0], state)  # compile
        wall_z, _ = best_of(
            lambda: [z_phase(k, state) for k in keys][-1], reps=reps
        )
        us_z = wall_z * 1e6 / n_evals

        api.sample(alg, key, 2, chunk_size=2)  # compile chunk
        wall_step, _ = best_of(
            lambda: api.sample(alg, key, iters, chunk_size=iters), reps=reps
        )
        us_step = wall_step * 1e6 / iters

        record[zb] = {
            "us_per_z_phase": us_z,
            "us_per_step": us_step,
            "hbm_bytes_per_z_phase_model": bmodel[zb]["total"],
            "hbm_bytes_terms": bmodel[zb]["terms"],
            "interpret": interpret if zb == "fused" else False,
        }
    record["bytes_model_ratio"] = (
        bmodel["jnp"]["total"] / bmodel["fused"]["total"]
    )
    # Interpret-mode wall times are not kernel speed — null the ratio there,
    # same policy as bright_glm_backend.
    record["us_per_z_phase_ratio"] = (
        None if interpret
        else record["jnp"]["us_per_z_phase"] / record["fused"]["us_per_z_phase"]
    )
    return record


def main(quick=False):
    record = bench(
        n=2000 if quick else 5000,
        capacity=512 if quick else 1024,
        iters=100 if quick else 300,
    )
    merge_write({"z_update_backend": record})
    for zb in ("jnp", "fused"):
        r = record[zb]
        tag = " (interpret)" if r["interpret"] else ""
        print(f"{zb:>6}{tag}: {r['us_per_z_phase']:9.1f} us/z-phase  "
              f"{r['us_per_step']:9.1f} us/step  "
              f"~{r['hbm_bytes_per_z_phase_model']/1e3:.1f} KB HBM/z-phase")
    ratio = record["us_per_z_phase_ratio"]
    print(f"z-phase bytes-model ratio (jnp/fused): "
          f"{record['bytes_model_ratio']:.1f}x; wall ratio: "
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
