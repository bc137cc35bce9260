"""Benchmark harness entry point (brief deliverable d).

One benchmark per paper table/figure plus the roofline headline:
  * Table 1 (three experiments × three algorithms) — benchmarks/table1.py
  * Fig 1 / §3.1 bound-tightness claim       — benchmarks/bound_tightness.py
  * §Roofline headline cells (from the dry-run JSONs, if present)

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--full]
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks.bound_tightness import check_paper_claim
from benchmarks.table1 import format_results, table1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="5%% scale, 400 iters (CI-sized)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale OPV (N=1.8M)")
    args = ap.parse_args()

    rows: list[str] = []

    # --- Table 1 -----------------------------------------------------------
    if args.quick:
        res = table1(scale=0.05, iters=400, burn=100, opv_n=20_000)
    else:
        res = table1(
            scale=1.0, iters=1200, burn=300,
            opv_n=1_800_000 if args.full else 100_000,
        )
    print(format_results(res))
    for r in res:
        rows.append(
            f"table1/{r.name},{r.us_per_iter:.1f},"
            f"q={r.queries_per_iter:.0f};ess1000={r.ess_per_1000:.2f};"
            f"speedup={r.speedup:.2f}"
        )

    # --- θ-update backend: jnp vs fused pallas kernel ----------------------
    from benchmarks.bright_glm import main as bench_backend

    brec = bench_backend(quick=args.quick)
    rows.append(
        f"bright_glm/pallas,{brec['pallas']['us_per_eval']:.1f},"
        f"jnp_us={brec['jnp']['us_per_eval']:.1f};"
        f"interpret={brec['pallas']['interpret']}"
    )

    # --- z-update engine: jnp vs fused streaming kernel --------------------
    from benchmarks.z_update import main as bench_z

    zrec = bench_z(quick=args.quick)
    rows.append(
        f"z_update/fused,{zrec['fused']['us_per_z_phase']:.1f},"
        f"jnp_us={zrec['jnp']['us_per_z_phase']:.1f};"
        f"bytes_ratio={zrec['bytes_model_ratio']:.1f};"
        f"interpret={zrec['fused']['interpret']}"
    )

    # --- chain scaling: vmap vs chain-batched megakernels ------------------
    from benchmarks.chain_scaling import main as bench_chains

    srec = bench_chains(quick=args.quick)
    top = str(max(int(k) for k in srec["batched"]))
    rows.append(
        f"chain_scaling/batched{top},"
        f"{srec['batched'][top]['us_per_step']:.1f},"
        f"vmap_us={srec['vmap'][top]['us_per_step']:.1f};"
        f"marginal_us={srec['batched'][top]['marginal_us_per_chain']:.1f};"
        f"sublinear={srec['batched'][top]['sublinear']};"
        f"interpret={srec['interpret']}"
    )

    # --- streaming collectors vs dense FullTrace ---------------------------
    from benchmarks.collectors import main as bench_collectors

    crec = bench_collectors(quick=args.quick)["collectors"]
    rows.append(
        f"collectors/streaming,{crec['streaming']['us_per_step']:.1f},"
        f"full_us={crec['full_trace']['us_per_step']:.1f};"
        f"overhead_us={crec['overhead_us_per_step']:.2f};"
        f"bytes_ratio={crec['bytes_ratio']:.0f}"
    )

    # --- serving: continuous batching vs sequential ------------------------
    from benchmarks.serving import main as bench_serving

    vrec = bench_serving(quick=args.quick)
    rows.append(
        f"serving/batched,{vrec['service']['wall_s'] * 1e6 / vrec['n_jobs']:.0f},"
        f"seq_s={vrec['sequential']['wall_s']};speedup={vrec['speedup']};"
        f"p95_s={vrec['service']['latency_p95_s']};"
        f"occupancy={vrec['service']['occupancy_mean']};"
        f"steps_saved={vrec['auto_termination']['steps_saved_frac']};"
        f"bitwise={vrec['fixed_length_results_bitwise_equal']}"
    )

    # --- fault tolerance: durable checkpoints, supervision, chaos ----------
    from benchmarks.faults import main as bench_faults

    frec = bench_faults(quick=args.quick)
    rows.append(
        f"faults/save,{frec['checkpoint']['durable_save_s'] * 1e6:.0f},"
        f"verify_s={frec['checkpoint']['verify_s']};"
        f"restore_s={frec['checkpoint']['verified_restore_s']};"
        f"supervision_overhead={frec['supervision']['overhead_frac']};"
        f"chaos_restarts={frec['chaos']['restarts']};"
        f"chaos_survivors={frec['chaos']['survivors']}"
    )

    # --- static analysis: cost fingerprints of every hot-path jit ----------
    from benchmarks.static_analysis import main as bench_static

    arec = bench_static(quick=args.quick)
    worst_rng = max(
        e["max_rng_size"] for name, e in arec["entry_points"].items()
        if name != "step.jnp"  # the registered known-bad engine
    )
    rows.append(
        f"static_analysis/sweep,0.0,"
        f"ok={arec['ok']};entry_points={len(arec['entry_points'])};"
        f"worst_fused_rng={worst_rng}"
    )

    # --- §3.1 bound tightness ---------------------------------------------
    bt = check_paper_claim()
    print(
        f"\nbound tightness (xi=1.5): max p(bright)="
        f"{bt['claim_max_p_bright']:.5f} in 0.1<L<0.9 "
        f"(paper: <0.02 — {'holds' if bt['claim_holds'] else 'FAILS'})"
    )
    rows.append(
        f"bound_tightness/xi1.5,0.0,"
        f"max_p={bt['claim_max_p_bright']:.5f};holds={bt['claim_holds']}"
    )

    # --- roofline headline (if the dry-run has been run) --------------------
    results = Path(__file__).parent / "results"
    headline = [
        ("qwen1.5-110b", "train_4k"),
        ("rwkv6-7b", "train_4k"),
        ("mixtral-8x7b", "decode_32k"),
    ]
    for arch, shape in headline:
        f = results / f"dryrun_single_{arch.replace('.', '_')}_{shape}.json"
        if not f.exists():
            continue
        rec = json.loads(f.read_text())
        if rec.get("status") != "ok":
            continue
        r = rec["roofline"]
        rows.append(
            f"roofline/{arch}/{shape},{r['compute_s']*1e6:.0f},"
            f"mem_s={r['memory_s']:.3f};coll_s={r['collective_s']:.3f};"
            f"dominant={r['dominant']};fits={rec['memory']['fits_16g']}"
        )

    print("\nname,us_per_call,derived")
    for row in rows:
        print(row)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
