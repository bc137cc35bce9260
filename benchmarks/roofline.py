"""Roofline assembly (brief deliverable g).

Reads the dry-run JSONs (launch.dryrun) and emits the per-(arch × shape)
roofline table for the single-pod mesh:

    compute_s    = HLO dot-FLOPs per device / 197e12
    memory_s     = HLO HBM-traffic per device / 819e9
    collective_s = bf16-corrected collective wire bytes per device / 50e9
    model_vs_hlo = (6·N·D / chips) / HLO_FLOPs   (remat/redundancy waste)

plus the dominant term and a what-would-move-it note. FLOPs/traffic/
collectives come from the trip-count-aware HLO parse (launch.hlo_analysis),
not cost_analysis (which counts while bodies once — see module docs).

Usage: PYTHONPATH=src python -m benchmarks.roofline [--results DIR] [--mesh single]
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

ARCH_ORDER = [
    "whisper-tiny", "qwen1.5-110b", "stablelm-1.6b", "qwen2-7b",
    "llama3.2-3b", "mixtral-8x7b", "arctic-480b", "recurrentgemma-9b",
    "rwkv6-7b", "llava-next-mistral-7b",
]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

NOTES = {
    "compute_s": "raise arithmetic intensity (larger per-chip batch, fuse "
    "attention, skip masked SWA blocks)",
    "memory_s": "cut HBM traffic (remat policy, fused CE, bf16 collectives, "
    "time-chunked recurrence)",
    "collective_s": "cut wire bytes (int8 pod grads, overlap gathers with "
    "compute, TP-resident serve weights)",
}


def load(results: Path, mesh: str):
    rows = {}
    for f in sorted(results.glob(f"dryrun_{mesh}_*.json")):
        rec = json.loads(f.read_text())
        rows[(rec["arch"], rec["shape"])] = rec
    return rows


def fmt_s(x: float) -> str:
    if x >= 1.0:
        return f"{x:7.2f}s "
    return f"{x*1e3:7.1f}ms"


def table(rows, mesh: str) -> str:
    out = [
        f"### Roofline — {mesh} pod "
        "(v5e: 197 TF/s bf16, 819 GB/s HBM, 50 GB/s ICI)",
        "",
        "| arch | shape | compute | memory | collective | dominant | "
        "6ND/HLO | fits 16G | per-dev GiB |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            rec = rows.get((arch, shape))
            if rec is None:
                continue
            if rec["status"] == "skipped":
                out.append(
                    f"| {arch} | {shape} | — | — | — | skipped "
                    f"(full attention @500k) | — | — | — |"
                )
                continue
            if rec["status"] != "ok":
                out.append(
                    f"| {arch} | {shape} | ERROR: {rec['error'][:60]} |"
                )
                continue
            r = rec["roofline"]
            mem = rec["memory"]
            out.append(
                "| {a} | {s} | {c} | {m} | {k} | {dom} | {ratio:.2f} | "
                "{fit} | {gib:.2f} |".format(
                    a=arch,
                    s=shape,
                    c=fmt_s(r["compute_s"]),
                    m=fmt_s(r["memory_s"]),
                    k=fmt_s(r["collective_s"]),
                    dom=r["dominant"].replace("_s", ""),
                    ratio=r["model_vs_hlo_flops"],
                    fit="yes" if mem["fits_16g"] else "NO",
                    gib=mem["per_device_bytes"] / 2**30,
                )
            )
    return "\n".join(out)


def summarize(rows):
    """Pick the three hillclimb cells per the brief."""
    ok = {k: v for k, v in rows.items() if v["status"] == "ok"}

    def frac(rec):
        r = rec["roofline"]
        total = max(r["compute_s"], r["memory_s"], r["collective_s"])
        return r["compute_s"] / total if total else 0.0

    worst = min(ok.items(), key=lambda kv: frac(kv[1]))
    coll = max(
        ok.items(),
        key=lambda kv: kv[1]["roofline"]["collective_s"]
        / max(kv[1]["roofline"]["compute_s"], 1e-9),
    )
    return {"worst_roofline_fraction": worst[0], "most_collective_bound": coll[0]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default="benchmarks/results")
    ap.add_argument("--mesh", default="single")
    args = ap.parse_args()
    rows = load(Path(args.results), args.mesh)
    print(table(rows, args.mesh))
    print()
    print("hillclimb candidates:", summarize(rows))


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
