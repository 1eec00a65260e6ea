"""Roofline analysis: read experiments/dryrun/*.json, emit the §Roofline table.

Per (arch x shape x mesh):
    compute term    = analytic FLOPs / (chip peak 197 TFLOP/s bf16)
    memory term     = analytic HBM bytes / (819 GB/s)
    collective term = loop-aware HLO wire bytes (TPU-adjusted) / (50 GB/s)
plus the dominant term, MODEL_FLOPS/HLO_FLOPs utilisation ratio, and a
one-line "what would move the dominant term" note.

All terms are per-device per-step seconds on the TPU v5e target.
"""

from __future__ import annotations

import argparse
import glob
import json
import os

from repro.launch.mesh import V5E, chip_peaks

PEAKS = chip_peaks(V5E)

ADVICE = {
    ("compute",): "raise arithmetic intensity: larger per-device batch or "
                  "lower-precision matmuls; already compute-bound is the goal",
    ("memory",): "cut HBM traffic: fp32->bf16 averaging buffers, microbatch "
                 "activations, fuse averaging axpy (kernels/group_average)",
    ("collective",): "cut wire bytes: arch-tuned logical mesh (less TP for "
                     "small models), sequence-parallel resharding, bf16 "
                     "averaging payload, one-shot MoE all-to-all",
}


def analyse(rec: dict) -> dict:
    a = rec["analytic"]
    colls = rec["collectives"]
    compute = a["flops_per_device"] / PEAKS.flops
    memory = a["hbm_bytes_per_device"] / PEAKS.hbm_bw
    wire = colls.get("total_wire_bytes_tpu_adjusted",
                     colls["total_wire_bytes"])
    collective = wire / PEAKS.ici_bw
    terms = {"compute": compute, "memory": memory, "collective": collective}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    useful = a["model_flops_per_device"] / max(a["flops_per_device"], 1.0)
    mem_dev = rec["memory"]["per_device_total"]
    return {
        "tag": rec["tag"],
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dom,
        "step_lower_bound_s": bound,
        "roofline_fraction": compute / bound if bound else 0.0,
        "useful_flop_ratio": useful,
        "hbm_per_device_GiB": mem_dev / 2**30,
        "fits_hbm": mem_dev <= PEAKS.hbm_bytes,
        "advice": ADVICE[(dom,)],
    }


def fmt_s(x: float) -> str:
    if x >= 1.0:
        return f"{x:7.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:6.1f}ms"
    return f"{x*1e6:6.0f}us"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--json-out", default="experiments/roofline.json")
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()

    rows = []
    for path in sorted(glob.glob(os.path.join(args.dir, "*.json"))):
        if path.endswith("summary.json"):
            continue
        rec = json.load(open(path))
        if rec.get("status") != "ok":
            if rec.get("status") == "skipped":
                rows.append({"tag": rec["tag"], "skipped": rec["reason"]})
            continue
        rows.append(analyse(rec))

    with open(args.json_out, "w") as f:
        json.dump(rows, f, indent=2)

    hdr = (f"{'pair (arch__shape__mesh)':58s} {'compute':>9s} {'memory':>9s} "
           f"{'collect':>9s} {'dominant':>10s} {'cmp/roof':>8s} "
           f"{'useful':>7s} {'HBM GiB':>8s} fits")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if "skipped" in r:
            print(f"{r['tag']:58s} SKIP ({r['skipped']})")
            continue
        print(f"{r['tag']:58s} {fmt_s(r['compute_s']):>9s} "
              f"{fmt_s(r['memory_s']):>9s} {fmt_s(r['collective_s']):>9s} "
              f"{r['dominant']:>10s} {r['roofline_fraction']:8.2%} "
              f"{r['useful_flop_ratio']:7.2f} {r['hbm_per_device_GiB']:8.2f} "
              f"{'y' if r['fits_hbm'] else 'N'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
