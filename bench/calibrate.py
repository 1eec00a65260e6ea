#!/usr/bin/env python3
"""Readings that the correctness limits are set from, for one cell.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,... \
        [--control-seeds 3] [--out calibrate-<name>.jsonl]

For every seed: the program's checked steps, exactly as a run of the cell
makes them (``harness.build`` and ``harness.program_readings``), and the
reference's; the three numbers of ``correct.gaps`` between them are the
program's readings.  For the first ``--control-seeds`` seeds also the
control (the reference in int8, in the program's place) and the fault a
one-chip cell can have beside a state left unchanged, planted in the
reference put in the program's place: half of each worker's batch left
out.  A state left unchanged reads 1 on ``change_gap`` by construction and
needs no run.  One JSON line per reading.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import correct as CK  # noqa: E402
from bench import harness  # noqa: E402


def calibrate(workload, seeds, control_seeds, emit, *,
              require_accelerator=True, cell=None):
    import jax
    from repro import compat
    r = cell or harness.resolve(harness.load_manifest(), workload)
    harness.enable_compile_cache(jax)
    devices = harness.chips_for(jax, r["cell"]["chips"], require_accelerator)
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        tr, data, t0 = harness.build(jax, r, seed, devices)
        with compat.set_mesh(tr.mesh):
            prog, _ = harness.program_readings(jax, r, tr, t0)
        mesh = tr.mesh
        del tr
        gc.collect()
        prog = harness.change_readings(jax, r, mesh, prog, seed)
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = harness.reference_for(jax, r, data, t0, seed)
        t_ref = time.perf_counter() - t
        emit({"seed": seed, "kind": "program", **CK.gaps(prog, ref),
              "losses": prog["losses"], "ref_losses": ref["losses"],
              "program_s": t_prog, "reference_s": t_ref})
        if i == 0:
            emit({"seed": seed, "kind": "leaves",
                  "program": {k: {n: v.tolist() for n, v in prog[k].items()}
                              for k in ("grad_norms", "change_norms")},
                  "reference": {k: {n: v.tolist() for n, v in ref[k].items()}
                                for k in ("grad_norms", "change_norms")}})
        if i >= control_seeds:
            continue
        for mode, fault in (("int8", None), ("reference", "half_batch")):
            other = harness.reference_for(jax, r, data, t0, seed, mode=mode,
                                          fault=fault)
            emit({"seed": seed, "kind": fault or mode,
                  **CK.gaps(other, ref), "losses": other["losses"]})


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec = {"workload": args.workload, **rec}
        line = json.dumps(rec)
        if rec["kind"] != "leaves":
            print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        calibrate(args.workload, seeds, args.control_seeds, emit)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
