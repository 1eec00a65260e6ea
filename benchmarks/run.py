"""Benchmark harness — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  fig4_throughput_imagenet   cluster-sim steps/hour @P=256, derived = WAGMA
                             speedup over local SGD        (paper Fig. 4)
  fig7_throughput_wmt        same for the WMT workload     (paper Fig. 7)
  fig10_throughput_rl        same for the RL workload, P=1024 (paper Fig. 10)
  fig5_convergence_*         final-loss per SGD variant + ablations 1-4
                             (paper Fig. 5 / §V-B experiments)
  table1_collective_bytes    per-device bytes/step per algorithm for the
                             paper's three models           (paper Table I/§VI)
"""

from __future__ import annotations

import time

import numpy as np


def row(name: str, us: float, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_throughput():
    from benchmarks.cluster_sim import simulate
    model_bytes = {"imagenet": 25.56e6 * 4, "wmt": 61.36e6 * 4,
                   "rl": 8.48e6 * 4}
    setups = [("fig4_throughput_imagenet", "imagenet", 256),
              ("fig7_throughput_wmt", "wmt", 64),
              ("fig10_throughput_rl", "rl", 1024)]
    for name, wl, Pmax in setups:
        res = {}
        for algo in ("allreduce", "local_sgd", "dpsgd", "sgp", "adpsgd",
                     "eager", "wagma"):
            res[algo] = simulate(algo, Pmax, model_bytes=model_bytes[wl],
                                 workload=wl, steps=120)
        wag = res["wagma"].steps_per_hour
        base = res["local_sgd"].steps_per_hour
        us_per_step = 3600e6 / wag
        row(name, us_per_step, f"wagma_speedup_vs_localsgd={wag/base:.2f}x")
        for algo, r in res.items():
            row(f"  {name}.{algo}", 3600e6 / r.steps_per_hour,
                f"steps_per_hour={r.steps_per_hour:.1f}")


def bench_convergence():
    from benchmarks import convergence
    t0 = time.time()
    rows, checks = convergence.main()
    per = (time.time() - t0) * 1e6 / len(rows)
    for disp, loss, comm in rows:
        row(f"fig5_convergence_{disp}", per,
            f"final_loss={loss:.4f};comm_MB_per_step={comm/1e6:.2f}")
    row("fig5_claims_validated", 0.0,
        f"{sum(checks.values())}/{len(checks)}")


def bench_collective_model():
    from repro.core.group_allreduce import collective_bytes_per_device
    models = {"resnet50": 25.56e6 * 4, "transformer": 61.36e6 * 4,
              "resnet_lstm": 8.48e6 * 4}
    for mname, nbytes in models.items():
        for P_ in (64, 1024):
            S = int(np.sqrt(P_))
            w = collective_bytes_per_device(nbytes, P_, S, "wagma")
            r = collective_bytes_per_device(nbytes, P_, S, "ring_allreduce")
            b = collective_bytes_per_device(nbytes, P_, S, "butterfly_global")
            row(f"table1_collective_bytes_{mname}_P{P_}", 0.0,
                f"wagma_MB={w/1e6:.1f};ring_MB={r/1e6:.1f};"
                f"butterfly_global_MB={b/1e6:.1f}")


def main() -> None:
    print("name,us_per_call,derived")
    bench_collective_model()
    bench_throughput()
    bench_convergence()


if __name__ == "__main__":
    main()
