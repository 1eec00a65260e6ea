"""Request-level serving simulator: latency percentiles vs load + disagg gate.

Answers the question the serving engine exists for: *what request rate can
this cluster sustain at what tail latency?* — and whether prefill/decode
**disaggregation** (serve/kv_transfer.py) beats colocation at the modeled
operating point.

Model (DESIGN.md §14):

* Per-phase latencies come from the analytic cost model
  (``prefill_cost`` / ``decode_cost``, launch/costmodel.py) pushed
  through the chip roofline (the v5e entry of
  ``launch/mesh.CHIP_PEAKS``).
* KV transfer (disaggregated only) is costed by
  ``plan.link_transfer_seconds`` on the DCN link class at the link's
  modeled-optimal message budget — the same arithmetic the
  ``LinkCostedConnector`` executes (``--measured`` swaps in the
  calibrated constants from the tracked ``LINK_CONSTANTS.json``).
* Arrivals are Poisson; prompt/output lengths are seeded lognormals.
  The sweep is expressed as *load fractions* of the cluster's modeled
  capacity so the same flags exercise any arch at comparable pressure.
* A **colocated** pod interleaves prefill into its continuous-batching
  decode loop: each admission stalls every running request's next token
  for the full prefill — the head-of-line blocking disaggregation
  removes.  A **disaggregated** cluster splits the same pod count into
  FCFS prefill pods and pure-decode pods; each request's KV blocks ride
  DCN between them, which delays its *second* token (the first comes
  back from the prefill itself).
* The decode batch is capped by pod HBM: weights + per-token KV bytes
  (``kv_transfer.kv_payload_bytes``) must fit — the simulator derives
  the block-pool capacity instead of assuming one.

Reported per placement and load: TTFT p50/p95/p99, per-output-token
latency — both per-request mean (TPOT) and per-gap inter-token latency
(ITL) percentiles — and goodput (finished requests/s meeting the
TTFT+TPOT SLO).  ``disagg_win`` = colocated p99 ITL / disaggregated p99
ITL at the operating point: colocation stalls *every* running stream
once per admission, while the disagg transfer taxes each stream exactly
once, so under load the tail gap is where the placement decision shows.

Results land in ``BENCH_serving.json`` at the repo root.  ``--check``
(CHECK-SERVE, wired into scripts/ci.sh) exits non-zero unless
disaggregation wins p99 ITL *and* holds goodput at the operating point.
"""

import argparse
import json
import os
import sys
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))

import numpy as np

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import plan as plan_mod
from repro.launch import costmodel
from repro.launch.mesh import V5E, chip_peaks
from repro.serve.kv_transfer import kv_payload_bytes

OUT_JSON = os.path.join(_ROOT, "BENCH_serving.json")
PEAKS = chip_peaks(V5E)


def _roofline(report) -> float:
    return max(report.flops_per_device / PEAKS.flops,
               report.hbm_bytes_per_device / PEAKS.hbm_bw)


class Latency:
    """Memoised per-phase roofline latencies for one (arch, pod) point."""

    def __init__(self, cfg, n_model: int):
        self.cfg, self.n_model = cfg, n_model
        self._pf, self._dec = {}, {}

    def prefill(self, prompt_len: int) -> float:
        key = max(64, int(prompt_len))
        if key not in self._pf:
            shape = InputShape("pf", key, 1, "prefill")
            self._pf[key] = _roofline(costmodel.prefill_cost(
                self.cfg, shape, n_dp=1, n_model=self.n_model))
        return self._pf[key]

    def decode(self, batch: int, ctx: int) -> float:
        # quantise ctx so the memo table stays small
        ctx = max(256, 1 << int(np.ceil(np.log2(max(ctx, 1)))))
        key = (int(batch), ctx)
        if key not in self._dec:
            shape = InputShape("dec", ctx, key[0], "decode")
            self._dec[key] = _roofline(costmodel.decode_cost(
                self.cfg, shape, n_dp=1, n_model=self.n_model))
        return self._dec[key]


@dataclass
class SimRequest:
    rid: int
    t_arrive: float
    prompt_len: int
    n_new: int
    t_ready: float = 0.0            # KV available at the decode pod
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    t_last: Optional[float] = None  # previous token's emission time
    tokens: int = 0                 # decode tokens produced so far
    itl: List[float] = field(default_factory=list)

    @property
    def ttft(self) -> float:
        return self.t_first - self.t_arrive

    @property
    def tpot(self) -> float:
        return (self.t_done - self.t_first) / max(self.n_new - 1, 1)


def sample_workload(rng, n: int, *, max_prompt: int,
                    max_new: int) -> List[SimRequest]:
    t = np.cumsum(rng.exponential(1.0, size=n))   # unit rate; scaled later
    pl = np.clip(rng.lognormal(np.log(max_prompt / 4), 0.7, n), 16,
                 max_prompt).astype(int)
    nn = np.clip(rng.lognormal(np.log(max_new / 2), 0.6, n), 4,
                 max_new).astype(int)
    return [SimRequest(i, float(t[i]), int(pl[i]), int(nn[i]))
            for i in range(n)]


def run_decode_pod(jobs: List[SimRequest], lat: Latency, *,
                   inline_prefill: bool, max_batch: int) -> None:
    """Continuous-batching loop of one pod (mutates the jobs in place).

    ``inline_prefill``: prefill runs on this pod between decode
    iterations and stalls the running batch (colocated).  Otherwise jobs
    arrive with KV ready at ``t_ready`` and ``t_first``/``t_last``
    already set by the prefill pod (disaggregated decode pod).
    """
    waiting = deque(sorted(jobs, key=lambda r: r.t_ready))
    running: List[SimRequest] = []
    now = 0.0
    while waiting or running:
        if not running and waiting and waiting[0].t_ready > now:
            now = waiting[0].t_ready
        while waiting and len(running) < max_batch \
                and waiting[0].t_ready <= now:
            req = waiting.popleft()
            if inline_prefill:
                now += lat.prefill(req.prompt_len)   # stalls the whole pod
                req.t_first = now                    # first token at prefill
                req.t_last = now
            if req.n_new <= 1:
                req.t_done = req.t_first
                continue
            running.append(req)
        if not running:
            continue
        ctx = int(np.mean([r.prompt_len + r.tokens for r in running]))
        now += lat.decode(len(running), ctx)
        for req in list(running):
            req.tokens += 1
            req.itl.append(now - req.t_last)
            req.t_last = now
            if req.tokens >= req.n_new - 1:
                req.t_done = now
                running.remove(req)


def run_prefill_pods(reqs: List[SimRequest], lat: Latency, *,
                     n_pods: int, transfer) -> None:
    """FCFS prefill across ``n_pods``; sets t_first and decode t_ready."""
    free_at = [0.0] * n_pods
    for req in sorted(reqs, key=lambda r: r.t_arrive):
        pod = int(np.argmin(free_at))
        start = max(free_at[pod], req.t_arrive)
        done = start + lat.prefill(req.prompt_len)
        free_at[pod] = done
        req.t_first = done                           # first token from prefill
        req.t_last = done
        req.t_ready = done + transfer(req.prompt_len)


def simulate(reqs: List[SimRequest], lat: Latency, *, pods: int,
             prefill_pods: int, max_batch: int, transfer,
             disaggregated: bool) -> List[SimRequest]:
    reqs = [SimRequest(r.rid, r.t_arrive, r.prompt_len, r.n_new)
            for r in reqs]
    if disaggregated:
        decode_pods = pods - prefill_pods
        assert decode_pods >= 1
        run_prefill_pods(reqs, lat, n_pods=prefill_pods, transfer=transfer)
    else:
        decode_pods = pods
        for r in reqs:
            r.t_ready = r.t_arrive                   # prefill runs in-loop
    shards = [[] for _ in range(decode_pods)]
    for r in reqs:
        shards[r.rid % decode_pods].append(r)
    for shard in shards:
        run_decode_pod(shard, lat, inline_prefill=not disaggregated,
                       max_batch=max_batch)
    return reqs


def percentiles(xs) -> dict:
    xs = np.asarray(sorted(xs))
    return {p: float(np.percentile(xs, q))
            for p, q in (("p50", 50), ("p95", 95), ("p99", 99))}


def summarise(reqs: List[SimRequest], *, slo_ttft: float,
              slo_tpot: float) -> dict:
    span = max(r.t_done for r in reqs) - min(r.t_arrive for r in reqs)
    good = [r for r in reqs if r.ttft <= slo_ttft and r.tpot <= slo_tpot]
    gaps = [g for r in reqs for g in r.itl]
    return {
        "ttft_s": percentiles([r.ttft for r in reqs]),
        "tpot_s": percentiles([r.tpot for r in reqs]),
        "itl_s": percentiles(gaps) if gaps else {},
        "goodput_rps": len(good) / max(span, 1e-9),
        "slo_attainment": len(good) / len(reqs),
        "finish_span_s": float(span),
    }


def modeled_capacity_rps(lat: Latency, reqs, *, pods: int,
                         max_batch: int) -> float:
    """Rough cluster capacity: per-request pod occupancy at full batch."""
    mean_prompt = float(np.mean([r.prompt_len for r in reqs]))
    mean_new = float(np.mean([r.n_new for r in reqs]))
    ctx = int(mean_prompt + mean_new / 2)
    occupancy = (lat.prefill(int(mean_prompt))
                 + mean_new * lat.decode(max_batch, ctx) / max_batch)
    return pods / occupancy


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--pods", type=int, default=8,
                    help="total serving pods (disagg splits them)")
    ap.add_argument("--prefill-pods", type=int, default=1)
    ap.add_argument("--devices-per-pod", type=int, default=4,
                    help="model-parallel degree inside a pod")
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--load", type=float, nargs="*",
                    default=[0.3, 0.5, 0.7, 0.85],
                    help="arrival rates as fractions of modeled capacity")
    ap.add_argument("--qps", type=float, nargs="*", default=None,
                    help="absolute arrival rates (overrides --load)")
    ap.add_argument("--max-prompt", type=int, default=4096)
    ap.add_argument("--max-new", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--slo-ttft", type=float, default=2.0)
    ap.add_argument("--slo-tpot", type=float, default=0.05)
    ap.add_argument("--measured", action="store_true",
                    help="price KV transfer with the calibrated "
                         "LINK_CONSTANTS.json instead of the nominal DCN "
                         "class (host-smoke calibrations are wildly "
                         "pessimistic, so the CI gate runs nominal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=OUT_JSON)
    ap.add_argument("--check", action="store_true",
                    help="CHECK-SERVE gate: disagg wins p99 ITL and holds "
                         "goodput at the operating point (mid-sweep load)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    n_model = args.devices_per_pod
    lat = Latency(cfg, n_model)

    # KV transfer rides DCN.  ``--measured`` swaps in the calibrated
    # constants from the tracked LINK_CONSTANTS.json; the default (and the
    # CI gate) prices the nominal class so the result is deterministic
    # whatever the last calibration measured.
    link = plan_mod.DCN
    measured = bool(args.measured
                    and os.path.exists(plan_mod.DEFAULT_LINK_CONSTANTS_PATH))
    if measured:
        topo = plan_mod.Topology.hierarchical(
            ("data", "pod"), (2, 2)).with_measured()
        link = topo.link_classes[1]

    def transfer(prompt_len: int) -> float:
        return plan_mod.link_transfer_seconds(
            kv_payload_bytes(cfg, prompt_len), link)

    # derive the pod's KV token capacity from HBM (the block-pool budget)
    total, _ = costmodel.param_count(cfg)
    weight_bytes = total * 2 / n_model
    kv_tok = kv_payload_bytes(cfg, 1) / n_model
    kv_budget = 0.9 * PEAKS.hbm_bytes - weight_bytes
    cap_tokens = int(kv_budget / kv_tok)
    max_batch = min(args.max_batch,
                    max(1, cap_tokens // (args.max_prompt + args.max_new)))

    rng = np.random.default_rng(args.seed)
    base = sample_workload(rng, args.requests, max_prompt=args.max_prompt,
                           max_new=args.max_new)
    cap_rps = modeled_capacity_rps(lat, base, pods=args.pods,
                                   max_batch=max_batch)
    if args.qps:
        points = [(q, q / cap_rps) for q in args.qps]
    else:
        points = [(f * cap_rps, f) for f in args.load]
    print(f"[serve_sim] {cfg.name}: modeled capacity {cap_rps:.1f} rps "
          f"({args.pods} pods x {n_model} chips, max_batch {max_batch}, "
          f"KV capacity {cap_tokens} tokens/pod)")

    sweep = []
    for qps, loadf in points:
        reqs = [SimRequest(r.rid, r.t_arrive / qps, r.prompt_len, r.n_new)
                for r in base]
        colo = simulate(reqs, lat, pods=args.pods, prefill_pods=0,
                        max_batch=max_batch, transfer=transfer,
                        disaggregated=False)
        disagg = simulate(reqs, lat, pods=args.pods,
                          prefill_pods=args.prefill_pods,
                          max_batch=max_batch, transfer=transfer,
                          disaggregated=True)
        kw = dict(slo_ttft=args.slo_ttft, slo_tpot=args.slo_tpot)
        c, d = summarise(colo, **kw), summarise(disagg, **kw)
        win = c["itl_s"]["p99"] / max(d["itl_s"]["p99"], 1e-12)
        sweep.append({"qps": qps, "load": loadf, "colocated": c,
                      "disaggregated": d, "disagg_win_p99_itl": win})
        print(f"[serve_sim] load={loadf:.2f} ({qps:.1f} rps) | colo p99 itl "
              f"{c['itl_s']['p99']*1e3:.2f} ms ttft "
              f"{c['ttft_s']['p99']*1e3:.0f} ms goodput "
              f"{c['goodput_rps']:.1f} rps | disagg p99 itl "
              f"{d['itl_s']['p99']*1e3:.2f} ms ttft "
              f"{d['ttft_s']['p99']*1e3:.0f} ms goodput "
              f"{d['goodput_rps']:.1f} rps | win {win:.2f}x")

    op = sweep[len(sweep) // 2]
    report = {
        "arch": cfg.name,
        "pods": args.pods,
        "prefill_pods": args.prefill_pods,
        "devices_per_pod": n_model,
        "max_batch": max_batch,
        "kv_token_capacity_per_pod": cap_tokens,
        "modeled_capacity_rps": cap_rps,
        "dcn_link": {"name": link.name, "alpha": link.alpha,
                     "beta": link.beta, "measured": measured},
        "transfer_example_s": {str(n): transfer(n) for n in (1024, 4096)},
        "slo": {"ttft_s": args.slo_ttft, "tpot_s": args.slo_tpot},
        "requests": args.requests,
        "seed": args.seed,
        "sweep": sweep,
        "operating_point": {
            "qps": op["qps"],
            "load": op["load"],
            "disagg_win_p99_itl": op["disagg_win_p99_itl"],
            "goodput_colocated_rps": op["colocated"]["goodput_rps"],
            "goodput_disaggregated_rps":
                op["disaggregated"]["goodput_rps"],
        },
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"[serve_sim] wrote {args.out}")

    if args.check:
        opp = report["operating_point"]
        ok_itl = opp["disagg_win_p99_itl"] > 1.0
        ok_goodput = (opp["goodput_disaggregated_rps"]
                      >= 0.95 * opp["goodput_colocated_rps"])
        print("CHECK-SERVE", "PASS" if (ok_itl and ok_goodput) else "FAIL",
              f"(load={opp['load']:.2f}: disagg p99-ITL win "
              f"{opp['disagg_win_p99_itl']:.2f}x, goodput "
              f"{opp['goodput_disaggregated_rps']:.2f} vs "
              f"{opp['goodput_colocated_rps']:.2f} rps colocated)")
        if not (ok_itl and ok_goodput):
            sys.exit(1)


if __name__ == "__main__":
    main()
