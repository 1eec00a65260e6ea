"""Microbenchmark: per-leaf vs bucketed (serial) vs overlapped group averaging.

Measures the tentpole claims of the bucketed averaging subsystem on an 8-way
forced-host-device CPU mesh:

* **ppermute launches** per averaging step (traced from the jaxpr) drop from
  ``n_leaves * log2(S)`` to ``n_buckets * log2(S)`` — and stay there under
  the overlapped wavefront schedule (overlap reorders, never multiplies);
* wall time per step for the four realisations of the same math:
  per-leaf reference, bucketed + jnp combine, bucketed + fused Pallas
  combine, bucketed + overlapped pipeline (interpret mode off-TPU, so CPU
  timings measure the bucketing/launch saving, not the kernel — run on a
  TPU backend for the HBM-floor combine numbers);
* the alpha-beta-gamma model's prediction at cluster scale for the
  transformer_wmt config (the paper's own model): serial-bucketed step time
  (``wire + combine`` per stage, fixed 32 MiB budget) vs overlapped step
  time (``max(wire, combine) + fill`` at the modeled-optimal budget from
  ``bucketing.choose_bucket_bytes``).

Results land in ``BENCH_group_average.json`` at the repo root so the perf
trajectory is machine-trackable PR over PR.

A second modeled section covers the **hierarchical (2-link-class) topology**
(DESIGN.md §9): intra-pod butterfly stages priced at ICI constants, inter-pod
stages at DCN constants, each link class at its own
``plan.choose_class_bucket_bytes`` budget — recorded next to the same
topology forced onto one global 32 MiB budget and the flat-topology model.

Usage:
    python benchmarks/bench_group_average.py [--layers 24] [--d 512]
    python benchmarks/bench_group_average.py --check      # model-only, fast;
        exits non-zero unless overlapped < serial for transformer_wmt AND
        the hierarchical per-class budgets beat the single global budget
        with distinct per-class choices
"""

import argparse
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import bucketing, grouping
from repro.core import group_allreduce as ga
from repro.launch.hlo_analysis import count_ppermutes
from repro.launch.mesh import make_mesh

OUT_JSON = os.path.join(_ROOT, "BENCH_group_average.json")


def transformer_like_tree(rng, n_dp: int, layers: int, d: int):
    """A params pytree with realistic leaf-count structure (per dp replica)."""
    tree = {"emb": jnp.asarray(rng.normal(size=(n_dp, 4 * d, d)) * 0.02,
                               jnp.float32)}
    for i in range(layers):
        tree[f"blk{i}"] = {
            "wq": jnp.asarray(rng.normal(size=(n_dp, d, d)), jnp.float32),
            "wk": jnp.asarray(rng.normal(size=(n_dp, d, d)), jnp.float32),
            "wv": jnp.asarray(rng.normal(size=(n_dp, d, d)), jnp.float32),
            "wo": jnp.asarray(rng.normal(size=(n_dp, d, d)), jnp.float32),
            "w1": jnp.asarray(rng.normal(size=(n_dp, d, 4 * d)), jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(n_dp, 4 * d, d)), jnp.float32),
            "ln1": jnp.asarray(rng.normal(size=(n_dp, d)), jnp.float32),
            "ln2": jnp.asarray(rng.normal(size=(n_dp, d)), jnp.float32),
        }
    return tree


def bench(fn, tree, iters: int) -> float:
    out = jax.block_until_ready(fn(tree))          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(tree))
    del out
    return (time.perf_counter() - t0) / iters


def modeled_transformer_wmt(*, P_cluster: int = 64, tau: int = 10) -> dict:
    """Alpha-beta-gamma model for the paper's WMT transformer at scale.

    Serial baseline: fixed 32 MiB budget, per-stage ``wire + combine``.
    Overlapped: modeled-optimal budget, per-stage ``max(wire, combine)``
    plus pipeline fill/drain (core/overlap.py wavefront schedule).  The
    modeling itself is ``costmodel.averaging_comm_cost`` — this function
    only supplies the exact payload/leaf count from the real model's
    ``eval_shape`` and reshapes the CommReport into the tracked JSON.
    """
    from repro.configs import get_config
    from repro.launch.costmodel import averaging_comm_cost
    from repro.models.registry import build_model

    cfg = get_config("transformer-wmt")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_leaves = len(jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)))
    payload = bucketing.tree_payload_bytes(shapes)   # exact, real dtypes
    S = grouping.default_group_size(P_cluster)
    stages = grouping.ilog2(S)

    rep = averaging_comm_cost(cfg, P=P_cluster, S=S, tau=tau,
                              n_leaves=n_leaves, payload_bytes=payload)
    return {
        "config": cfg.name,
        "P": P_cluster, "S": S, "tau": tau,
        "payload_bytes": payload, "n_leaves": n_leaves,
        "alpha_s": ga.DEFAULT_ALPHA, "beta_s_per_byte": ga.DEFAULT_BETA,
        "gamma_s_per_byte": ga.DEFAULT_GAMMA,
        "serial": {"bucket_bytes": bucketing.DEFAULT_BUCKET_BYTES,
                   "n_buckets": rep.n_buckets,
                   "launches_per_group_step": rep.n_buckets * stages,
                   "modeled_step_s": rep.t_serial_gamma},
        "overlapped": {"bucket_bytes": rep.chosen_bucket_bytes,
                       "n_buckets": rep.n_buckets_overlapped,
                       "launches_per_group_step":
                           rep.n_buckets_overlapped * stages,
                       "modeled_step_s": rep.t_overlapped},
        "overlapped_same_budget_step_s": rep.t_overlapped_same_budget,
        "per_leaf_step_s": rep.t_per_leaf,
        "chosen_bucket_bytes": rep.chosen_bucket_bytes,
        "overlap_win": rep.overlap_speedup,
        "combine_hidden_s_per_step":
            rep.t_serial_gamma - rep.t_overlapped_same_budget,
    }


def modeled_hierarchical_wmt(*, P_cluster: int = 64, n_pods: int = 4,
                             tau: int = 10) -> dict:
    """Per-link-class model for the WMT transformer on a pod-aware topology.

    Builds the 2-class (pod x data) topology — intra-pod butterfly bits ride
    ICI, inter-pod bits ride DCN — and records the modeled step time three
    ways: per-class budgets (``plan.choose_class_bucket_bytes`` argmin per
    link class), the same topology forced onto one global 32 MiB budget
    (pre-plan behaviour), and the flat single-class model for reference.
    ``--check`` gates per-class <= single-budget: the per-class sweep must
    never lose to the global default it replaces.
    """
    from repro.configs import get_config
    from repro.core import plan as plan_mod
    from repro.models.registry import build_model

    cfg = get_config("transformer-wmt")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    payload = bucketing.tree_payload_bytes(shapes)
    S = grouping.default_group_size(P_cluster)
    n_data = P_cluster // n_pods
    topo = plan_mod.Topology.hierarchical(("data", "pod"), (n_data, n_pods),
                                          dcn_axes=("pod",))
    hier = plan_mod.modeled_wagma_step_seconds(payload, topo, S, tau=tau)
    single = plan_mod.modeled_wagma_step_seconds(
        payload, topo, S, tau=tau,
        bucket_bytes=bucketing.DEFAULT_BUCKET_BYTES)
    flat = plan_mod.modeled_wagma_step_seconds(
        payload, plan_mod.Topology.flat(("data", "pod"), (n_data, n_pods)),
        S, tau=tau)
    return {
        "config": cfg.name,
        "P": P_cluster, "S": S, "tau": tau, "n_pods": n_pods,
        "payload_bytes": payload,
        "topology": topo.describe(),
        "per_class": hier["per_class"],
        "per_class_budget_step_s": hier["step_s"],
        "single_budget_step_s": single["step_s"],
        "flat_topology_step_s": flat["step_s"],
        "per_class_budget_win": single["step_s"] / hier["step_s"],
    }


def modeled_fsdp_wmt(*, P_cluster: int = 64, n_pods: int = 4,
                     tau: int = 10) -> dict:
    """FSDP-within-pod model for the WMT transformer (DESIGN.md §10).

    Replicas inside a pod share weights sharded over the intra-pod (data)
    axis: persistent per-device param+opt memory ÷ pod size, pod-to-pod
    butterfly on shard slices (DCN wire ÷ pod size), plus the per-step
    all-gather/reduce-scatter overhead on ICI.  ``--check`` gates
    (a) memory ratio >= pod size and (b) the modeled sharded step within
    10% of (i.e. not slower than 1.1x) the replicated hierarchical step.
    """
    from repro.configs import get_config
    from repro.core import plan as plan_mod
    from repro.launch.costmodel import replica_memory_bytes
    from repro.models.registry import build_model

    cfg = get_config("transformer-wmt")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    payload = bucketing.tree_payload_bytes(shapes)
    n_data = P_cluster // n_pods
    topo = plan_mod.Topology.hierarchical(("data", "pod"), (n_data, n_pods),
                                          dcn_axes=("pod",))
    S_rep = grouping.default_group_size(P_cluster)
    S_eff = grouping.default_group_size(n_pods)
    replicated = plan_mod.modeled_wagma_step_seconds(payload, topo, S_rep,
                                                     tau=tau)
    fsdp = plan_mod.modeled_fsdp_step_seconds(payload, topo, S_eff,
                                              shard_axis="data", tau=tau)
    mem = replica_memory_bytes(payload, pod_size=n_data)
    return {
        "config": cfg.name,
        "P": P_cluster, "n_pods": n_pods, "pod_size": n_data,
        "S_replicated": S_rep, "S_pod_level": S_eff, "tau": tau,
        "payload_bytes": payload,
        "topology": topo.describe(),
        "per_class": fsdp["per_class"],
        "replicated_hier_step_s": replicated["step_s"],
        "fsdp_step_s": fsdp["step_s"],
        "gather_scatter_s": fsdp["gather_scatter_s"],
        "step_ratio": fsdp["step_s"] / replicated["step_s"],
        **mem,
    }


def modeled_streamed_fsdp(*, P_cluster: int = 64, n_pods: int = 4,
                          tau: int = 10) -> dict:
    """Layer-streamed FSDP model for the WMT transformer (DESIGN.md §11).

    The gather-all FSDP step (§10) pays the full-tree all-gather serially
    before the forward and pins the gathered tree through fwd/bwd; the
    streamed engine gathers span k+1 while span k computes and re-gathers
    in the backward, so per-step time is ``max(compute, gather)`` per span
    and peak transient memory is ~2 layer spans.  Span compute comes from
    the analytic train cost at the production chip's peak FLOP/s.
    ``--check`` gates (a) streamed peak gathered bytes < the full-tree
    gather and (b) streamed modeled step <= the gather-all step.
    """
    from repro.configs import SHAPES, get_config
    from repro.core import plan as plan_mod
    from repro.launch.costmodel import averaging_comm_cost, train_cost
    from repro.launch.mesh import V5E, chip_peaks
    from repro.models.registry import build_model

    cfg = get_config("transformer-wmt")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_leaves = len(jax.tree.leaves(shapes))
    payload = bucketing.tree_payload_bytes(shapes)
    n_data = P_cluster // n_pods
    topo = plan_mod.Topology.hierarchical(("data", "pod"), (n_data, n_pods),
                                          dcn_axes=("pod",))
    # one span per (encoder or decoder) layer; fwd compute per span from
    # the analytic cost model (flops_per_device = 4x fwd incl. remat)
    n_spans = cfg.n_layers + cfg.encoder_layers
    cm = train_cost(cfg, SHAPES["train_4k"], n_dp=P_cluster, n_model=1)
    span_fwd_s = cm.flops_per_device / 4.0 / n_spans / chip_peaks(V5E).flops
    rep = averaging_comm_cost(cfg, P=P_cluster,
                              S=grouping.default_group_size(P_cluster),
                              tau=tau, n_leaves=n_leaves,
                              payload_bytes=payload, topology=topo,
                              fsdp_shard_axis="data",
                              fsdp_streamed_spans=n_spans,
                              span_fwd_compute_s=span_fwd_s)
    return {
        "config": cfg.name,
        "P": P_cluster, "n_pods": n_pods, "pod_size": n_data,
        "tau": tau, "payload_bytes": payload, "n_spans": n_spans,
        "span_fwd_compute_s": span_fwd_s,
        "topology": topo.describe(),
        "peak_gathered_bytes_full": rep.peak_gathered_bytes,
        "peak_gathered_bytes_streamed": rep.peak_gathered_bytes_streamed,
        "peak_gathered_ratio": (rep.peak_gathered_bytes
                                / max(rep.peak_gathered_bytes_streamed, 1.0)),
        "streamed_step_s": rep.t_fsdp_streamed,
        "gather_all_step_s": rep.t_fsdp_gather_all,
        "streamed_win": rep.streamed_win,
        "fsdp_butterfly_step_s": rep.t_fsdp,
    }


def modeled_elastic_churn(*, P_cluster: int = 64, steps: int = 3000,
                          tau: int = 10, seed: int = 0) -> dict:
    """Elastic membership vs checkpoint-restart under preemption churn.

    Delegates to ``cluster_sim.churn_scenario`` (DESIGN.md §12): one
    Poisson preemption trace drives both recovery policies; elastic pays
    an in-place plan recompile + host-side state handoff per world
    change, restart pays the full job restart plus recomputation since
    the last periodic checkpoint.  ``--check`` gates (a) the elastic
    overhead fraction staying bounded and (b) elastic goodput beating
    restart goodput.
    """
    from cluster_sim import churn_scenario
    return churn_scenario(P_cluster, steps=steps, tau=tau, seed=seed)


def modeled_degraded_mode(*, P_cluster: int = 64, steps: int = 600,
                          tau: int = 10, seed: int = 0) -> dict:
    """Degraded-mode rounds vs wait-for-all under the §V-B 320 ms trace.

    Delegates to ``cluster_sim.degraded_mode_scenario`` (DESIGN.md §13):
    the same seeded `FaultSchedule` the chaos tests replay delays two
    workers per step by 320 ms; wait-for-all eats the full delay every
    round, degraded mode waits only the collective deadline and charges
    the late partner one round of staleness, repaid at the tau-sync.
    ``--check`` (CHECK-CHAOS) gates degraded goodput beating wait-for-all
    with the staleness bound intact.
    """
    from cluster_sim import degraded_mode_scenario
    return degraded_mode_scenario(P_cluster, steps=steps, tau=tau,
                                  seed=seed)


def live_mesh_bench(args) -> dict:
    """Wall-clock + launch-count measurement on the 8-device CPU mesh."""
    n_dp, S = 8, args.S
    mesh = make_mesh((n_dp,), ("data",))
    names, sizes = ga.dp_axis_layout(("data",), {"data": n_dp}, ("data",))
    rng = np.random.default_rng(0)
    tree = transformer_like_tree(rng, n_dp, args.layers, args.d)

    local = jax.tree.map(lambda a: a[:1], tree)
    n_leaves = len(jax.tree.leaves(tree))
    bucket_bytes = args.bucket_mb * 1024 * 1024
    layout = bucketing.layout_for(local, max_bucket_bytes=bucket_bytes)
    stages = grouping.ilog2(S)
    payload = bucketing.tree_payload_bytes(local)

    variants = {
        "per_leaf": dict(fused=False),
        "bucketed_jnp": dict(fused=True, use_pallas=False, overlap=False),
        "bucketed_pallas": dict(fused=True, use_pallas=True, overlap=False),
        "overlapped_pallas": dict(fused=True, use_pallas=True, overlap=True),
    }
    print(f"tree: {n_leaves} leaves, {payload / 1e6:.1f} MB/replica; "
          f"S={S} ({stages} butterfly stages); "
          f"layout: {layout.n_buckets} buckets {layout.describe()}")

    from repro.core import plan as plan_mod
    topo = plan_mod.Topology.flat(names, sizes)
    results = {}
    for name, kw in variants.items():
        plan = plan_mod.compile_plan(
            topo, jax.tree.map(lambda a: a[0], tree),
            plan_mod.AveragingConfig(group_size=S, average_dtype="float32",
                                     bucket_bytes=bucket_bytes, **kw))
        f = jax.jit(compat.shard_map(
            lambda tr, plan=plan: plan.average_offset(tr, 0),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            axis_names={"data"}))
        n_pp = count_ppermutes(jax.make_jaxpr(f)(tree).jaxpr)
        dt = bench(f, tree, args.iters)
        results[name] = {"ppermutes_per_step": n_pp, "wall_s": dt}
        print(f"{name:18s} ppermutes/step {n_pp:5d}   wall {dt * 1e3:8.2f} ms")

    n_pp_leaf = results["per_leaf"]["ppermutes_per_step"]
    n_pp_fused = results["bucketed_pallas"]["ppermutes_per_step"]
    assert n_pp_leaf == n_leaves * stages
    assert n_pp_fused == layout.n_buckets * stages
    # the wavefront schedule reorders launches but never adds any
    assert results["overlapped_pallas"]["ppermutes_per_step"] == n_pp_fused
    print(f"ppermute launches: {n_leaves} x log2(S) -> "
          f"{layout.n_buckets} x log2(S)  "
          f"({n_pp_leaf} -> {n_pp_fused}, {n_pp_leaf / n_pp_fused:.1f}x fewer)")
    return {"n_leaves": n_leaves, "payload_bytes": payload,
            "S": S, "n_buckets": layout.n_buckets, "variants": results}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--S", type=int, default=4)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--bucket-mb", type=int, default=32)
    ap.add_argument("--check", action="store_true",
                    help="model-only: assert overlapped < serial for "
                         "transformer_wmt and write the JSON")
    ap.add_argument("--out", default=OUT_JSON)
    args = ap.parse_args()

    report = {"modeled_transformer_wmt": modeled_transformer_wmt(),
              "modeled_hierarchical_wmt": modeled_hierarchical_wmt(),
              "modeled_fsdp_wmt": modeled_fsdp_wmt(),
              "modeled_streamed_fsdp": modeled_streamed_fsdp(),
              "modeled_elastic_churn": modeled_elastic_churn(),
              "modeled_degraded_mode": modeled_degraded_mode()}
    m = report["modeled_transformer_wmt"]
    print(f"[model] transformer_wmt @ P={m['P']} S={m['S']}: "
          f"serial {m['serial']['modeled_step_s'] * 1e3:.3f} ms/step "
          f"({m['serial']['n_buckets']} x 32MiB buckets), overlapped "
          f"{m['overlapped']['modeled_step_s'] * 1e3:.3f} ms/step "
          f"({m['overlapped']['n_buckets']} x "
          f"{m['chosen_bucket_bytes'] // 2**20}MiB buckets, "
          f"{m['overlap_win']:.3f}x)")
    h = report["modeled_hierarchical_wmt"]
    budgets = {k: f"{v['bucket_bytes'] // 2**20}MiB"
               for k, v in h["per_class"].items()}
    print(f"[model] hierarchical (pod x data) @ P={h['P']} "
          f"pods={h['n_pods']}: per-class budgets {budgets} -> "
          f"{h['per_class_budget_step_s'] * 1e3:.3f} ms/step vs single "
          f"32MiB {h['single_budget_step_s'] * 1e3:.3f} ms/step "
          f"({h['per_class_budget_win']:.4f}x), flat-topology ref "
          f"{h['flat_topology_step_s'] * 1e3:.3f} ms/step")
    fd = report["modeled_fsdp_wmt"]
    print(f"[model] fsdp-within-pod @ P={fd['P']} pod_size="
          f"{fd['pod_size']}: mem/dev "
          f"{fd['mem_replicated'] / 2**20:.0f} -> "
          f"{fd['mem_fsdp_within_pod'] / 2**20:.0f} MiB "
          f"({fd['mem_ratio']:.1f}x), step "
          f"{fd['fsdp_step_s'] * 1e3:.3f} ms (incl. AG/RS "
          f"{fd['gather_scatter_s'] * 1e3:.3f} ms) vs replicated hier "
          f"{fd['replicated_hier_step_s'] * 1e3:.3f} ms "
          f"({fd['step_ratio']:.3f}x)")

    st = report["modeled_streamed_fsdp"]
    print(f"[model] streamed fsdp @ {st['n_spans']} spans: peak gathered "
          f"{st['peak_gathered_bytes_full'] / 2**20:.1f} -> "
          f"{st['peak_gathered_bytes_streamed'] / 2**20:.1f} MiB "
          f"({st['peak_gathered_ratio']:.1f}x), step "
          f"{st['gather_all_step_s'] * 1e3:.3f} (gather-all) -> "
          f"{st['streamed_step_s'] * 1e3:.3f} ms (streamed, "
          f"{st['streamed_win']:.3f}x)")

    el = report["modeled_elastic_churn"]
    print(f"[model] elastic churn @ P={el['P']} over {el['steps']} steps: "
          f"{el['n_preemptions']} preemptions -> {el['n_shrinks']} shrinks "
          f"+ {el['n_regrows']} regrows; overhead elastic "
          f"{el['elastic_overhead_frac']:.1%} vs restart "
          f"{el['restart_overhead_frac']:.1%}, goodput "
          f"{el['goodput_speedup']:.2f}x")

    dg = report["modeled_degraded_mode"]
    print(f"[model] degraded mode @ P={dg['P']} (§V-B trace "
          f"{dg['schedule_fingerprint']}): wait-for-all "
          f"{dg['waitall_step_s'] * 1e3:.1f} ms/step vs degraded "
          f"{dg['degraded_step_s'] * 1e3:.1f} ms/step "
          f"({dg['goodput_speedup']:.2f}x), "
          f"{dg['skipped_contributions']} skipped contributions, peak "
          f"staleness {dg['peak_staleness_age']} <= "
          f"{dg['staleness_bound']}")

    if not args.check:
        report["live_8dev_cpu"] = live_mesh_bench(args)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")

    ok = (m["overlapped"]["modeled_step_s"] < m["serial"]["modeled_step_s"])
    # hierarchical gate: per-class budgets must never lose to the single
    # global budget on the same 2-class topology, and the per-class cost
    # model must actually pick distinct budgets per link class
    ok_hier = (h["per_class_budget_step_s"] <= h["single_budget_step_s"]
               and len({v["bucket_bytes"] for v in h["per_class"].values()})
               == len(h["per_class"]))
    # fsdp gate: persistent per-device param+opt memory must divide by at
    # least the pod size, and the sharded step model must stay within 10%
    # of the replicated hierarchical step it replaces
    ok_fsdp = (fd["mem_ratio"] >= fd["pod_size"]
               and fd["step_ratio"] <= 1.10)
    # streamed gate: the layer-streamed engine must strictly shrink the
    # transient gathered footprint and never lose to gather-all on time
    ok_stream = (st["peak_gathered_bytes_streamed"]
                 < st["peak_gathered_bytes_full"]
                 and st["streamed_step_s"] <= st["gather_all_step_s"])
    # elastic gate: churn recovery must stay a bounded tax (recompile +
    # handoff under 10% of wall clock) and strictly beat the
    # checkpoint-restart baseline on goodput
    ok_elastic = (el["elastic_overhead_frac"] < 0.10
                  and el["goodput_speedup"] > 1.0
                  and el["n_world_changes"] >= 2)
    # chaos gate: under the paper's §V-B straggler trace, degraded-mode
    # rounds (deadline-bounded waits, staleness charged and repaid at the
    # tau-sync) must beat the wait-for-all baseline without ever
    # exceeding max_staleness_bound(tau)
    ok_chaos = (dg["goodput_speedup"] > 1.0 and dg["staleness_bounded"]
                and dg["skipped_contributions"] > 0)
    if args.check:
        print("CHECK", "PASS" if ok else "FAIL",
              f"(overlapped {m['overlapped']['modeled_step_s']:.6e} "
              f"< serial {m['serial']['modeled_step_s']:.6e})")
        print("CHECK-HIER", "PASS" if ok_hier else "FAIL",
              f"(per-class {h['per_class_budget_step_s']:.6e} <= single "
              f"{h['single_budget_step_s']:.6e}, budgets {budgets})")
        print("CHECK-FSDP", "PASS" if ok_fsdp else "FAIL",
              f"(mem ratio {fd['mem_ratio']:.1f} >= pod "
              f"{fd['pod_size']}, step ratio {fd['step_ratio']:.3f} "
              f"<= 1.10)")
        print("CHECK-STREAM", "PASS" if ok_stream else "FAIL",
              f"(peak gathered {st['peak_gathered_bytes_streamed']:.3e} < "
              f"full {st['peak_gathered_bytes_full']:.3e}, streamed "
              f"{st['streamed_step_s']:.6e} <= gather-all "
              f"{st['gather_all_step_s']:.6e})")
        print("CHECK-ELASTIC", "PASS" if ok_elastic else "FAIL",
              f"(overhead {el['elastic_overhead_frac']:.3f} < 0.10, "
              f"goodput {el['goodput_speedup']:.2f}x > 1, "
              f"{el['n_world_changes']} world changes)")
        print("CHECK-CHAOS", "PASS" if ok_chaos else "FAIL",
              f"(degraded/wait-for-all goodput "
              f"{dg['goodput_speedup']:.2f}x > 1, peak staleness "
              f"{dg['peak_staleness_age']} <= {dg['staleness_bound']}, "
              f"{dg['skipped_contributions']} skipped)")
        return 0 if (ok and ok_hier and ok_fsdp and ok_stream
                     and ok_elastic and ok_chaos) else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
