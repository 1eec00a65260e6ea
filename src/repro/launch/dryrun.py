"""Multi-pod dry-run: .lower().compile() every (arch x input-shape x mesh).

The forced host-device count is set by ``main()`` (CLI entry) BEFORE the
first jax backend init — jax locks the device count there, not at import
— via ``os.environ.setdefault`` so a caller (e.g. the CI fsdp smoke) can
force a smaller count.  Importing this module has NO side effects: tools
that import it for :func:`resolve_config` / :func:`lower_pair` keep their
own device view (tests/test_launch_import.py pins this).

For each pair this lowers the appropriate step:
    train_4k              -> WAGMA train_step (group-averaging variant)
    prefill_32k           -> prefill (forward + KV capture)
    decode_32k, long_500k -> serve_step (1 token vs seq_len cache)

and records memory_analysis / cost_analysis / loop-aware collective bytes —
plus, for train steps, the compiled-plan launch cross-check (expected
ppermutes per link class from the AveragingPlan vs collective-permutes
found in the compiled HLO, classified per mesh axis) and the plan's
human-readable summary (stages, link class, bucket count, budget per
class) — to experiments/dryrun/<arch>__<shape>__<mesh>[__variant].json.
``--hierarchical`` compiles the pod-aware 2-link-class topology.
``--sharding fsdp`` compiles the FSDP-within-pod ReplicaState step
(DESIGN.md §10) and FAILS if any parameter all-gather / gradient
reduce-scatter leaks off the intra-pod shard axis onto a DCN axis
(``hlo_analysis.collective_axis_counts``); ``--smoke`` + ``--mesh-shape``
shrink the sweep to the CI-sized 8-device smoke (scripts/ci.sh).

long_500k rules (DESIGN.md §5): native for xlstm/recurrentgemma/gemma3;
explicit `swa` sliding-window variant for the pure full-attention archs;
skipped for whisper (enc-dec 448-position decoder semantics).
"""

import argparse
import json
import os
import time
import traceback

import jax


def _force_host_device_count(n: int = 512) -> None:
    """Pin the forced host-device count for the dry-run sweep.

    Must run before the first jax backend init (the first ``jax.devices``
    /first compilation — importing jax does not init).  ``setdefault`` so
    an explicit caller-supplied XLA_FLAGS (the CI smokes) wins.  Called
    from ``main()`` only: merely importing this module must never pin the
    device count of the embedding process.
    """
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")

from repro.configs import SHAPES, arch_names, get_config
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import specs as specs_lib
from repro.launch.hlo_analysis import collective_summary
from repro.launch.costmodel import cost_for, param_count
from repro.models.registry import build_model
from repro import compat

LONG_NATIVE = {"xlstm-350m", "recurrentgemma-2b", "gemma3-12b"}
LONG_SKIP = {"whisper-medium"}
SWA_WINDOW = 8192


def bucket_collective_summary(averager, local_params, colls: dict,
                              mesh=None, hlo_text: str = None) -> dict:
    """Compiled-plan launch accounting, cross-checked against HLO per class.

    Asks the averager's :class:`~repro.core.plan.AveragingPlan` for the
    expected ``ppermute`` launch count of one averaging step — per link
    class (one collective per bucket per butterfly/gossip round on that
    class's own budget; the overlapped scheduler reorders launches but
    never adds any) — and compares it with the ``collective-permute`` count
    the loop-aware HLO parser found in the compiled step.  With ``mesh``
    and ``hlo_text`` given, each compiled permute is additionally
    classified by the mesh axis it moves (``hlo_analysis.
    permute_axis_counts``) so the cross-check runs per link class, not
    just in aggregate.  ``match`` is exact on dp-only meshes; with a model
    axis GSPMD may add its own permutes, so ``extra_in_hlo`` reports the
    difference instead of failing.

    Also emits ``plan_summary`` — the plan's human-readable compilation
    record (stages, link class, bucket count, budget per class).
    """
    from repro.core import bucketing, grouping

    n_leaves = len(jax.tree_util.tree_leaves(local_params))
    name = getattr(averager, "name", "?")
    plan = averager.plan_for(local_params)
    fused = plan.cfg.fused

    if name == "wagma":
        offset = plan.offsets[0]            # dryrun compiles phase 0
        per_class = plan.per_class_expected(offset)
        expected = plan.expected_ppermutes(offset)
        mix_budget = None
    else:
        # (bit, permutes-on-that-bit) per phase-0 mix round: D-PSGD sends to
        # both ring neighbours on the minor axis; SGP one permute per
        # rotating neighbour bit; AD-PSGD one pairwise exchange on bit 0
        bit_rounds = {"dpsgd": ((0, 2),), "adpsgd": ((0, 1),),
                      "sgp": tuple((b, 1) for b in range(
                          getattr(averager, "neighbours", 1)))
                      }.get(name, ())
        bits = tuple(b for b, _ in bit_rounds)
        mix_budget = plan.mix_bucket_bytes(bits)
        layout = bucketing.layout_for(local_params,
                                      max_bucket_bytes=mix_budget)
        units = layout.n_buckets if fused else n_leaves
        per_class = {}
        for bit, rounds in bit_rounds:
            link = plan.topology.link_classes[plan.topology.class_of_bit(bit)]
            ent = per_class.setdefault(link.name, {
                "stages": 0, "ppermutes": 0, "bucket_bytes": mix_budget,
                "n_buckets": units, "axes": ()})
            ent["stages"] += rounds
            ent["ppermutes"] += rounds * units
            ent["axes"] = tuple(dict.fromkeys(
                ent["axes"] + (plan.topology.axis_of_bit(bit),)))
        expected = sum(e["ppermutes"] for e in per_class.values())

    hlo_pp = int(colls.get("counts_by_kind", {}).get("collective-permute", 0))
    out = {
        "averager": name,
        "topology": plan.topology.describe(),
        "n_leaves": n_leaves,
        "class_bucket_bytes": {
            plan.topology.link_classes[ci].name: bb
            for ci, bb in plan.class_bucket_bytes.items()},
        "per_class_expected": per_class,
        "expected_ppermutes": expected,
        "hlo_ppermutes": hlo_pp,
        "match": hlo_pp == expected,
        "extra_in_hlo": hlo_pp - expected,
        "plan_summary": plan.describe(),
        # legacy aggregate field kept for existing consumers
        "n_buckets": max((v["n_buckets"] for v in per_class.values()),
                         default=0),
    }
    if mesh is not None and hlo_text is not None:
        from repro.launch.hlo_analysis import permute_axis_counts
        axis_counts = permute_axis_counts(
            hlo_text, tuple(mesh.axis_names),
            tuple(mesh.shape[a] for a in mesh.axis_names))
        by_class = {}
        known = set()
        for ci in plan.topology.classes_in_use():
            cls_name = plan.topology.link_classes[ci].name
            axes = [a for a, c in zip(plan.topology.axis_names,
                                      plan.topology.axis_class) if c == ci]
            by_class[cls_name] = sum(axis_counts.get(a, 0) for a in axes)
            known.update(axes)
        out["hlo_ppermutes_by_axis"] = axis_counts
        out["hlo_ppermutes_by_class"] = by_class
        out["hlo_ppermutes_other_axes"] = sum(
            n for a, n in axis_counts.items() if a not in known)
        out["per_class_match"] = {
            cls: by_class.get(cls, 0) == ent["ppermutes"]
            for cls, ent in per_class.items()}
    return out


def resolve_config(arch: str, shape_name: str, smoke: bool = False):
    """Returns (cfg, variant_tag) or (None, reason) for documented skips.

    ``smoke`` picks the reduced config BEFORE the long_500k variant logic
    so the sliding-window (swa) patch still applies to the smoke config.
    """
    cfg = get_config(arch, smoke=smoke)
    if shape_name != "long_500k":
        return cfg, ""
    if arch in LONG_SKIP:
        return None, "skip: enc-dec decoder has no 500k-context analogue"
    if arch in LONG_NATIVE:
        return cfg, ""
    return cfg.with_sliding_window(SWA_WINDOW), "swa"


def lower_pair(arch: str, shape_name: str, mesh, *, averager: str = "wagma",
               group_size=None, donate: bool = True,
               average_dtype: str = "float32", microbatch=None,
               cfg_overrides: dict = None, hierarchical: bool = False,
               sharding: str = "replicated", streamed: bool = False,
               smoke: bool = False):
    """Build + lower + compile one (arch, shape) on the given mesh.

    Tuning knobs for the §Perf hillclimb: ``mesh`` may be any logical
    reshaping of the production chips (e.g. (256,1) for a TP-free small
    model), ``average_dtype`` sets the butterfly payload precision,
    ``microbatch`` enables gradient accumulation, ``cfg_overrides`` patches
    the ModelConfig (e.g. attention block sizes, moe_chunks).
    """
    cfg, variant = resolve_config(arch, shape_name, smoke=smoke)
    if cfg is None:
        return {"status": "skipped", "reason": variant}
    if cfg_overrides:
        cfg = cfg.variant(**cfg_overrides)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    av = None
    t0 = time.time()

    with compat.set_mesh(mesh):
        if shape.kind == "train":
            from repro.core.baselines import make_averager
            from repro.core.group_allreduce import dp_axis_layout
            from repro.launch.train import resolve_sharding
            from repro.optim import sgd
            from repro.train import build_train_step, init_replica_state

            names, sizes = dp_axis_layout(
                mesh.axis_names, dict(mesh.shape),
                tuple(a for a in mesh.axis_names if a in ("pod", "data")))
            policy = resolve_sharding(sharding, names, streamed=streamed)
            kw = {"sharding": policy}
            if averager == "wagma":
                kw["average_dtype"] = average_dtype
                if group_size:
                    kw["group_size"] = group_size
            if hierarchical:
                from repro.core.plan import Topology
                kw["topology"] = Topology.hierarchical(names, sizes)
            av = make_averager(averager, names, sizes, **kw)
            opt = sgd(0.1, momentum=0.9)
            state_sds = init_replica_state(model, opt, av, mesh,
                                           jax.random.PRNGKey(0),
                                           abstract=True)
            params_sds = state_sds.params
            batch = specs_lib.batch_specs(cfg, shape, mesh)
            step = build_train_step(model, opt, av, mesh, phase=0, sync=False,
                                    microbatch=microbatch)
            lowered = step.lower(state_sds, batch)
        elif shape.kind == "prefill":
            params_sds = specs_lib.serve_params_specs(cfg, mesh)
            batch = specs_lib.batch_specs(cfg, shape, mesh)

            def prefill_fn(params, b):
                return model.prefill(params, b, shape.seq_len)

            lowered = jax.jit(prefill_fn).lower(params_sds, batch)
        else:  # decode
            params_sds, caches_sds, token, pos = specs_lib.decode_specs(
                cfg, shape, mesh)

            def serve_step(params, caches, tok, pos):
                import jax.numpy as jnp
                logits, caches = model.decode_step(params, caches, tok, pos)
                nxt = jnp.argmax(logits[:, -1, :], -1).astype(tok.dtype)[:, None]
                return nxt, caches

            lowered = jax.jit(serve_step,
                              donate_argnums=(1,) if donate else ()
                              ).lower(params_sds, caches_sds, token, pos)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compat.cost_analysis(compiled)
    hlo = compiled.as_text()
    halve = ["all-reduce"]
    if average_dtype == "bfloat16":
        halve.append("collective-permute")   # butterfly payload is bf16
    colls = collective_summary(hlo, halve_kinds=tuple(halve))
    bucket_colls = None
    if av is not None:
        if av.sharding.is_sharded and av.sharding.streamed:
            # streamed plans compile over the layered tree (layer-aware
            # shard layout, DESIGN.md §11)
            from repro.train.train_step import _layered_shapes
            local_params = _layered_shapes(model)
        elif av.sharding.is_sharded:
            # the sharded plan was compiled from the full model tree at
            # state-init time; hand the summary the same structure
            local_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        else:
            local_params = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                params_sds,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        bucket_colls = bucket_collective_summary(av, local_params, colls,
                                                 mesh=mesh, hlo_text=hlo)
        if av.sharding.is_sharded:
            # FSDP invariant: parameter all-gathers / gradient
            # reduce-scatters ride the intra-pod shard axis ONLY —
            # classify every grouped collective by mesh axis and flag
            # any landing on another dp axis (a DCN leak)
            from repro.launch.hlo_analysis import collective_axis_counts
            ag = collective_axis_counts(
                hlo, tuple(mesh.axis_names),
                tuple(mesh.shape[a] for a in mesh.axis_names))
            dp_axes = {a for a in mesh.axis_names if a in ("pod", "data")}
            shard_ax = av.sharding.shard_axis
            # a "mixed" classification (replica groups spanning several
            # mesh axes — e.g. a full-dp pod x data gather) is exactly the
            # kind of leak this gate exists to catch, so it counts too
            leaks = {
                kind: {a: n for a, n in ent.items()
                       if a == "mixed" or (a in dp_axes and a != shard_ax)}
                for kind, ent in ag.items()}
            leaks = {k: v for k, v in leaks.items() if v}
            # the gate must not pass vacuously: if the parser classified
            # ZERO gathers onto the shard axis (e.g. an XLA version
            # switches to iota-form replica_groups the regex cannot read),
            # the invariant is untested and the smoke must fail loudly
            on_shard = (ag.get("all-gather", {}).get(shard_ax, 0)
                        + ag.get("reduce-scatter", {}).get(shard_ax, 0))
            if on_shard == 0:
                leaks["unparsed"] = {
                    "reason": "no all-gather/reduce-scatter classified "
                              "onto the shard axis — parser saw nothing"}
            bucket_colls["gather_scatter_by_axis"] = ag
            bucket_colls["fsdp_gather_leaks"] = leaks
            bucket_colls["fsdp_gathers_intra_pod_only"] = not leaks
        if av.sharding.is_sharded and av.sharding.streamed:
            # streamed invariants (DESIGN.md §11), cross-checked in HLO:
            # (a) no single all-gather exceeds one layer-span bucket (a
            #     gather-all regression reappears as a full-tree-sized
            #     gather), (b) the all-gather count on the shard axis
            #     equals the schedule's fwd+bwd expectation (a CSE'd
            #     backward re-gather silently pins forward buffers and
            #     shows up as a shortfall), (c) the schedule's own peak
            #     stays under the two-span bound vs the full tree
            from repro.core import streaming
            from repro.launch.hlo_analysis import grouped_collective_details
            plan = av.plan_for(local_params)
            lay = plan.shard_layout
            # XLA-CPU widens bf16 collectives to f32 (see
            # hlo_analysis.collective_summary), so the per-op bound uses
            # the widened itemsize; on TPU the payload stays narrow
            max_bucket = max(
                (s * max(d.itemsize, 4) for s, d in zip(lay.bucket_sizes,
                                                        lay.bucket_dtypes)),
                default=0)
            details = grouped_collective_details(
                hlo, tuple(mesh.axis_names),
                tuple(mesh.shape[a] for a in mesh.axis_names))
            shard_ax = av.sharding.shard_axis
            ags = [d for d in details
                   if d["kind"] == "all-gather" and d["axis"] == shard_ax]
            expected_ags = streaming.expected_stream_gathers(plan)
            oversize = [d for d in ags if d["tensor_bytes"] > max_bucket]
            stream_report = {
                "expected_gathers": expected_ags,
                "hlo_gathers_on_shard_axis": len(ags),
                "gathers_match": len(ags) == expected_ags,
                "max_gather_bytes": max(
                    (d["tensor_bytes"] for d in ags), default=0),
                "max_span_bucket_bytes": max_bucket,
                "oversize_gathers": len(oversize),
                "peak_gathered_bytes": plan.stream_peak_gathered_bytes(),
                "full_gathered_bytes": plan.full_gathered_bytes(),
                "layer_bucket_map": lay.describe_groups(),
            }
            stream_report["ok"] = (stream_report["gathers_match"]
                                   and not oversize
                                   and stream_report["peak_gathered_bytes"]
                                   < stream_report["full_gathered_bytes"])
            bucket_colls["streamed"] = stream_report
        print("  " + bucket_colls["plan_summary"].replace("\n", "\n  "),
              flush=True)
    n_dp = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            n_dp *= mesh.shape[a]
    n_model = mesh.shape.get("model", 1)
    cm = cost_for(cfg, shape, shape.kind, n_dp=n_dp, n_model=n_model)
    total_p, active_p = param_count(cfg)

    return {
        "status": "ok",
        "arch": arch, "shape": shape_name, "variant": variant,
        "averager": averager if shape.kind == "train" else None,
        "mesh": dict(mesh.shape),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "per_device_total": (ma.argument_size_in_bytes
                                 + ma.temp_size_in_bytes
                                 + ma.output_size_in_bytes
                                 - ma.alias_size_in_bytes),
        },
        "xla_cost_analysis": {
            "flops": ca.get("flops"),
            "bytes_accessed": ca.get("bytes accessed"),
            "note": "scan bodies counted once by XLA; see analytic model",
        },
        "collectives": colls,
        "bucket_collectives": bucket_colls,
        "analytic": {
            "flops_per_device": cm.flops_per_device,
            "hbm_bytes_per_device": cm.hbm_bytes_per_device,
            "model_flops_per_device": cm.model_flops,
            "params_total": total_p,
            "params_active": active_p,
        },
        "hlo_bytes": len(hlo),
    }


def main():
    _force_host_device_count()          # before any jax device/compile use
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--averager", default="wagma")
    ap.add_argument("--group-size", type=int, default=None)
    ap.add_argument("--hierarchical", action="store_true",
                    help="pod-aware topology: pod axis rides DCN, data "
                         "rides ICI, per-class bucket budgets")
    ap.add_argument("--sharding", default="replicated",
                    choices=["replicated", "fsdp"],
                    help="fsdp: FSDP-within-pod sharded replicas "
                         "(DESIGN.md §10); the run fails if any parameter "
                         "all-gather leaks off the intra-pod shard axis")
    ap.add_argument("--streamed", action="store_true",
                    help="with --sharding fsdp: layer-streamed execution "
                         "engine (DESIGN.md §11) — the run fails if any "
                         "gather leaves the intra-pod axis, any single "
                         "all-gather exceeds one layer-span bucket, or the "
                         "shard-axis gather count mismatches the streamed "
                         "schedule (CSE'd backward re-gathers)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced smoke configs (CI-sized compile)")
    ap.add_argument("--mesh-shape", default=None,
                    help="comma ints overriding the production mesh: "
                         "'pod,data,model' (3 values) or 'data,model' (2); "
                         "product must equal the forced host-device count")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    pairs = []
    archs = arch_names() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                pairs.append((arch, shape, mp))

    results = []
    for arch, shape, mp in pairs:
        if args.mesh_shape:
            dims = tuple(int(x) for x in args.mesh_shape.split(","))
            axes = ("pod", "data", "model") if len(dims) == 3 \
                else ("data", "model")
            mesh = mesh_lib.make_mesh(dims, axes)
            mesh_tag = "x".join(str(d) for d in dims)
        else:
            mesh = mesh_lib.make_production_mesh(multi_pod=mp)
            mesh_tag = "2x16x16" if mp else "16x16"
        tag = f"{arch}__{shape}__{mesh_tag}"
        if args.averager != "wagma":
            tag += f"__{args.averager}"
        if args.hierarchical:
            tag += "__hier"
        if args.sharding != "replicated":
            tag += f"__{args.sharding}"
        if args.streamed:
            tag += "__streamed"
        print(f"=== {tag} ===", flush=True)
        try:
            res = lower_pair(arch, shape, mesh, averager=args.averager,
                             group_size=args.group_size,
                             hierarchical=args.hierarchical,
                             sharding=args.sharding, streamed=args.streamed,
                             smoke=args.smoke)
            if res.get("bucket_collectives") and \
                    res["bucket_collectives"].get(
                        "fsdp_gathers_intra_pod_only") is False:
                res["status"] = "error"
                res["error"] = ("fsdp all-gather leak: " + str(
                    res["bucket_collectives"]["fsdp_gather_leaks"]))
            stream_rep = (res.get("bucket_collectives") or {}).get("streamed")
            if stream_rep and not stream_rep["ok"]:
                res["status"] = "error"
                res["error"] = ("streamed invariant violated: "
                                + str(stream_rep))
        except Exception as e:
            res = {"status": "error", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            print("  ERROR:", res["error"], flush=True)
        else:
            if res["status"] == "ok":
                mem = res["memory"]["per_device_total"] / 2**30
                cw = res["collectives"]["total_wire_bytes"] / 2**20
                print(f"  ok: compile={res['compile_s']}s "
                      f"mem/dev={mem:.2f}GiB coll={cw:.1f}MiB "
                      f"flops/dev={res['analytic']['flops_per_device']:.3e}",
                      flush=True)
            else:
                print(f"  {res['status']}: "
                      f"{res.get('reason', res.get('error', ''))}",
                      flush=True)
        res["tag"] = tag
        results.append(res)
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(res, f, indent=2, default=str)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\nDRY-RUN SUMMARY: ok={n_ok} skipped={n_skip} error={n_err} "
          f"of {len(results)}")
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump([{k: r.get(k) for k in
                    ("tag", "status", "compile_s", "memory", "collectives",
                     "bucket_collectives", "analytic", "error")}
                   for r in results], f, indent=2, default=str)
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
