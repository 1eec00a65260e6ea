"""ReplicaState & ShardingPolicy differentials (DESIGN.md §10).

Host-side tests pin the pure pieces: policy validation, the shard-aligned
bucket layout, plan-cache keying on the policy, effective-rank mapping,
host-side cross-policy state conversion, and the FSDP memory/step cost
model.  Subprocess tests pin the sharded execution on the 8-device CPU
mesh: ``fsdp_within_pod`` plan execution must be bit-identical to the
replicated plan and the stacked simulator on EVERY phase offset (flat and
hierarchical topologies), shard ownership must round-trip, per-class
launch counts must be unchanged by sharding, the sharded train step's
all-gathers must ride the intra-pod axis only, and a checkpoint written
by a sharded run must restore into a replicated run (and vice versa) with
``consolidate`` agreeing bit-for-bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from subproc import run_sub as _run_sub

from repro.core import bucketing, grouping
from repro.core import plan as plan_mod
from repro.core import replica
from repro.core.plan import AveragingConfig, LinkClass, Topology, compile_plan
from repro.core.replica import (ReplicaState, ShardingPolicy,
                                effective_rank_map)
from repro.optim import sgd


# ---------------------------------------------------------------------------
# Policy + state basics
# ---------------------------------------------------------------------------

def test_sharding_policy_validation():
    assert ShardingPolicy.replicated().kind == "replicated"
    pol = ShardingPolicy.fsdp_within_pod("data")
    assert pol.is_sharded and pol.shard_axis == "data"
    with pytest.raises(ValueError):
        ShardingPolicy("zero3")
    with pytest.raises(ValueError):
        ShardingPolicy("fsdp_within_pod")          # no shard axis
    with pytest.raises(ValueError):
        ShardingPolicy("replicated", "data")       # spurious shard axis


def test_replica_state_is_a_pytree():
    params = {"w": jnp.arange(4.0)}
    opt = sgd(0.1).init(params)
    st = ReplicaState.create(params, opt, step=3, phase=1)
    leaves, treedef = jax.tree_util.tree_flatten(st)
    st2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert int(st2.step) == 3 and int(st2.phase) == 1
    bumped = jax.jit(lambda s: ReplicaState(s.params, s.opt_state,
                                            s.step + 1, s.phase))(st)
    assert int(bumped.step) == 4


# ---------------------------------------------------------------------------
# Sharded plan compilation
# ---------------------------------------------------------------------------

TREE = {"emb": jax.ShapeDtypeStruct((33, 70), jnp.float32),
        "w": jax.ShapeDtypeStruct((1300,), jnp.float32),
        "h": jax.ShapeDtypeStruct((300,), jnp.bfloat16),
        "e": jax.ShapeDtypeStruct((0, 4), jnp.float32)}
FSDP = ShardingPolicy.fsdp_within_pod("data")


def test_shard_layout_alignment_and_struct():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    plan = compile_plan(topo, TREE, AveragingConfig(group_size=2,
                                                    bucket_bytes=4096), FSDP)
    k = plan.shard_size
    assert k == 4 and plan.P_eff == 2
    lay = plan.shard_layout
    for size in lay.bucket_sizes:
        assert size % (k * 128) == 0, "buckets must split into lane-aligned shards"
    for sds, size, dt in zip(plan.shard_struct(), lay.bucket_sizes,
                             lay.bucket_dtypes):
        assert sds.shape == (size // k,) and sds.dtype == dt
    # storage dtypes survive (bf16 stays bf16 between averaging steps)
    assert np.dtype(jnp.bfloat16) in set(lay.bucket_dtypes)


def test_plan_cache_keyed_on_sharding_and_shard_struct_registry():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    cfg = AveragingConfig(group_size=2)
    p_rep = compile_plan(topo, TREE, cfg)
    p_fsdp = compile_plan(topo, TREE, cfg, FSDP)
    assert p_rep is not p_fsdp
    assert compile_plan(topo, TREE, cfg, FSDP) is p_fsdp
    # the shard-buffer structure resolves back to the same plan (the train
    # step holds shards, not the full tree)
    assert compile_plan(topo, p_fsdp.shard_struct(), cfg, FSDP) is p_fsdp


def test_fsdp_validation():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    with pytest.raises(ValueError, match="bottleneck"):
        compile_plan(topo, TREE, AveragingConfig(group_size=2),
                     ShardingPolicy.fsdp_within_pod("pod"))
    with pytest.raises(ValueError, match="not a dp axis"):
        compile_plan(topo, TREE, AveragingConfig(group_size=2),
                     ShardingPolicy.fsdp_within_pod("model"))
    with pytest.raises(ValueError):
        Topology.flat(("data",), (8,)).drop_axis("data")
    # group size is bounded by the logical (pod) world, not the dp world
    with pytest.raises(ValueError, match="replica world"):
        compile_plan(topo, TREE, AveragingConfig(group_size=4), FSDP)


def test_effective_rank_map():
    # minor-to-major (data=4, pod=2); dp rank = pod*4 + data
    eff = effective_rank_map((4, 2), 0)
    np.testing.assert_array_equal(eff, [0, 0, 0, 0, 1, 1, 1, 1])
    # sharding over the major axis keeps the minor coordinate
    eff2 = effective_rank_map((4, 2), 1)
    np.testing.assert_array_equal(eff2, [0, 1, 2, 3, 0, 1, 2, 3])


def test_launch_counts_unchanged_by_sharding():
    """One ppermute per bucket per stage — sharding never multiplies the
    launch count by the shard count, and an all-f32 tree lays out into the
    same bucket count as the replicated plan at the same budget."""
    tree = {f"l{i}": jax.ShapeDtypeStruct((700,), jnp.float32)
            for i in range(6)}
    topo = Topology.flat(("data", "pod"), (4, 2),
                         link=LinkClass("link", bucket_bytes=4096))
    cfg = AveragingConfig(group_size=2, bucket_bytes=4096)
    p_fsdp = compile_plan(topo, tree, cfg, FSDP)
    p_rep_eff = compile_plan(Topology.flat(("pod",), (2,),
                                           link=LinkClass("link")),
                             tree, cfg)
    n = p_fsdp.shard_layout.n_buckets
    assert n == p_rep_eff.class_layout(0).n_buckets > 1
    for off in p_fsdp.offsets:
        stages = len(grouping.mask_bits_for_offset(p_fsdp.P_eff, p_fsdp.S,
                                                   off))
        assert p_fsdp.expected_ppermutes(off) == n * stages
        assert p_fsdp.expected_ppermutes(off) == \
            p_rep_eff.expected_ppermutes(off)


# ---------------------------------------------------------------------------
# Host-side cross-policy conversion
# ---------------------------------------------------------------------------

def _pod_identical_stacked_state(topo, plan, seed=0):
    """(P_dp, ...)-stacked state whose pod members hold identical weights."""
    rng = np.random.default_rng(seed)
    eff = effective_rank_map(topo.axis_sizes, plan.shard_axis_index)
    pod_models = [
        {"emb": jnp.asarray(rng.normal(size=(33, 70)), jnp.float32),
         "w": jnp.asarray(rng.normal(size=(1300,)), jnp.float32),
         "h": jnp.asarray(rng.normal(size=(300,)),
                          jnp.float32).astype(jnp.bfloat16),
         "e": jnp.zeros((0, 4), jnp.float32)}
        for _ in range(plan.P_eff)]
    stacked = jax.tree.map(
        lambda *ls: jnp.stack([np.asarray(ls[e]) for e in eff]), *pod_models)
    opt = jax.vmap(sgd(0.1).init)(stacked)
    return ReplicaState.create(stacked, opt, step=7, phase=1)


def test_cross_policy_conversion_round_trip_exact():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    plan = compile_plan(topo, TREE, AveragingConfig(group_size=2,
                                                    bucket_bytes=4096), FSDP)
    st_rep = _pod_identical_stacked_state(topo, plan)
    st_fsdp = replica.replicated_to_fsdp_state(st_rep, plan)
    assert isinstance(st_fsdp.params, tuple)
    assert all(b.shape[0] == plan.P_eff for b in st_fsdp.params)
    back = replica.fsdp_to_replicated_state(st_fsdp, plan)
    for a, b in zip(jax.tree.leaves(st_rep.params),
                    jax.tree.leaves(back.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for a, b in zip(jax.tree.leaves(st_rep.opt_state),
                    jax.tree.leaves(back.opt_state)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert int(back.step) == 7 and int(back.phase) == 1
    # consolidation agrees across layouts (summation order differs --
    # mean over P_dp duplicated rows vs mean over P_eff pod rows)
    cons_rep = replica.consolidate_state(st_rep)
    cons_fsdp = replica.consolidate_state(st_fsdp, plan)
    for k in TREE:
        tol = 2e-2 if k == "h" else 1e-6
        np.testing.assert_allclose(np.asarray(cons_rep[k], np.float32),
                                   np.asarray(cons_fsdp[k], np.float32),
                                   rtol=tol, atol=tol)


def test_state_templates_match_converted_shapes():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    plan = compile_plan(topo, TREE, AveragingConfig(group_size=2,
                                                    bucket_bytes=4096), FSDP)
    st_rep = _pod_identical_stacked_state(topo, plan)
    st_fsdp = replica.replicated_to_fsdp_state(st_rep, plan)
    tpl_s = replica.sharded_state_template(plan, st_rep.opt_state)
    tpl_r = replica.replicated_state_template(plan, st_fsdp.opt_state)
    for got, want in ((st_fsdp, tpl_s), (st_rep, tpl_r)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert tuple(np.shape(a)) == tuple(b.shape), (np.shape(a), b)


# ---------------------------------------------------------------------------
# Cost model: memory ÷ pod size, gather/scatter overhead
# ---------------------------------------------------------------------------

def test_costmodel_fsdp_memory_and_step_fields():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    from repro.configs.base import ModelConfig
    from repro.launch.costmodel import averaging_comm_cost
    cfg = ModelConfig(name="cm", family="dense", n_layers=24, d_model=1024,
                      n_heads=8, n_kv_heads=8, d_ff=4096, vocab=32000,
                      dtype="float32")
    topo = Topology.hierarchical(("data", "pod"), (16, 4))
    rep = averaging_comm_cost(cfg, P=64, S=8, n_leaves=290, topology=topo,
                              fsdp_shard_axis="data")
    assert rep.fsdp_pod_size == 16
    assert rep.mem_ratio >= rep.fsdp_pod_size
    assert rep.mem_fsdp_within_pod * rep.fsdp_pod_size == \
        pytest.approx(rep.mem_replicated)
    assert rep.t_fsdp > 0 and rep.gather_scatter_s > 0
    assert rep.gather_scatter_s < rep.t_fsdp
    from cluster_sim import fsdp_win
    win = fsdp_win(P=64, model_bytes=245e6, n_pods=4)
    assert win["mem_ratio"] >= win["pod_size"]
    assert win["step_ratio"] <= 1.10, win


def test_modeled_fsdp_wire_scales_with_pod_size():
    topo = Topology.hierarchical(("data", "pod"), (16, 4))
    small = plan_mod.modeled_fsdp_step_seconds(
        245_000_000, topo, 2, shard_axis="data")
    rep = plan_mod.modeled_wagma_step_seconds(245_000_000, topo, 2)
    # the sharded butterfly moves 1/16 of the payload per stage
    assert small["group_s"] < rep["group_s"]
    assert small["pod_size"] == 16 and small["P_eff"] == 4


def test_collective_axis_counts_classifies_synthetic_hlo():
    from repro.launch.hlo_analysis import collective_axis_counts
    # mesh ('pod','data') = (2,4): id = pod*4 + data
    hlo = """
ENTRY %main (p: f32[16]) -> f32[16] {
  %ag = f32[16] all-gather(%p), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %rs = f32[4] reduce-scatter(%ag), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %bad = f32[16] all-gather(%ag), replica_groups={{0,4},{1,5},{2,6},{3,7}}, dimensions={0}
  %mix = f32[16] all-gather(%ag), replica_groups={{0,5},{1,4},{2,7},{3,6}}, dimensions={0}
}
"""
    counts = collective_axis_counts(hlo, ("pod", "data"), (2, 4))
    assert counts["all-gather"] == {"data": 1, "pod": 1, "mixed": 1}
    assert counts["reduce-scatter"] == {"data": 1}


# ---------------------------------------------------------------------------
# Differential acceptance on the 8-device CPU mesh (subprocess)
# ---------------------------------------------------------------------------

_PREAMBLE = """
    from repro.core import bucketing, grouping
    from repro.core import group_allreduce as ga
    from repro.core import plan as plan_mod
    from repro.core import replica as replica_mod
    from repro.core.replica import ReplicaState, ShardingPolicy
    from repro.launch.hlo_analysis import (collective_axis_counts,
                                           collective_summary,
                                           count_ppermutes,
                                           permute_axis_counts)

    FSDP = ShardingPolicy.fsdp_within_pod("data")

    def pod_tree(rng):
        return {
            "emb": jnp.asarray(rng.normal(size=(33, 70)), jnp.float32),
            "w": jnp.asarray(rng.normal(size=(1300,)), jnp.float32),
            "h": jnp.asarray(rng.normal(size=(300,)),
                             jnp.float32).astype(jnp.bfloat16),
            "e": jnp.zeros((0, 4), jnp.float32),
        }

    # 4 pods x 2 shards: P_eff=4 with S=2 walks TWO phase offsets; tiny
    # pinned budgets force multi-bucket sharded plans on test trees
    TOPO_HIER = plan_mod.Topology(
        ("data", "pod"), (2, 4),
        (plan_mod.LinkClass("ici", alpha=1e-6, beta=1e-11, bucket_bytes=4096),
         plan_mod.LinkClass("dcn", alpha=5e-5, beta=1e-10, bucket_bytes=4096)),
        (0, 1))
    TOPO_FLAT = plan_mod.Topology.flat(
        ("data", "pod"), (2, 4),
        link=plan_mod.LinkClass("link", bucket_bytes=4096))

    def sharded_buffers(plan, pods, mesh):
        packed = [bucketing.pack(t, plan.shard_layout) for t in pods]
        spec = P("pod", "data")
        return tuple(jax.device_put(
            jnp.stack([packed[e][b] for e in range(len(pods))]),
            NamedSharding(mesh, spec)) for b in range(
                plan.shard_layout.n_buckets))
"""


def run_sub(body: str, devices: int = 8, timeout: int = 420):
    return _run_sub(body, devices=devices, timeout=timeout,
                    preamble=_PREAMBLE)


def test_fsdp_average_bit_identical_to_replicated_every_offset():
    """Acceptance gate: sharded plan execution == the replicated plan on
    the pod axis == the stacked simulator, bit-for-bit, on every phase
    offset, for flat AND hierarchical topologies and for the overlapped,
    serial, and jnp-combine realisations."""
    out = run_sub("""
        mesh = make_mesh((4, 2), ("pod", "data"))
        rng = np.random.default_rng(0)
        pods = [pod_tree(rng) for _ in range(4)]
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *pods)

        for topo in (TOPO_FLAT, TOPO_HIER):
            cfgs = {
                "overlap": plan_mod.AveragingConfig(group_size=2),
                "serial": plan_mod.AveragingConfig(group_size=2,
                                                   overlap=False),
                "jnp": plan_mod.AveragingConfig(group_size=2,
                                                use_pallas=False),
            }
            plans = {k: plan_mod.compile_plan(topo, pods[0], c, FSDP)
                     for k, c in cfgs.items()}
            pl = plans["overlap"]
            assert pl.shard_layout.n_buckets > 1, "budget must force buckets"
            bufs = sharded_buffers(pl, pods, mesh)

            assert len(pl.offsets) > 1, "must walk several phase offsets"
            # replicated reference: same butterfly over the pod axis only,
            # executed on the pod-stacked full tree (data members identical)
            rep_plan = plan_mod.compile_plan(
                plan_mod.Topology.flat(("pod",), (4,)), pods[0],
                plan_mod.AveragingConfig(group_size=2))

            for ph, off in enumerate(pl.offsets):
                got = {}
                for key, p in plans.items():
                    f = compat.shard_map(
                        lambda sh, p=p, ph=ph: tuple(
                            o[None] for o in p.average(
                                tuple(s[0] for s in sh), ph)),
                        mesh=mesh, in_specs=(P("pod", "data"),),
                        out_specs=P("pod", "data"),
                        axis_names={"pod", "data"})
                    got[key] = jax.jit(f)(bufs)
                g = compat.shard_map(
                    lambda tr, ph=ph: rep_plan.average(tr, ph), mesh=mesh,
                    in_specs=P("pod"), out_specs=P("pod"),
                    axis_names={"pod", "data"})
                rep_out = jax.jit(g)(stacked)
                want = ga.group_average_stacked(stacked, P=4, S=2, t=ph)
                for key, res in got.items():
                    for e in range(4):
                        tree_e = bucketing.unpack(
                            tuple(np.asarray(b)[e] for b in res),
                            pl.shard_layout)
                        for leaf in pods[0]:
                            np.testing.assert_array_equal(
                                np.asarray(tree_e[leaf], np.float32),
                                np.asarray(want[leaf], np.float32)[e],
                                err_msg=f"{key} vs stacked, offset {off}")
                            np.testing.assert_array_equal(
                                np.asarray(tree_e[leaf], np.float32),
                                np.asarray(rep_out[leaf], np.float32)[e],
                                err_msg=f"{key} vs replicated, offset {off}")
        print("FSDP_BIT_EXACT_OK")
    """)
    assert "FSDP_BIT_EXACT_OK" in out


def test_fsdp_shard_round_trip_sync_and_launch_counts():
    """Shard ownership round-trips (shard -> all-gather -> shard is the
    identity), sync equalises pods without touching shard neighbours, and
    the jaxpr ppermute count equals the plan expectation on every offset
    (launch counts unchanged by sharding)."""
    out = run_sub("""
        mesh = make_mesh((4, 2), ("pod", "data"))
        rng = np.random.default_rng(3)
        pods = [pod_tree(rng) for _ in range(4)]
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *pods)
        plan = plan_mod.compile_plan(
            TOPO_HIER, pods[0], plan_mod.AveragingConfig(group_size=2), FSDP)
        bufs = sharded_buffers(plan, pods, mesh)

        def rt(sh):
            local = tuple(s[0] for s in sh)
            back = plan.shard_tree(plan.unshard_tree(local))
            return tuple(b[None] for b in back)
        got = jax.jit(compat.shard_map(
            rt, mesh=mesh, in_specs=(P("pod", "data"),),
            out_specs=P("pod", "data"), axis_names={"pod", "data"}))(bufs)
        for a, b in zip(got, bufs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        def sync(sh):
            return tuple(o[None] for o in plan.sync(
                tuple(s[0] for s in sh)))
        sy = jax.jit(compat.shard_map(
            sync, mesh=mesh, in_specs=(P("pod", "data"),),
            out_specs=P("pod", "data"), axis_names={"pod", "data"}))(bufs)
        want = ga.global_average_stacked(stacked, P=4)
        for e in range(4):
            tree_e = bucketing.unpack(tuple(np.asarray(b)[e] for b in sy),
                                      plan.shard_layout)
            for leaf in ("emb", "w"):
                np.testing.assert_allclose(
                    np.asarray(tree_e[leaf]),
                    np.asarray(want[leaf], np.float32)[e],
                    rtol=1e-6, atol=1e-6)

        for ph, off in enumerate(plan.offsets):
            f = jax.jit(compat.shard_map(
                lambda sh, ph=ph: tuple(o[None] for o in plan.average(
                    tuple(s[0] for s in sh), ph)),
                mesh=mesh, in_specs=(P("pod", "data"),),
                out_specs=P("pod", "data"), axis_names={"pod", "data"}))
            n = count_ppermutes(jax.make_jaxpr(f)(bufs).jaxpr)
            assert n == plan.expected_ppermutes(off), (off, n)
            # every butterfly launch rides the pod (DCN) axis
            hlo = f.lower(bufs).compile().as_text()
            per_axis = permute_axis_counts(hlo, ("pod", "data"), (4, 2))
            assert per_axis.get("data", 0) == 0, per_axis
            assert per_axis.get("pod", 0) == plan.expected_ppermutes(off)
        print("FSDP_STRUCTURE_OK")
    """)
    assert "FSDP_STRUCTURE_OK" in out


def test_fsdp_train_step_wagma_and_allreduce():
    """End to end on the dp x (model=1) mesh: the FSDP wagma step trains
    (loss decreases, tau-sync equalises pods), the FSDP allreduce step on
    identical batches matches the single-worker reference, and the
    compiled step's all-gathers/reduce-scatters ride the intra-pod shard
    axis only (no DCN leaks)."""
    out = run_sub("""
        from repro.configs import get_config, SHAPES
        from repro.models.registry import build_model
        from repro.data import make_batch_fn
        from repro.optim import sgd
        from repro.core.baselines import make_averager
        from repro.core.group_allreduce import dp_axis_layout
        from repro.train import build_train_step, init_replica_state

        mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
        cfg = get_config("qwen3-0.6b", smoke=True)
        model = build_model(cfg)
        names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape),
                                      ("pod", "data"))
        topo = plan_mod.Topology.hierarchical(names, sizes,
                                              dcn_axes=("pod",))
        av = make_averager("wagma", names, sizes, group_size=2, tau=4,
                           topology=topo, sharding=FSDP)
        opt = sgd(0.3, momentum=0.9)
        with compat.set_mesh(mesh):
            state = init_replica_state(model, opt, av, mesh,
                                       jax.random.PRNGKey(0))
            bf = make_batch_fn(cfg, SHAPES["train_4k"], seed=0)
            steps, losses = {}, []
            for t in range(8):
                key = (av.phase_for_step(t), av.sync_due(t))
                if key not in steps:
                    steps[key] = build_train_step(model, opt, av, mesh,
                                                  phase=key[0], sync=key[1])
                nb = {k: jnp.asarray(v)[:, :32]
                      for k, v in bf(t, 0, 8).items()}
                batch = {k: jax.device_put(
                    v, NamedSharding(mesh, P(("pod", "data"), None)))
                    for k, v in nb.items()}
                state, m = steps[key](state, batch)
                losses.append(float(m["loss"]))
            assert int(state.step) == 8
            b0 = np.asarray(state.params[0])
            assert np.abs(b0 - b0[0:1]).max() < 1e-6, "sync equalises pods"
            assert losses[-1] < losses[0], losses

            # all-gathers/reduce-scatters must ride the shard (data) axis
            hlo = steps[(0, False)].lower(state, batch).compile().as_text()
            ag = collective_axis_counts(
                hlo, ("pod", "data", "model"), (2, 4, 1))
            assert ag.get("all-gather", {}).get("data", 0) > 0, ag
            for kind in ("all-gather", "reduce-scatter"):
                leaks = {a: n for a, n in ag.get(kind, {}).items()
                         if a != "data"}
                assert not leaks, (kind, ag)

        # allreduce under FSDP == classic ZeRO data parallelism: identical
        # batches on every device -> matches the single-worker reference
        cfg32 = get_config("tinyllama-1.1b", smoke=True).variant(
            dtype="float32")
        model32 = build_model(cfg32)
        av2 = make_averager("allreduce", names, sizes, topology=topo,
                            sharding=FSDP)
        opt2 = sgd(0.1, momentum=0.9)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg32.vocab, (1, 32)).astype(np.int32)
        batch_np = {"tokens": np.repeat(toks, 8, 0),
                    "labels": np.repeat(toks, 8, 0)}
        with compat.set_mesh(mesh):
            st2 = init_replica_state(model32, opt2, av2, mesh,
                                     jax.random.PRNGKey(0))
            step2 = build_train_step(model32, opt2, av2, mesh, phase=0,
                                     sync=False)
            batch = {k: jax.device_put(
                jnp.asarray(v), NamedSharding(mesh, P(("pod", "data"), None)))
                for k, v in batch_np.items()}
            st2, _ = step2(st2, batch)
            plan2 = av2.plan_for(jax.eval_shape(model32.init,
                                                jax.random.PRNGKey(0)))
            got = replica_mod.consolidate_state(jax.device_get(st2), plan2)
        p0 = model32.init(jax.random.PRNGKey(0))
        g = jax.grad(lambda p: model32.loss(
            p, {"tokens": jnp.asarray(toks),
                "labels": jnp.asarray(toks)})[0])(p0)
        p1, _ = opt2.update(g, opt2.init(p0), p0)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(p1)):
            if a.size:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-5)
        print("FSDP_TRAIN_OK")
    """, timeout=600)
    assert "FSDP_TRAIN_OK" in out


def test_fsdp_checkpoint_cross_policy_restore_and_consolidate():
    """Satellite: save from a sharded run, restore into a replicated run
    (and vice versa); step/phase bookkeeping round-trips and consolidate
    agrees bit-for-bit across the conversion."""
    out = run_sub("""
        import tempfile
        from repro.checkpoint import (checkpoint_sharding,
                                      load_replica_state,
                                      save_replica_state)
        from repro.optim import sgd

        rng = np.random.default_rng(5)
        pods = [pod_tree(rng) for _ in range(4)]
        plan = plan_mod.compile_plan(
            TOPO_HIER, pods[0], plan_mod.AveragingConfig(group_size=2), FSDP)
        opt = sgd(0.1)

        # a 'trained' sharded state: distinct pod weights, warm momentum
        bufs = tuple(jnp.stack([bucketing.pack(pods[e], plan.shard_layout)[b]
                                for e in range(4)])
                     for b in range(plan.shard_layout.n_buckets))
        opt_state = jax.vmap(opt.init)(bufs)
        # warm momentum, packed from leaves so pad regions stay zero (pad
        # elements are not state and do not survive cross-policy round trips)
        mom_tree = jax.tree.map(lambda a: jnp.full(a.shape, 0.25,
                                                   jnp.float32), pods[0])
        mom_row = bucketing.pack(mom_tree, plan.shard_layout,
                                 dtype=jnp.float32)
        mom = tuple(jnp.broadcast_to(m[None], (4,) + m.shape)
                    for m in mom_row)
        opt_state = type(opt_state)(momentum=mom,
                                    count=opt_state.count + 3)
        st_fsdp = ReplicaState.create(bufs, opt_state, step=11, phase=1)

        with tempfile.TemporaryDirectory() as d:
            save_replica_state(d, st_fsdp, sharding=FSDP,
                               metadata={"arch": "test"})
            assert checkpoint_sharding(d).is_sharded

            # sharded checkpoint -> replicated run
            tpl_rep = replica_mod.replicated_state_template(
                plan, st_fsdp.opt_state)
            st_rep = load_replica_state(d, tpl_rep, plan=plan)
            assert int(st_rep.step) == 11 and int(st_rep.phase) == 1
            eff = replica_mod.effective_rank_map(
                plan.topology.axis_sizes, plan.shard_axis_index)
            for leaf in pods[0]:
                want = np.stack([np.asarray(pods[e][leaf], np.float32)
                                 for e in eff])
                np.testing.assert_array_equal(
                    np.asarray(st_rep.params[leaf], np.float32), want)

            cons_a = replica_mod.consolidate_state(st_fsdp, plan)
            cons_b = replica_mod.consolidate_state(st_rep)
            for leaf in pods[0]:
                tol = 2e-2 if leaf == "h" else 1e-6
                np.testing.assert_allclose(
                    np.asarray(cons_a[leaf], np.float32),
                    np.asarray(cons_b[leaf], np.float32),
                    rtol=tol, atol=tol)

        # replicated checkpoint -> sharded run (round trip back to shards)
        with tempfile.TemporaryDirectory() as d:
            save_replica_state(d, st_rep)
            tpl_s = replica_mod.sharded_state_template(
                plan, st_rep.opt_state)
            st_back = load_replica_state(d, tpl_s, sharding=FSDP, plan=plan)
            for a, b in zip(st_back.params, st_fsdp.params):
                np.testing.assert_array_equal(
                    np.asarray(a, np.float32), np.asarray(b, np.float32))
            for a, b in zip(jax.tree.leaves(st_back.opt_state),
                            jax.tree.leaves(st_fsdp.opt_state)):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))
        print("CKPT_CROSS_POLICY_OK")
    """)
    assert "CKPT_CROSS_POLICY_OK" in out
