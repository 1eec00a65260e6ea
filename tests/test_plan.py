"""Compiled AveragingPlan: topology classification, per-class budgets,
compile caching, and differential acceptance (DESIGN.md §9).

Host-side tests pin the pure compilation pipeline — bit → axis → link class,
per-class ``choose_class_bucket_bytes`` argmins, stage-run splitting, plan
caching, the per-class step model.  Subprocess tests pin the execution
semantics on the 8-device CPU mesh: ``plan.average`` must be bit-identical
to the legacy fused shim, the serial-bucketed and per-leaf paths, and the
stacked simulator on EVERY phase offset — including hierarchical (2-link-
class) topologies whose butterflies repack between ICI and DCN stage runs —
and the per-class launch accounting must match both the jaxpr and the
compiled HLO's axis-classified collective-permutes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from subproc import run_sub as _run_sub

from repro.core import bucketing, grouping
from repro.core import plan as plan_mod
from repro.core.plan import (AveragingConfig, DCN, ICI, LinkClass, Topology,
                             choose_class_bucket_bytes, class_stage_seconds,
                             compile_plan, modeled_wagma_step_seconds)


# ---------------------------------------------------------------------------
# Topology: bit -> axis -> link class
# ---------------------------------------------------------------------------

def test_hierarchical_low_bits_ici_high_bits_dcn():
    # minor-to-major (data, pod): data=16 owns bits 0..3, pod=4 bits 4..5
    topo = Topology.hierarchical(("data", "pod"), (16, 4), dcn_axes=("pod",))
    assert topo.P == 64
    assert [topo.link_of_bit(b).name for b in range(6)] == \
        ["ici"] * 4 + ["dcn"] * 2
    assert [topo.axis_of_bit(b) for b in range(6)] == \
        ["data"] * 4 + ["pod"] * 2
    assert topo.bottleneck().name == "dcn"
    with pytest.raises(ValueError):
        topo.class_of_bit(6)


def test_flat_topology_single_class_everywhere():
    topo = Topology.flat(("data",), (8,))
    assert topo.classes_in_use() == (0,)
    assert all(topo.link_of_bit(b).name == "link" for b in range(3))
    # hierarchical with no matching dcn axis degrades to flat ICI
    t2 = Topology.hierarchical(("data",), (8,), dcn_axes=("pod",))
    assert t2.link_classes == (ICI,)


def test_topology_validation():
    with pytest.raises(ValueError):
        Topology(("data",), (6,), (ICI,), (0,))        # not a power of two
    with pytest.raises(ValueError):
        Topology(("data",), (8,), (ICI,), (1,))        # class out of range
    with pytest.raises(ValueError):
        Topology(("data", "pod"), (8,), (ICI,), (0,))  # length mismatch


# ---------------------------------------------------------------------------
# Per-class budgets
# ---------------------------------------------------------------------------

BIG = {"w": jax.ShapeDtypeStruct((64, 1024, 1024), jnp.float32)}   # 256 MiB


def test_per_class_budgets_distinct_and_argmin():
    plan = compile_plan(Topology.hierarchical(("data", "pod"), (16, 4)),
                        BIG, AveragingConfig(group_size=8))
    b_ici, b_dcn = plan.class_bucket_bytes[0], plan.class_bucket_bytes[1]
    assert b_ici != b_dcn, "2-class topology must pick distinct budgets"
    payload = plan.payload_bytes
    for budget, link in ((b_ici, ICI), (b_dcn, DCN)):
        assert budget in bucketing.BUCKET_BYTES_CANDIDATES
        t_star = class_stage_seconds(payload, link,
                                     -(-payload // budget), overlap=True)
        for cand in bucketing.BUCKET_BYTES_CANDIDATES:
            t = class_stage_seconds(payload, link,
                                    -(-payload // cand), overlap=True)
            assert t_star <= t + 1e-15, (link.name, budget, cand)
    # cheap-launch ICI pipelines finer than expensive-launch DCN
    assert b_ici < b_dcn


def test_pinned_link_budget_and_global_override():
    pinned = LinkClass("ici", alpha=1e-6, beta=1e-11, bucket_bytes=4096)
    assert choose_class_bucket_bytes(10**9, pinned) == 4096
    topo = Topology.hierarchical(("data", "pod"), (16, 4))
    plan = compile_plan(topo, BIG, AveragingConfig(group_size=8,
                                                   bucket_bytes=2**20))
    assert set(plan.class_bucket_bytes.values()) == {2**20}


def test_mix_bucket_bytes_follows_link_class():
    plan = compile_plan(Topology.hierarchical(("data", "pod"), (16, 4)),
                        BIG, AveragingConfig(group_size=8))
    ici_b = choose_class_bucket_bytes(plan.payload_bytes, ICI)
    dcn_b = choose_class_bucket_bytes(plan.payload_bytes, DCN)
    assert plan.mix_bucket_bytes((0,)) == ici_b      # minor-axis ring
    assert plan.mix_bucket_bytes((5,)) == dcn_b      # pod-crossing bit
    assert plan.mix_bucket_bytes((0, 5)) == dcn_b    # bound by slowest wire
    assert plan.mix_bucket_bytes(()) == dcn_b        # global collective


# ---------------------------------------------------------------------------
# Stage runs + plan accounting
# ---------------------------------------------------------------------------

def test_stage_runs_split_by_class():
    topo = Topology.hierarchical(("data", "pod"), (16, 4))
    plan = compile_plan(topo, BIG, AveragingConfig(group_size=8))  # ls=3
    assert [(r.class_index, r.bits) for r in plan.runs_for_offset(0)] == \
        [(0, (0, 1, 2))]
    # offset 3: bit 3 still data/ICI, bits 4-5 pod/DCN -> two runs
    assert [(r.class_index, r.bits) for r in plan.runs_for_offset(3)] == \
        [(0, (3,)), (1, (4, 5))]
    # wrap-around offset: DCN then ICI
    assert [(r.class_index, r.bits) for r in plan.runs_for_offset(4)] == \
        [(1, (4, 5)), (0, (0,))]


def test_expected_ppermutes_and_describe():
    topo = Topology.hierarchical(("data", "pod"), (16, 4))
    plan = compile_plan(topo, BIG, AveragingConfig(group_size=8))
    for off in plan.offsets:
        per_class = plan.per_class_expected(off)
        total = sum(e["ppermutes"] for e in per_class.values())
        assert total == plan.expected_ppermutes(off)
        for ent in per_class.values():
            assert ent["ppermutes"] == ent["stages"] * ent["n_buckets"]
    text = plan.describe()
    assert "ici" in text and "dcn" in text and "phase" in text
    for bb in plan.class_bucket_bytes.values():
        assert f"{bb / 2**20:.0f}MiB" in text


# ---------------------------------------------------------------------------
# Compile caching (satellite: no re-derivation when only the phase changes)
# ---------------------------------------------------------------------------

def test_compile_plan_cached_across_structures_and_phases():
    topo = Topology.flat(("data",), (8,))
    cfg = AveragingConfig(group_size=4)
    t1 = {"a": jnp.zeros((3, 4), jnp.float32), "b": jnp.ones((5,), jnp.bfloat16)}
    t2 = {"a": jnp.full((3, 4), 9.0, jnp.float32),
          "b": jnp.zeros((5,), jnp.bfloat16)}
    p1 = compile_plan(topo, t1, cfg)
    assert compile_plan(topo, t2, cfg) is p1            # same structure
    sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t1)
    assert compile_plan(topo, sds, cfg) is p1           # arrays == structs
    assert compile_plan(topo, t1, AveragingConfig(group_size=2)) is not p1
    # walking every phase offset reuses ONE cached layout: only the first
    # class_layout call may miss, later offsets/classes hit
    assert len(p1.offsets) > 1
    p1.class_layout(0)
    stats0 = bucketing.layout_cache_stats()
    for off in p1.offsets:
        for run in p1.runs_for_offset(off):
            p1.class_layout(run.class_index)
    stats1 = bucketing.layout_cache_stats()
    assert stats1["misses"] == stats0["misses"], (stats0, stats1)
    assert stats1["hits"] > stats0["hits"]


def test_choose_bucket_bytes_sweep_is_cached():
    bucketing.choose_bucket_bytes.cache_clear()
    kw = dict(P=64, S=8, tau=10)
    bucketing.choose_bucket_bytes(245_000_000, **kw)
    h0 = bucketing.choose_bucket_bytes.cache_info().hits
    bucketing.choose_bucket_bytes(245_000_000, **kw)
    assert bucketing.choose_bucket_bytes.cache_info().hits == h0 + 1


# ---------------------------------------------------------------------------
# Per-class step model (costmodel / bench / cluster_sim composition)
# ---------------------------------------------------------------------------

def test_modeled_hierarchical_step_per_class_budgets_win():
    topo = Topology.hierarchical(("data", "pod"), (16, 4))
    payload = 245_000_000
    hier = modeled_wagma_step_seconds(payload, topo, 8, tau=10)
    single = modeled_wagma_step_seconds(payload, topo, 8, tau=10,
                                        bucket_bytes=32 * 2**20)
    assert set(hier["per_class"]) == {"ici", "dcn"}
    assert hier["per_class"]["ici"]["bucket_bytes"] != \
        hier["per_class"]["dcn"]["bucket_bytes"]
    assert hier["step_s"] <= single["step_s"]
    assert hier["step_s"] > 0 and hier["sync_s"] > 0
    # a slower DCN can only make the step slower than all-ICI
    all_ici = modeled_wagma_step_seconds(
        payload, Topology.flat(("data", "pod"), (16, 4), link=ICI), 8, tau=10)
    assert hier["group_s"] >= all_ici["group_s"]


def test_costmodel_commreport_per_class_fields():
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    from repro.launch.costmodel import averaging_comm_cost
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(name="cm", family="dense", n_layers=24, d_model=1024,
                      n_heads=8, n_kv_heads=8, d_ff=4096, vocab=32000,
                      dtype="float32")
    topo = Topology.hierarchical(("data", "pod"), (16, 4))
    rep = averaging_comm_cost(cfg, P=64, S=8, n_leaves=290, topology=topo)
    assert set(rep.per_class) == {"ici", "dcn"}
    assert rep.t_hierarchical > 0
    assert rep.t_hierarchical <= rep.t_hierarchical_flat_budget
    assert rep.hierarchical_budget_win >= 1.0
    from cluster_sim import hierarchical_win
    win = hierarchical_win(P=64, model_bytes=245e6)
    assert win["speedup"] >= 1.0
    assert win["class_budgets"]["ici"] != win["class_budgets"]["dcn"]


def test_removed_shims_hard_error_with_plan_pointer():
    """The deprecated kwarg entry points completed their deprecation cycle:
    calling them is a hard error pointing at the plan API (ROADMAP item)."""
    from repro.core import group_allreduce as ga
    for fn, kwargs in [
            (ga.group_average, dict(offset=0, P=8, S=4,
                                    axis_names=("data",), axis_sizes=(8,))),
            (ga.global_average, dict(axis_names=("data",))),
            (ga.resolve_bucket_bytes, dict(bucket_bytes=None, P=8, S=4))]:
        with pytest.raises(RuntimeError, match="compile_plan"):
            fn({"w": jnp.zeros((4,))}, **kwargs)
    # the constants and the stacked simulator legitimately remain
    assert ga.DEFAULT_ALPHA > 0 and ga.DEFAULT_BETA > 0
    assert callable(ga.group_average_stacked)


def test_permute_axis_counts_classifies_synthetic_hlo():
    from repro.launch.hlo_analysis import permute_axis_counts
    # mesh ('pod','data') = (2,4): id = pod*4 + data
    hlo = """
ENTRY %main (p: f32[8]) -> f32[8] {
  %cp1 = f32[8] collective-permute(%p), source_target_pairs={{0,1},{1,0},{2,3},{3,2},{4,5},{5,4},{6,7},{7,6}}
  %cp2 = f32[8] collective-permute-start(%cp1), source_target_pairs={{0,4},{4,0},{1,5},{5,1},{2,6},{6,2},{3,7},{7,3}}
  %cp3 = f32[8] collective-permute(%cp2), source_target_pairs={{0,2},{2,0},{1,3},{3,1},{4,6},{6,4},{5,7},{7,5}}
}
"""
    counts = permute_axis_counts(hlo, ("pod", "data"), (2, 4))
    assert counts == {"data": 2, "pod": 1}


# ---------------------------------------------------------------------------
# Differential acceptance on the 8-device CPU mesh (subprocess)
# ---------------------------------------------------------------------------

_PREAMBLE = """
    from repro.core import bucketing, grouping
    from repro.core import group_allreduce as ga
    from repro.core import plan as plan_mod
    from repro.launch.hlo_analysis import (collective_summary,
                                           count_ppermutes,
                                           permute_axis_counts)

    def mixed_tree(rng, P_dp):
        return {
            "emb": jnp.asarray(rng.normal(size=(P_dp, 33, 70)), jnp.float32),
            "w": jnp.asarray(rng.normal(size=(P_dp, 1300)), jnp.float32),
            "s": jnp.asarray(rng.normal(size=(P_dp,)), jnp.float32),
            "h": jnp.asarray(rng.normal(size=(P_dp, 300)),
                             jnp.float32).astype(jnp.bfloat16),
            "e": jnp.zeros((P_dp, 0, 4), jnp.float32),
        }

    # tiny pinned budgets force multi-bucket, multi-run plans on test trees
    TOPO_HIER = plan_mod.Topology(
        ("data", "pod"), (4, 2),
        (plan_mod.LinkClass("ici", alpha=1e-6, beta=1e-11, bucket_bytes=4096),
         plan_mod.LinkClass("dcn", alpha=5e-5, beta=1e-10, bucket_bytes=8192)),
        (0, 1))
"""


def run_sub(body: str, devices: int = 8, timeout: int = 420):
    return _run_sub(body, devices=devices, timeout=timeout,
                    preamble=_PREAMBLE)


def test_plan_average_bit_identical_to_legacy_paths_every_offset():
    """Acceptance gate: the overlapped plan == serial-bucketed == per-leaf
    == stacked simulator, bit-for-bit, on every phase offset (the removed
    kwarg shims' realisations, now expressed as plan configs)."""
    out = run_sub("""
        P_dp, S = 8, 4
        mesh = make_mesh((2, 4), ("pod", "data"))
        names, sizes = ga.dp_axis_layout(("pod", "data"), dict(pod=2, data=4),
                                         ("pod", "data"))
        rng = np.random.default_rng(0)
        tree = mixed_tree(rng, P_dp)
        local = jax.tree.map(lambda a: a[0], tree)
        topo = plan_mod.Topology.flat(names, sizes)
        plan = plan_mod.compile_plan(
            topo, local,
            plan_mod.AveragingConfig(group_size=S, average_dtype="float32"))
        offsets = grouping.distinct_offsets(P_dp, S)
        assert plan.offsets == offsets and len(offsets) > 1
        for ph, off in enumerate(offsets):
            variants = {}
            f = compat.shard_map(
                lambda tr, p=ph: plan.average(tr, p),
                mesh=mesh, in_specs=P(("pod", "data")),
                out_specs=P(("pod", "data")), axis_names={"pod", "data"})
            variants["plan"] = jax.jit(f)(tree)
            for key, kw in [
                    ("legacy_fused", dict(fused=True)),
                    ("serial_bucketed", dict(fused=True, overlap=False)),
                    ("per_leaf", dict(fused=False))]:
                pv = plan_mod.compile_plan(
                    topo, local,
                    plan_mod.AveragingConfig(group_size=S,
                                             average_dtype="float32", **kw))
                g = compat.shard_map(
                    lambda tr, pv=pv, off=off: pv.average_offset(tr, off),
                    mesh=mesh, in_specs=P(("pod", "data")),
                    out_specs=P(("pod", "data")),
                    axis_names={"pod", "data"})
                variants[key] = jax.jit(g)(tree)
            want = ga.group_average_stacked(tree, P=P_dp, S=S, t=ph)
            for key, got in variants.items():
                for leaf in tree:
                    tol = 2e-2 if leaf == "h" else 1e-5
                    np.testing.assert_allclose(
                        np.asarray(got[leaf], np.float32),
                        np.asarray(want[leaf], np.float32), rtol=tol,
                        atol=tol, err_msg=f"{key} vs stacked, offset {off}")
                for leaf in tree:    # exactness across realisations
                    np.testing.assert_array_equal(
                        np.asarray(got[leaf], np.float32),
                        np.asarray(variants["per_leaf"][leaf], np.float32),
                        err_msg=f"{key} exactness, offset {off}, {leaf}")
        print("PLAN_OFFSETS_MATCH", len(offsets))
    """)
    assert "PLAN_OFFSETS_MATCH" in out


def test_hierarchical_plan_bit_identical_every_offset():
    """2-link-class butterflies repack between ICI and DCN stage runs with
    distinct budgets — still bit-identical to per-leaf and the stacked
    simulator on every phase offset (fp32 continuity across runs)."""
    out = run_sub("""
        P_dp, S = 8, 4
        mesh = make_mesh((2, 4), ("pod", "data"))
        names, sizes = ga.dp_axis_layout(("pod", "data"), dict(pod=2, data=4),
                                         ("pod", "data"))
        rng = np.random.default_rng(7)
        tree = mixed_tree(rng, P_dp)
        local = jax.tree.map(lambda a: a[0], tree)
        cfgs = {
            "hier_overlap": plan_mod.AveragingConfig(group_size=S),
            "hier_serial": plan_mod.AveragingConfig(group_size=S,
                                                    overlap=False),
            "hier_jnp": plan_mod.AveragingConfig(group_size=S,
                                                 use_pallas=False),
            "per_leaf": plan_mod.AveragingConfig(group_size=S, fused=False),
        }
        plans = {k: plan_mod.compile_plan(TOPO_HIER, local, c)
                 for k, c in cfgs.items()}
        pl = plans["hier_overlap"]
        assert pl.class_bucket_bytes == {0: 4096, 1: 8192}
        assert pl.class_layout(0).n_buckets > 1, "budget must force buckets"
        # at least one offset must mix classes within one butterfly
        assert any(len(pl.runs_for_offset(o)) > 1 for o in pl.offsets)
        for ph, off in enumerate(pl.offsets):
            got = {}
            for key, p in plans.items():
                f = compat.shard_map(
                    lambda tr, p=p, ph=ph: p.average(tr, ph), mesh=mesh,
                    in_specs=P(("pod", "data")),
                    out_specs=P(("pod", "data")),
                    axis_names={"pod", "data"})
                got[key] = jax.jit(f)(tree)
            want = ga.group_average_stacked(tree, P=P_dp, S=S, t=ph)
            for key, res in got.items():
                for leaf in tree:
                    tol = 2e-2 if leaf == "h" else 1e-5
                    np.testing.assert_allclose(
                        np.asarray(res[leaf], np.float32),
                        np.asarray(want[leaf], np.float32), rtol=tol,
                        atol=tol, err_msg=f"{key} vs stacked, offset {off}")
                    np.testing.assert_array_equal(
                        np.asarray(res[leaf], np.float32),
                        np.asarray(got["per_leaf"][leaf], np.float32),
                        err_msg=f"{key} exactness, offset {off}, {leaf}")
        print("HIER_OFFSETS_MATCH", len(pl.offsets))
    """)
    assert "HIER_OFFSETS_MATCH" in out


def test_hierarchical_launch_counts_per_class_match_jaxpr_and_hlo():
    """Per-class accounting: jaxpr ppermutes == plan expectation per offset,
    and the compiled HLO's axis-classified collective-permutes match the
    per-class split (ICI launches on 'data', DCN launches on 'pod')."""
    out = run_sub("""
        P_dp, S = 8, 4
        mesh = make_mesh((2, 4), ("pod", "data"))
        names, sizes = ga.dp_axis_layout(("pod", "data"), dict(pod=2, data=4),
                                         ("pod", "data"))
        rng = np.random.default_rng(1)
        tree = mixed_tree(rng, P_dp)
        local = jax.tree.map(lambda a: a[0], tree)
        plan = plan_mod.compile_plan(
            TOPO_HIER, local, plan_mod.AveragingConfig(group_size=S))

        def make(ph):
            return jax.jit(compat.shard_map(
                lambda tr: plan.average(tr, ph), mesh=mesh,
                in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
                axis_names={"pod", "data"}))

        for ph, off in enumerate(plan.offsets):
            expected = plan.expected_ppermutes(off)
            n = count_ppermutes(jax.make_jaxpr(make(ph))(tree).jaxpr)
            assert n == expected, (off, n, expected)

        # HLO per-class cross-check on the class-mixing offset
        ph = next(i for i, o in enumerate(plan.offsets)
                  if len(plan.runs_for_offset(o)) > 1)
        off = plan.offsets[ph]
        hlo = make(ph).lower(tree).compile().as_text()
        per_axis = permute_axis_counts(hlo, ("pod", "data"), (2, 4))
        per_class = plan.per_class_expected(off)
        assert per_axis.get("data", 0) == per_class["ici"]["ppermutes"], \\
            (per_axis, per_class)
        assert per_axis.get("pod", 0) == per_class["dcn"]["ppermutes"], \\
            (per_axis, per_class)
        counts = collective_summary(hlo)["counts_by_kind"]
        assert counts.get("collective-permute", 0) == \\
            plan.expected_ppermutes(off)
        print("PER_CLASS_LAUNCHES_OK")
    """)
    assert "PER_CLASS_LAUNCHES_OK" in out


def test_wagma_averager_with_topology_and_dryrun_summary():
    """WagmaAverager(topology=...) end to end: comm matches the stacked
    simulator per phase, sync equalises, and the dryrun plan summary
    reports per-class expectations that match the compiled HLO."""
    out = run_sub("""
        from repro.core.wagma import WagmaAverager, WagmaConfig
        from repro.launch.dryrun import bucket_collective_summary
        P_dp, S = 8, 4
        mesh = make_mesh((2, 4), ("pod", "data"))
        names, sizes = ga.dp_axis_layout(("pod", "data"), dict(pod=2, data=4),
                                         ("pod", "data"))
        rng = np.random.default_rng(4)
        tree = mixed_tree(rng, P_dp)
        local = jax.tree.map(lambda a: a[0], tree)
        av = WagmaAverager(names, sizes, WagmaConfig(group_size=S),
                           topology=TOPO_HIER)
        for ph in range(av.n_phases):
            f = compat.shard_map(lambda tr, p=ph: av.comm(tr, p), mesh=mesh,
                                 in_specs=P(("pod", "data")),
                                 out_specs=P(("pod", "data")),
                                 axis_names={"pod", "data"})
            got = jax.jit(f)(tree)
            want = ga.group_average_stacked(tree, P=P_dp, S=S, t=ph)
            for leaf in tree:
                tol = 2e-2 if leaf == "h" else 1e-5
                np.testing.assert_allclose(
                    np.asarray(got[leaf], np.float32),
                    np.asarray(want[leaf], np.float32), rtol=tol, atol=tol)
        g = compat.shard_map(av.sync, mesh=mesh, in_specs=P(("pod", "data")),
                             out_specs=P(("pod", "data")),
                             axis_names={"pod", "data"})
        synced = jax.jit(g)(tree)
        for leaf in ("emb", "w", "s"):
            want = np.asarray(tree[leaf], np.float32).mean(0)
            np.testing.assert_allclose(
                np.asarray(synced[leaf], np.float32),
                np.broadcast_to(want, synced[leaf].shape), rtol=1e-5,
                atol=1e-5)

        # dryrun summary: phase-0 expectations vs compiled phase-0 HLO
        f0 = jax.jit(compat.shard_map(
            lambda tr: av.comm(tr, 0), mesh=mesh,
            in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
            axis_names={"pod", "data"}))
        hlo = f0.lower(tree).compile().as_text()
        summary = bucket_collective_summary(
            av, local, collective_summary(hlo), mesh=mesh, hlo_text=hlo)
        assert summary["match"], summary
        assert all(summary["per_class_match"].values()), summary
        assert "ici" in summary["plan_summary"]
        assert "dcn" in summary["plan_summary"]
        print("WAGMA_TOPOLOGY_OK")
    """)
    assert "WAGMA_TOPOLOGY_OK" in out


def test_baseline_plans_use_class_budgets():
    """Baselines hold plans: D-PSGD's minor-axis ring buckets at the ICI
    budget while the global allreduce buckets at the DCN (bottleneck)
    budget; results still match the per-leaf reference."""
    out = run_sub("""
        from repro.core.baselines import make_averager
        P_dp = 8
        mesh = make_mesh((2, 4), ("pod", "data"))
        names, sizes = ga.dp_axis_layout(("pod", "data"), dict(pod=2, data=4),
                                         ("pod", "data"))
        rng = np.random.default_rng(3)
        tree = {"w": jnp.asarray(rng.normal(size=(8, 1300)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)}
        local = jax.tree.map(lambda a: a[0], tree)
        for name in ("dpsgd", "allreduce", "sgp", "adpsgd"):
            got = {}
            for mode, kw in [("fused", dict(fused=True, bucket_bytes=None)),
                             ("per_leaf", dict(fused=False))]:
                av = make_averager(name, names, sizes, topology=TOPO_HIER,
                                   **kw)
                f = compat.shard_map(
                    lambda tr, av=av: av.comm(tr, 0), mesh=mesh,
                    in_specs=P(("pod", "data")),
                    out_specs=P(("pod", "data")),
                    axis_names={"pod", "data"})
                got[mode] = jax.jit(f)(tree)
            for k in tree:
                np.testing.assert_allclose(
                    np.asarray(got["fused"][k]),
                    np.asarray(got["per_leaf"][k]), rtol=1e-5, atol=1e-6,
                    err_msg=name)
        av = make_averager("dpsgd", names, sizes, topology=TOPO_HIER,
                           bucket_bytes=None)
        plan = av.plan_for(local)
        assert plan.mix_bucket_bytes((0,)) == 4096      # ring: ICI budget
        assert plan.mix_bucket_bytes(()) == 8192        # global: bottleneck
        print("BASELINE_PLAN_OK")
    """)
    assert "BASELINE_PLAN_OK" in out