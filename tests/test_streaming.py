"""Layer-streamed FSDP engine differentials (DESIGN.md §11).

Host-side tests pin the pure pieces: layer-aware (grouped) bucket layouts
— group-pure contiguous buckets, the layer<->bucket map, the
oversize-layer edge case, cache keying — the streamed schedule invariants
(gather k+1 before compute k, bounded in-flight spans), streamed plan
compilation (sublayout views, accounting, describe output), the streamed
cost-model fields, and cross-policy checkpoint restore when the sharded
side uses a layer-aware layout.

Subprocess tests pin the execution on the 8-device CPU mesh: the streamed
(layer-aware) plan's butterfly must stay bit-identical to the replicated
plan and the stacked simulator on EVERY phase offset (flat and
hierarchical), and the streamed train step must be bit-identical to the
gather-all FSDP step — same losses, same resulting logical parameters —
across steps covering every phase offset and a tau-sync, while compiling
exactly the scheduled number of shard-axis all-gathers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from subproc import run_sub as _run_sub

from repro.core import bucketing, streaming
from repro.core import plan as plan_mod
from repro.core import replica
from repro.core.plan import AveragingConfig, LinkClass, Topology, compile_plan
from repro.core.replica import ReplicaState, ShardingPolicy
from repro.models import common as cm
from repro.optim import sgd

# synthetic layered trees double as their own "canonical" layout; the
# real merge/split round trip is pinned by the qwen3 test below
_IDENTITY_LAYERED = cm.LayeredModel(
    n_spans=2, split=lambda t: t, merge=lambda t: t,
    stem=None, span=None, head_loss=None)


# ---------------------------------------------------------------------------
# Layer-aware bucket layouts
# ---------------------------------------------------------------------------

def _grouped_tree():
    # canonical dict order interleaves groups on purpose: "head" < "layers"
    # < "stem" alphabetically, but groups order stem(0) < spans < head
    return {
        "stem": {"emb": jax.ShapeDtypeStruct((33, 70), jnp.float32)},
        "layers": (
            {"w": jax.ShapeDtypeStruct((1300,), jnp.float32),
             "h": jax.ShapeDtypeStruct((300,), jnp.bfloat16)},
            {"w": jax.ShapeDtypeStruct((1300,), jnp.float32),
             "h": jax.ShapeDtypeStruct((300,), jnp.bfloat16)},
        ),
        "head": {"out": jax.ShapeDtypeStruct((40,), jnp.float32),
                 "e": jax.ShapeDtypeStruct((0, 4), jnp.float32)},
    }


def test_grouped_layout_group_pure_ordered_buckets():
    tree = _grouped_tree()
    groups = streaming.layered_leaf_groups(tree)
    lay = bucketing.build_layout(tree, max_bucket_bytes=4096, groups=groups)
    assert lay.grouped
    # buckets ordered by group, each bucket exactly one group
    assert list(lay.bucket_groups) == sorted(lay.bucket_groups)
    # every group's buckets are contiguous
    gmap = lay.group_bucket_map()
    for g, idxs in gmap.items():
        assert list(idxs) == list(range(idxs[0], idxs[-1] + 1)), (g, idxs)
    assert set(gmap) == {0, 1, 2, 3}
    # leaves land in their own group's buckets only
    for slot, g in zip(lay.slots, groups):
        assert lay.bucket_groups[slot.bucket] == g
    # group_bytes sums the padded bucket bytes
    total = sum(lay.group_bytes(g) for g in gmap)
    assert total == sum(s * d.itemsize for s, d in
                        zip(lay.bucket_sizes, lay.bucket_dtypes))
    assert "->" in lay.describe_groups()
    # pack/unpack round trip through the grouped layout
    rng = np.random.default_rng(0)
    conc = jax.tree.map(
        lambda s: jnp.asarray(rng.normal(size=s.shape),
                              jnp.float32).astype(s.dtype), tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    back = bucketing.unpack(bucketing.pack(conc, lay), lay)
    for a, b in zip(jax.tree.leaves(conc), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_grouped_layout_matches_per_group_sublayouts():
    """The global grouped layout restarts its fill per group, so each
    group's slice equals the layout of the group's sub-tree alone — the
    invariant the plan's sublayout views (stream_unshard) rely on."""
    tree = _grouped_tree()
    groups = streaming.layered_leaf_groups(tree)
    lay = bucketing.build_layout(tree, max_bucket_bytes=4096, groups=groups)
    subtrees = {0: tree["stem"], 1: tree["layers"][0],
                2: tree["layers"][1], 3: tree["head"]}
    for g, sub in subtrees.items():
        sublay = bucketing.build_layout(sub, max_bucket_bytes=4096)
        idxs = lay.group_bucket_indices(g)
        assert sublay.n_buckets == len(idxs)
        assert tuple(sublay.bucket_sizes) == tuple(
            lay.bucket_sizes[i] for i in idxs)
        assert tuple(sublay.bucket_dtypes) == tuple(
            lay.bucket_dtypes[i] for i in idxs)
        # within-bucket slot offsets agree too
        glob_slots = [(s.offset, s.size) for s, gg in
                      zip(lay.slots, groups) if gg == g]
        sub_slots = [(s.offset, s.size) for s in sublay.slots]
        assert glob_slots == sub_slots


def test_grouped_layout_oversize_layer_edge_case():
    """A single layer larger than the class budget still gets buckets of
    its own (oversize leaves are never split, never shared across
    groups), and small neighbouring layers do not merge into it."""
    big = 4096    # bytes budget; the span below is ~5x that
    tree = {
        "stem": {"s": jax.ShapeDtypeStruct((8,), jnp.float32)},
        "layers": (
            {"a": jax.ShapeDtypeStruct((3000,), jnp.float32),   # 12000 B
             "b": jax.ShapeDtypeStruct((900,), jnp.float32),
             "c": jax.ShapeDtypeStruct((900,), jnp.float32)},
            {"t": jax.ShapeDtypeStruct((8,), jnp.float32)},
        ),
        "head": {"h": jax.ShapeDtypeStruct((8,), jnp.float32)},
    }
    groups = streaming.layered_leaf_groups(tree)
    lay = bucketing.build_layout(tree, max_bucket_bytes=big, groups=groups)
    gmap = lay.group_bucket_map()
    # the oversize span split into several buckets, all its own
    assert len(gmap[1]) >= 2
    for bi in gmap[1]:
        assert lay.bucket_groups[bi] == 1
    # the tiny span/stem/head did not ride along in the big span's buckets
    assert len(gmap[0]) == len(gmap[2]) == len(gmap[3]) == 1
    assert set(gmap[2]).isdisjoint(gmap[1])
    # contiguity survives the split
    assert list(lay.bucket_groups) == sorted(lay.bucket_groups)


def test_layout_cache_keyed_on_groups():
    tree = _grouped_tree()
    groups = streaming.layered_leaf_groups(tree)
    a = bucketing.layout_for(tree, max_bucket_bytes=4096)
    b = bucketing.layout_for(tree, max_bucket_bytes=4096, groups=groups)
    c = bucketing.layout_for(tree, max_bucket_bytes=4096, groups=groups)
    assert a is not b and b is c
    assert not a.grouped and b.grouped
    # layer-aware spans differ from budget-only spans on this tree
    assert a.n_buckets != b.n_buckets or \
        tuple(a.bucket_sizes) != tuple(b.bucket_sizes)


def test_layered_leaf_groups_validation():
    with pytest.raises(ValueError, match="layered param tree"):
        streaming.layered_leaf_groups({"a": jnp.zeros(3)})
    with pytest.raises(ValueError, match="layered param tree"):
        streaming.layered_leaf_groups((jnp.zeros(3),))
    groups = streaming.layered_leaf_groups(_grouped_tree())
    assert sorted(set(groups)) == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# Streamed schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_spans", [1, 2, 3, 6, 13])
def test_stream_schedule_invariants(n_spans):
    events = streaming.stream_schedule(n_spans)
    streaming.validate_stream_schedule(events, n_spans)


def test_stream_schedule_peak_bytes_two_spans():
    """With uniform span bytes the liveness peak is stem + head + 2 spans
    — the two-layer-span in-flight bound the CI smoke enforces."""
    n = 8
    span_b, stem_b, head_b = 100, 7, 11
    gb = {0: stem_b, **{k + 1: span_b for k in range(n)},
          streaming.head_group(n): head_b}
    peak = streaming.max_in_flight_gathered_bytes(gb, n)
    assert peak <= stem_b + head_b + 2 * span_b
    assert peak >= 2 * span_b
    full = sum(gb.values())
    assert peak < full


# ---------------------------------------------------------------------------
# Streamed plan compilation
# ---------------------------------------------------------------------------

STREAM = ShardingPolicy.fsdp_within_pod("data", streamed=True)


def test_sharding_policy_streamed_validation():
    assert STREAM.streamed and STREAM.is_sharded
    assert "streamed" in STREAM.describe()
    with pytest.raises(ValueError, match="streamed"):
        ShardingPolicy("replicated", None, True)
    # distinct from the gather-all policy in the plan cache key
    assert STREAM != ShardingPolicy.fsdp_within_pod("data")


def test_streamed_plan_compile_and_accounting():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    cfg = AveragingConfig(group_size=2, bucket_bytes=4096)
    tree = _grouped_tree()
    plan = compile_plan(topo, tree, cfg, STREAM)
    assert plan.n_stream_spans == 2
    lay = plan.shard_layout
    assert lay.grouped
    for size in lay.bucket_sizes:
        assert size % (4 * 128) == 0
    # sublayout views agree with the global layout (asserted inside) and
    # templates point at the right sub-SDS-trees
    for g in sorted(set(lay.bucket_groups)):
        plan.stream_sublayout(g)
    assert set(plan.stream_group_template(0)) == {"emb"}
    assert set(plan.stream_group_template(3)) == {"out", "e"}
    # accounting: peak under the 2-span bound, strictly below full tree
    gb = plan.stream_group_bytes()
    assert plan.stream_peak_gathered_bytes() <= \
        gb[0] + gb[3] + 2 * max(gb[1], gb[2])
    assert plan.stream_peak_gathered_bytes() < plan.full_gathered_bytes()
    assert streaming.expected_stream_gathers(plan) > lay.n_buckets
    # describe surfaces the layer map + layout-cache stats (satellite)
    desc = plan.describe()
    assert "layer map" in desc and "layout cache" in desc
    assert "streamed coverage" in desc
    # a non-layered tree must fail at compile time
    with pytest.raises(ValueError, match="layered param tree"):
        compile_plan(topo, {"w": jax.ShapeDtypeStruct((64,), jnp.float32)},
                     cfg, STREAM)
    # the fp32 grad-shard structure resolves back to the same plan (the
    # averagers are handed the grad tuple inside the step)
    grad_struct = tuple(
        jax.ShapeDtypeStruct(s.shape, np.dtype(np.float32))
        for s in plan.shard_struct())
    assert compile_plan(topo, grad_struct, cfg, STREAM) is plan


def test_streamed_plan_distinct_from_gather_all_plan():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    cfg = AveragingConfig(group_size=2, bucket_bytes=4096)
    tree = _grouped_tree()
    p_stream = compile_plan(topo, tree, cfg, STREAM)
    p_all = compile_plan(topo, tree, cfg,
                         ShardingPolicy.fsdp_within_pod("data"))
    assert p_stream is not p_all
    assert not p_all.shard_layout.grouped
    with pytest.raises(ValueError, match="stream_"):
        p_all.stream_unshard((), 0)


# ---------------------------------------------------------------------------
# Cost model: streamed fields
# ---------------------------------------------------------------------------

def test_costmodel_streamed_fields_and_bounds():
    from repro.configs.base import ModelConfig
    from repro.launch.costmodel import averaging_comm_cost
    cfg = ModelConfig(name="cm", family="dense", n_layers=24, d_model=1024,
                      n_heads=8, n_kv_heads=8, d_ff=4096, vocab=32000,
                      dtype="float32")
    topo = Topology.hierarchical(("data", "pod"), (16, 4))
    rep = averaging_comm_cost(cfg, P=64, S=8, n_leaves=290, topology=topo,
                              fsdp_shard_axis="data",
                              fsdp_streamed_spans=24,
                              span_fwd_compute_s=2e-3)
    assert rep.peak_gathered_bytes > 0
    assert 0 < rep.peak_gathered_bytes_streamed < rep.peak_gathered_bytes
    assert rep.t_fsdp_streamed > 0
    # compute covers the span gather here -> streaming hides the wire
    assert rep.t_fsdp_streamed <= rep.t_fsdp_gather_all
    assert rep.streamed_win >= 1.0
    # comm-bound regime: the backward re-gather is honest in the model —
    # streaming can LOSE when span compute cannot cover the span gather
    starved = averaging_comm_cost(cfg, P=64, S=8, n_leaves=290,
                                  topology=topo, fsdp_shard_axis="data",
                                  fsdp_streamed_spans=24,
                                  span_fwd_compute_s=1e-6)
    assert starved.streamed_win < 1.0
    # degenerate single span: "two spans in flight" IS the whole tree —
    # the modeled peak clamps at the full payload, never above it
    one = plan_mod.modeled_streamed_fsdp_step_seconds(
        245_000_000, topo, 2, shard_axis="data", n_spans=1,
        span_fwd_compute_s=1e-3)
    assert one["peak_gathered_bytes_streamed"] == \
        one["peak_gathered_bytes_full"]


def test_topology_with_measured(tmp_path):
    import json
    path = tmp_path / "LINK_CONSTANTS.json"
    path.write_text(json.dumps({
        "backend": "cpu",
        "axes": {"data": {"alpha": 2e-6, "beta": 3e-11, "gamma": 1e-10,
                          "ag_alpha": 1e-6, "ag_beta": 5e-11},
                 "pod": {"alpha": 9e-5, "beta": 2e-10}},
    }))
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    m = topo.with_measured(str(path))
    ici, dcn = m.link_classes
    # the class takes the slower of the ppermute and all-gather rates
    assert ici.alpha == 2e-6 and ici.beta == 5e-11 and ici.gamma == 1e-10
    assert dcn.alpha == 9e-5 and dcn.beta == 2e-10
    assert dcn.gamma == topo.link_classes[1].gamma     # unmeasured: default
    assert "@measured" in m.describe()
    # partial files leave unmeasured classes untouched
    path.write_text(json.dumps({"axes": {"data": {"alpha": 1e-6,
                                                  "beta": 1e-11}}}))
    m2 = topo.with_measured(str(path))
    assert m2.link_classes[1] == topo.link_classes[1]


# ---------------------------------------------------------------------------
# Cross-policy checkpoint restore with a layer-aware layout (satellite)
# ---------------------------------------------------------------------------

def _concrete_layered(rng, oversize=False):
    span = lambda: {
        "w": jnp.asarray(rng.normal(size=(3000 if oversize else 1300,)),
                         jnp.float32),
        "h": jnp.asarray(rng.normal(size=(300,)),
                         jnp.float32).astype(jnp.bfloat16)}
    return {"stem": {"emb": jnp.asarray(rng.normal(size=(33, 70)),
                                        jnp.float32)},
            "layers": (span(), span()),
            "head": {"out": jnp.asarray(rng.normal(size=(40,)), jnp.float32),
                     "e": jnp.zeros((0, 4), jnp.float32)}}


def test_streamed_checkpoint_cross_policy_restore(tmp_path):
    """Save from a layer-aware sharded run, restore into a replicated run
    and back; one span exceeds the bucket budget (layer spans != budget
    spans) to pin the conversion against the grouped layout."""
    from repro.checkpoint import (checkpoint_sharding, load_replica_state,
                                  save_replica_state)
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    cfg = AveragingConfig(group_size=2, bucket_bytes=4096)
    rng = np.random.default_rng(3)
    pods = [_concrete_layered(rng, oversize=True) for _ in range(2)]
    plan = compile_plan(topo, pods[0], cfg, STREAM)
    assert len(plan.shard_layout.group_bucket_map()[1]) >= 2  # oversize span
    opt = sgd(0.1)

    bufs = tuple(jnp.stack([bucketing.pack(pods[e], plan.shard_layout)[b]
                            for e in range(2)])
                 for b in range(plan.shard_layout.n_buckets))
    st_fsdp = ReplicaState.create(bufs, jax.vmap(opt.init)(bufs),
                                  step=5, phase=1)
    d = str(tmp_path / "ck")
    save_replica_state(d, st_fsdp, sharding=STREAM)
    pol = checkpoint_sharding(d)
    assert pol.streamed and pol.shard_axis == "data"

    tpl_rep = replica.replicated_state_template(plan, st_fsdp.opt_state)
    # crossing layered <-> canonical requires the decomposition
    with pytest.raises(ValueError, match="layered"):
        load_replica_state(d, tpl_rep, plan=plan)
    st_rep = load_replica_state(d, tpl_rep, plan=plan,
                                layered=_IDENTITY_LAYERED)
    assert int(st_rep.step) == 5 and int(st_rep.phase) == 1
    eff = replica.effective_rank_map(topo.axis_sizes, plan.shard_axis_index)
    for (path, leaf) in jax.tree_util.tree_flatten_with_path(pods[0])[0]:
        got = _leaf_by_path(st_rep.params, path)
        want = np.stack([np.asarray(_leaf_by_path(pods[e], path), np.float32)
                         for e in eff])
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)

    # round trip back into the streamed layout
    d2 = str(tmp_path / "ck2")
    save_replica_state(d2, st_rep)
    tpl_s = replica.sharded_state_template(plan, st_rep.opt_state)
    st_back = load_replica_state(d2, tpl_s, sharding=STREAM, plan=plan,
                                 layered=_IDENTITY_LAYERED)
    for a, b in zip(st_back.params, st_fsdp.params):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    # streamed <-> gather-all restore auto-routes through the canonical
    # replicated conversion path (it used to fail loudly); here BOTH
    # plans compile over the same layered tree, so no layered= needed.
    # The destination plan must be supplied though — npz keys are flat
    # bucket indices, so mixing layouts without it would be silent
    # corruption.
    plan_all = compile_plan(topo, pods[0], cfg,
                            ShardingPolicy.fsdp_within_pod("data"))
    tpl_all = replica.sharded_state_template(plan_all, st_fsdp.opt_state)
    with pytest.raises(ValueError, match="pass the compiled plan"):
        load_replica_state(d, tpl_all,
                           sharding=ShardingPolicy.fsdp_within_pod("data"))
    st_all = load_replica_state(d, tpl_all,
                                sharding=ShardingPolicy.fsdp_within_pod(
                                    "data"),
                                plan=plan_all)
    assert int(st_all.step) == 5 and int(st_all.phase) == 1
    # bit-exact across the layout change: unpack both and compare leaves
    got_tree = replica._unpack_rows(st_all.params, plan_all.shard_layout)
    want_tree = replica._unpack_rows(st_fsdp.params, plan.shard_layout)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(got_tree)[0],
            jax.tree_util.tree_flatten_with_path(want_tree)[0]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=str(path))

    # and back: a gather-all checkpoint restores into the streamed layout
    d3 = str(tmp_path / "ck3")
    save_replica_state(d3, st_all,
                       sharding=ShardingPolicy.fsdp_within_pod("data"))
    st_round = load_replica_state(d3, replica.sharded_state_template(
        plan, st_fsdp.opt_state), sharding=STREAM, plan=plan)
    for a, b in zip(st_round.params, st_fsdp.params):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _leaf_by_path(tree, path):
    for k in path:
        key = getattr(k, "key", getattr(k, "idx", None))
        tree = tree[key]
    return tree


def test_streamed_checkpoint_restores_into_canonical_replicated(tmp_path):
    """The prescribed migration path works end to end on a real model: a
    streamed-fsdp checkpoint restores into a CANONICAL replicated state
    (layered rows merged via ModelAPI.layered), and a canonical replicated
    checkpoint restores back into the streamed layout — bit-exact both
    ways."""
    from repro.checkpoint import load_replica_state, save_replica_state
    from repro.configs import get_config
    from repro.models.registry import build_model

    cfg = get_config("qwen3-0.6b", smoke=True).variant(dtype="float32")
    model = build_model(cfg)
    topo = Topology.hierarchical(("data", "pod"), (2, 2))
    p0 = model.init(jax.random.PRNGKey(0))
    lt = model.layered.split(p0)
    plan = compile_plan(topo, lt, AveragingConfig(group_size=2), STREAM)
    packed = bucketing.pack(lt, plan.shard_layout)
    bufs = tuple(jnp.broadcast_to(b[None], (plan.P_eff,) + b.shape)
                 for b in packed)
    opt = sgd(0.1)
    st = ReplicaState.create(bufs, jax.vmap(opt.init)(bufs), step=2,
                             phase=0)
    d = str(tmp_path / "stream_ck")
    save_replica_state(d, st, sharding=STREAM)

    tpl_rep = replica.replicated_state_template(plan, st.opt_state)
    with pytest.raises(ValueError, match="layered"):
        load_replica_state(d, tpl_rep, plan=plan)
    st_rep = load_replica_state(d, tpl_rep, plan=plan,
                                layered=model.layered)
    assert "blocks" in st_rep.params, "canonical structure restored"
    for path, a in jax.tree_util.tree_flatten_with_path(p0)[0]:
        got = np.asarray(_leaf_by_path(st_rep.params, path), np.float32)
        want = np.asarray(a, np.float32)
        for r in range(plan.P):
            np.testing.assert_array_equal(got[r], want, err_msg=str(path))

    # canonical replicated checkpoint -> streamed run, bit-exact round trip
    d2 = str(tmp_path / "rep_ck")
    save_replica_state(d2, st_rep)
    tpl_s = replica.sharded_state_template(plan, st_rep.opt_state)
    st_back = load_replica_state(d2, tpl_s, sharding=STREAM, plan=plan,
                                 layered=model.layered)
    for a, b in zip(st_back.params, st.params):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert int(st_back.step) == 2 and int(st_back.phase) == 0


# ---------------------------------------------------------------------------
# Differential acceptance on the 8-device CPU mesh (subprocess)
# ---------------------------------------------------------------------------

_PREAMBLE = """
    from repro.core import bucketing, grouping, streaming
    from repro.core import group_allreduce as ga
    from repro.core import plan as plan_mod
    from repro.core.replica import ShardingPolicy
    from repro.launch.hlo_analysis import count_ppermutes

    STREAM = ShardingPolicy.fsdp_within_pod("data", streamed=True)

    def layered_tree(rng):
        span = lambda: {
            "w": jnp.asarray(rng.normal(size=(1300,)), jnp.float32),
            "h": jnp.asarray(rng.normal(size=(300,)),
                             jnp.float32).astype(jnp.bfloat16)}
        return {"stem": {"emb": jnp.asarray(rng.normal(size=(33, 70)),
                                            jnp.float32)},
                "layers": (span(), span()),
                "head": {"out": jnp.asarray(rng.normal(size=(40,)),
                                            jnp.float32),
                         "e": jnp.zeros((0, 4), jnp.float32)}}

    # 4 pods x 2 shards: P_eff=4 with S=2 walks TWO phase offsets; tiny
    # pinned budgets force multi-bucket groups
    TOPO_HIER = plan_mod.Topology(
        ("data", "pod"), (2, 4),
        (plan_mod.LinkClass("ici", alpha=1e-6, beta=1e-11,
                            bucket_bytes=4096),
         plan_mod.LinkClass("dcn", alpha=5e-5, beta=1e-10,
                            bucket_bytes=4096)),
        (0, 1))
    TOPO_FLAT = plan_mod.Topology.flat(
        ("data", "pod"), (2, 4),
        link=plan_mod.LinkClass("link", bucket_bytes=4096))

    def sharded_buffers(plan, pods, mesh):
        packed = [bucketing.pack(t, plan.shard_layout) for t in pods]
        return tuple(jax.device_put(
            jnp.stack([packed[e][b] for e in range(len(pods))]),
            NamedSharding(mesh, P("pod", "data"))) for b in range(
                plan.shard_layout.n_buckets))
"""


def run_sub(body: str, devices: int = 8, timeout: int = 600):
    return _run_sub(body, devices=devices, timeout=timeout,
                    preamble=_PREAMBLE)


def test_streamed_plan_average_bit_identical_every_offset():
    """The butterfly over the layer-aware (grouped) shard layout must stay
    bit-identical to the replicated plan on the pod axis and the stacked
    simulator, on every phase offset, flat AND hierarchical."""
    out = run_sub("""
        mesh = make_mesh((4, 2), ("pod", "data"))
        rng = np.random.default_rng(0)
        pods = [layered_tree(rng) for _ in range(4)]
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *pods)

        for topo in (TOPO_FLAT, TOPO_HIER):
            pl = plan_mod.compile_plan(
                topo, pods[0], plan_mod.AveragingConfig(group_size=2),
                STREAM)
            assert pl.shard_layout.grouped
            assert pl.shard_layout.n_buckets > 3
            bufs = sharded_buffers(pl, pods, mesh)
            assert len(pl.offsets) > 1
            rep_plan = plan_mod.compile_plan(
                plan_mod.Topology.flat(("pod",), (4,)), pods[0],
                plan_mod.AveragingConfig(group_size=2))
            for ph, off in enumerate(pl.offsets):
                f = compat.shard_map(
                    lambda sh, ph=ph: tuple(
                        o[None] for o in pl.average(
                            tuple(s[0] for s in sh), ph)),
                    mesh=mesh, in_specs=(P("pod", "data"),),
                    out_specs=P("pod", "data"),
                    axis_names={"pod", "data"})
                got = jax.jit(f)(bufs)
                n = count_ppermutes(jax.make_jaxpr(jax.jit(f))(bufs).jaxpr)
                assert n == pl.expected_ppermutes(off), (off, n)
                g = compat.shard_map(
                    lambda tr, ph=ph: rep_plan.average(tr, ph), mesh=mesh,
                    in_specs=P("pod"), out_specs=P("pod"),
                    axis_names={"pod", "data"})
                rep_out = jax.jit(g)(stacked)
                want = ga.group_average_stacked(stacked, P=4, S=2, t=ph)
                for e in range(4):
                    tree_e = bucketing.unpack(
                        tuple(np.asarray(b)[e] for b in got),
                        pl.shard_layout)
                    flat_e = jax.tree_util.tree_flatten_with_path(tree_e)[0]
                    flat_w = jax.tree_util.tree_flatten_with_path(want)[0]
                    flat_r = jax.tree_util.tree_flatten_with_path(rep_out)[0]
                    for (pa, a), (_, w), (_, r) in zip(flat_e, flat_w,
                                                       flat_r):
                        np.testing.assert_array_equal(
                            np.asarray(a, np.float32),
                            np.asarray(w, np.float32)[e],
                            err_msg=f"vs stacked {pa} off {off}")
                        np.testing.assert_array_equal(
                            np.asarray(a, np.float32),
                            np.asarray(r, np.float32)[e],
                            err_msg=f"vs replicated {pa} off {off}")
        print("STREAMED_AVG_BIT_EXACT_OK")
    """)
    assert "STREAMED_AVG_BIT_EXACT_OK" in out


def test_streamed_train_step_bit_exact_vs_gather_all():
    """Acceptance gate: the layer-streamed train step == the gather-all
    FSDP step bit-for-bit — losses and resulting logical params — across
    steps covering every phase offset and the tau-sync, on flat AND
    hierarchical topologies; its compiled HLO contains exactly the
    scheduled number of shard-axis all-gathers; and the microbatched
    gather-all path (re-gather per microbatch, shard-space fp32
    accumulation) agrees with the single-batch step."""
    out = run_sub("""
        from repro.configs import SHAPES, get_config
        from repro.core.baselines import make_averager
        from repro.core.group_allreduce import dp_axis_layout
        from repro.data import make_batch_fn
        from repro.launch.hlo_analysis import grouped_collective_details
        from repro.models.registry import build_model
        from repro.optim import sgd
        from repro.train import build_train_step, init_replica_state
        from repro.train.train_step import _plan_of

        mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
        cfg = get_config("qwen3-0.6b", smoke=True).variant(dtype="float32")
        model = build_model(cfg)
        names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape),
                                      ("pod", "data"))
        bf = make_batch_fn(cfg, SHAPES["train_4k"], seed=0)
        FSDP = ShardingPolicy.fsdp_within_pod("data")

        def logical(model, av, state):
            plan = _plan_of(model, av)
            out = []
            for e in range(plan.P_eff):
                tree = bucketing.unpack(
                    tuple(np.asarray(b)[e] for b in state.params),
                    plan.shard_layout)
                if av.sharding.streamed:
                    tree = model.layered.merge(tree)
                out.append(tree)
            return out

        for topo_name, topo in (
                ("hier", plan_mod.Topology.hierarchical(
                    names, sizes, dcn_axes=("pod",))),
                ("flat", plan_mod.Topology.flat(names, sizes))):
            runs = {}
            with compat.set_mesh(mesh):
                for tag, pol in (("gather_all", FSDP), ("streamed", STREAM)):
                    av = make_averager("wagma", names, sizes, group_size=2,
                                       tau=4, topology=topo, sharding=pol)
                    assert av.n_phases == 2
                    opt = sgd(0.3, momentum=0.9)
                    runs[tag] = dict(
                        av=av, opt=opt,
                        state=init_replica_state(model, opt, av, mesh,
                                                 jax.random.PRNGKey(0)))
                steps, losses = {}, {}
                for t in range(5):
                    nb = {k: jnp.asarray(v)[:, :32]
                          for k, v in bf(t, 0, 8).items()}
                    batch = {k: jax.device_put(v, NamedSharding(
                        mesh, P(("pod", "data"), None)))
                        for k, v in nb.items()}
                    for tag, r in runs.items():
                        key = (tag, r["av"].phase_for_step(t),
                               r["av"].sync_due(t))
                        if key not in steps:
                            steps[key] = build_train_step(
                                model, r["opt"], r["av"], mesh,
                                phase=key[1], sync=key[2])
                        r["state"], m = steps[key](r["state"], batch)
                        losses[tag] = float(m["loss"])
                    assert losses["streamed"] == losses["gather_all"], losses
                    pa = logical(model, runs["gather_all"]["av"],
                                 runs["gather_all"]["state"])
                    pb = logical(model, runs["streamed"]["av"],
                                 runs["streamed"]["state"])
                    for e, (ta, tb) in enumerate(zip(pa, pb)):
                        for a, b in zip(jax.tree.leaves(ta),
                                        jax.tree.leaves(tb)):
                            np.testing.assert_array_equal(
                                np.asarray(a, np.float32),
                                np.asarray(b, np.float32),
                                err_msg=f"{topo_name} t={t} pod={e}")
                print(topo_name, "bit-exact over 5 steps (2 offsets + sync)")

                # HLO cross-check on the streamed group step: exactly the
                # scheduled shard-axis all-gathers, none bigger than one
                # layer-span bucket
                r = runs["streamed"]
                plan = _plan_of(model, r["av"])
                hlo = steps[("streamed", 0, False)].lower(
                    r["state"], batch).compile().as_text()
                det = grouped_collective_details(
                    hlo, ("pod", "data", "model"), (4, 2, 1))
                ags = [d for d in det if d["kind"] == "all-gather"
                       and d["axis"] == "data"]
                assert len(ags) == streaming.expected_stream_gathers(plan), (
                    len(ags), streaming.expected_stream_gathers(plan))
                lay = plan.shard_layout
                max_bucket = max(s * max(d.itemsize, 4) for s, d in
                                 zip(lay.bucket_sizes, lay.bucket_dtypes))
                assert all(d["tensor_bytes"] <= max_bucket for d in ags)
                assert plan.stream_peak_gathered_bytes() < \
                    plan.full_gathered_bytes()

        # S2 bugfix check: the microbatched gather-all step (re-gather per
        # microbatch, fp32 shard-space accumulation) matches the
        # single-batch step closely (summation order differs)
        with compat.set_mesh(mesh):
            av = runs["gather_all"]["av"]
            opt = sgd(0.3, momentum=0.9)
            st_a = init_replica_state(model, opt, av, mesh,
                                      jax.random.PRNGKey(0))
            st_b = init_replica_state(model, opt, av, mesh,
                                      jax.random.PRNGKey(0))
            step_a = build_train_step(model, opt, av, mesh, phase=0,
                                      sync=False)
            step_b = build_train_step(model, opt, av, mesh, phase=0,
                                      sync=False, microbatch=2)
            nb = {k: jnp.asarray(v)[:, :32] for k, v in bf(0, 0, 16).items()}
            batch = {k: jax.device_put(v, NamedSharding(
                mesh, P(("pod", "data"), None))) for k, v in nb.items()}
            st_a, ma = step_a(st_a, batch)
            st_b, mb = step_b(st_b, batch)
            for a, b in zip(st_a.params, st_b.params):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-5, atol=2e-6)
        print("MICROBATCH_FSDP_OK")
        print("STREAMED_STEP_BIT_EXACT_OK")
    """, timeout=900)
    assert "STREAMED_STEP_BIT_EXACT_OK" in out
    assert "MICROBATCH_FSDP_OK" in out
