"""Elastic topology & membership (DESIGN.md §12).

Host-side tests pin the pure machinery: power-of-two quantisation,
topology diffing (membership changes only resize dp axes), the
epoch-stamped :class:`MembershipController` state machine (leave ->
immediate shrink + spares, join -> deferred to the tau-sync barrier,
epoch audit trail, min-world floor), checkpoint-free state handoff in
both layouts (replicated row selection; FSDP pod rows unpacked through
the old plan's shard layout and repacked through the new one's), and
plan-cache eviction of dropped topologies.

The subprocess test runs the full kill/rejoin protocol on the forced-host
CPU mesh — the SAME code path as the CI smoke
(``python -m repro.launch.elastic``): a worker leaves mid-training, the
dp mesh shrinks and the plan recompiles without a restart, and the
rejoined worker's replica row is bit-identical to the survivors' at the
first post-rejoin tau-sync.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from subproc import run_sub as _run_sub

from repro.core import bucketing
from repro.core import plan as plan_mod
from repro.core import replica
from repro.core.elastic import (MembershipController, diff_topology,
                                handoff_state, largest_pow2,
                                regrow_replica_state, resize_topology,
                                select_replica_rows)
from repro.core.plan import AveragingConfig, Topology, compile_plan
from repro.core.replica import (ReplicaState, ShardingPolicy,
                                effective_rank_map)
from repro.optim import sgd

TREE = {"emb": jax.ShapeDtypeStruct((33, 70), jnp.float32),
        "w": jax.ShapeDtypeStruct((1300,), jnp.float32),
        "h": jax.ShapeDtypeStruct((300,), jnp.bfloat16)}
FSDP = ShardingPolicy.fsdp_within_pod("data")


# ---------------------------------------------------------------------------
# Quantisation + topology diffing
# ---------------------------------------------------------------------------

def test_largest_pow2():
    assert [largest_pow2(n) for n in (0, 1, 2, 3, 4, 5, 7, 8, 9)] == \
        [0, 1, 2, 2, 4, 4, 4, 8, 8]
    assert largest_pow2(-3) == 0
    assert largest_pow2(1 << 20) == 1 << 20


def test_diff_topology_resize_only():
    old = Topology.hierarchical(("data", "pod"), (4, 2))
    new = resize_topology(old, "data", 2)
    d = diff_topology(old, new)
    assert d.requires_recompile
    assert d.resized == (("data", 4, 2),)
    assert "data: 4 -> 2" in d.describe()
    same = diff_topology(old, old)
    assert not same.requires_recompile
    assert same.describe() == "topology unchanged"


def test_diff_topology_rejects_structural_changes():
    old = Topology.hierarchical(("data", "pod"), (4, 2))
    renamed = Topology.hierarchical(("data", "node"), (4, 2))
    with pytest.raises(ValueError, match="axis names"):
        diff_topology(old, renamed)
    flat = Topology.flat(("data", "pod"), (4, 2))
    with pytest.raises(ValueError, match="link-class"):
        diff_topology(old, flat)


def test_resize_topology_validation():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    assert resize_topology(topo, "pod", 4).axis_sizes == (4, 4)
    with pytest.raises(ValueError, match="no axis"):
        resize_topology(topo, "nope", 2)
    with pytest.raises(ValueError):
        resize_topology(topo, "data", 3)       # Topology enforces pow2


# ---------------------------------------------------------------------------
# MembershipController state machine
# ---------------------------------------------------------------------------

def test_controller_quantizes_shrinks_and_regrows():
    c = MembershipController(range(6))
    m = c.membership
    assert m.active == (0, 1, 2, 3) and m.spares == (4, 5)
    assert m.epoch == 0 and m.world_size == 4

    # active leave: immediate shrink, demoted survivor becomes a spare
    ev = c.leave(1)
    assert ev.kind == "shrink" and ev.epoch == 1
    assert ev.world == (0, 2) and ev.keep_rows == (0, 2)
    assert c.membership.spares == (4, 5, 3)

    # spare leave is a noop (no collective rides on it)
    assert c.leave(4).kind == "noop"
    assert c.membership.spares == (5, 3)

    # joins defer to the barrier; duplicates are noops
    assert c.join(1).kind == "defer"
    assert c.join(1).kind == "noop"
    assert c.membership.pending == (1,)

    # barrier: spares + joiners promote up to the next power of two
    ev = c.at_sync_barrier()
    assert ev.kind == "regrow" and ev.epoch == 2 and ev.n_joined == 2
    assert ev.world == (0, 2, 5, 3)
    assert c.membership.pending == (1,)      # no room for it yet
    assert c.at_sync_barrier().kind == "noop"

    # the audit trail records every epoch
    assert [m.epoch for m in c.history] == [0, 1, 2]
    assert c.history[1].active == (0, 2)


def test_controller_min_world_floor():
    with pytest.raises(ValueError, match="at least"):
        MembershipController([0], min_world=2)
    c = MembershipController([0, 1])
    with pytest.raises(RuntimeError, match="survivors"):
        c.leave(0)
    with pytest.raises(ValueError, match="unknown worker"):
        c.leave(9)
    with pytest.raises(ValueError, match="duplicate"):
        MembershipController([0, 0, 1])


# ---------------------------------------------------------------------------
# Checkpoint-free state handoff
# ---------------------------------------------------------------------------

def _stacked_state(n_rows: int, seed: int = 0) -> ReplicaState:
    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.normal(size=(n_rows, 5)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(n_rows, 3)), jnp.float32)}
    opt = jax.vmap(sgd(0.1).init)(params)
    opt = replica.map_opt_state(
        opt,
        lambda t: jax.tree.map(lambda m, p: 0.5 * p.astype(jnp.float32),
                               t, params),
        lambda c: jnp.arange(n_rows, dtype=c.dtype))
    return ReplicaState.create(params, opt, step=7, phase=1)


def test_select_replica_rows_and_regrow():
    st = _stacked_state(4)
    rows = [2, 0]
    sel = select_replica_rows(st, rows)
    for got, src in zip(jax.tree.leaves((sel.params, sel.opt_state)),
                        jax.tree.leaves((st.params, st.opt_state))):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(src)[rows])
    assert int(sel.step) == 7 and int(sel.phase) == 1

    # regrow clones the consensus row for the appended joiners
    grown = regrow_replica_state(sel, 4, source_row=0)
    w = np.asarray(grown.params["w"])
    assert w.shape[0] == 4
    np.testing.assert_array_equal(w[2], w[0])
    np.testing.assert_array_equal(w[3], w[0])
    np.testing.assert_array_equal(np.asarray(grown.opt_state.count),
                                  np.asarray(sel.opt_state.count)[[0, 1, 0, 0]])
    with pytest.raises(ValueError, match="regrow"):
        regrow_replica_state(grown, 2)


def test_handoff_replicated_is_row_selection():
    st = _stacked_state(4)
    a = handoff_state(st, [1, 3])
    b = select_replica_rows(st, [1, 3])
    for x, y in zip(jax.tree.leaves((a.params, a.opt_state)),
                    jax.tree.leaves((b.params, b.opt_state))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _pod_state(pod_models, topo, plan) -> ReplicaState:
    """Stack per-pod models to full dp rows and convert to the fsdp layout."""
    eff = effective_rank_map(topo.axis_sizes,
                             topo.axis_names.index(plan.sharding.shard_axis))
    stacked = jax.tree.map(
        lambda *ls: jnp.stack([np.asarray(ls[e]) for e in eff]), *pod_models)
    opt = jax.vmap(sgd(0.1).init)(stacked)
    opt = replica.map_opt_state(
        opt,
        lambda t: jax.tree.map(
            lambda m, p: (0.5 * p.astype(jnp.float32)), t, stacked),
        lambda c: c)
    st_rep = ReplicaState.create(stacked, opt, step=7, phase=1)
    return replica.replicated_to_fsdp_state(st_rep, plan)


def test_handoff_fsdp_pod_shrink_bit_exact():
    """Pods 4 -> 2: unpack through the old layout, repack through the new.

    The two plans choose their own bucket budgets, so the layouts need
    not match — the handoff must still be bit-exact, equal to building
    the surviving pods' state under the new plan directly.
    """
    rng = np.random.default_rng(1)
    old_topo = Topology.hierarchical(("data", "pod"), (4, 4))
    new_topo = resize_topology(old_topo, "pod", 2)
    cfg = AveragingConfig(group_size=2, bucket_bytes=4096)
    old_plan = compile_plan(old_topo, TREE, cfg, FSDP)
    new_plan = compile_plan(new_topo, TREE, cfg, FSDP)
    assert old_plan.P_eff == 4 and new_plan.P_eff == 2

    pods = [{"emb": jnp.asarray(rng.normal(size=(33, 70)), jnp.float32),
             "w": jnp.asarray(rng.normal(size=(1300,)), jnp.float32),
             "h": jnp.asarray(rng.normal(size=(300,)),
                              jnp.float32).astype(jnp.bfloat16)}
            for _ in range(old_plan.P_eff)]
    st_old = _pod_state(pods, old_topo, old_plan)

    keep = [0, 2]
    moved = handoff_state(st_old, keep, old_plan=old_plan,
                          new_plan=new_plan)
    want = _pod_state([pods[i] for i in keep], new_topo, new_plan)
    for got, exp in zip(jax.tree.leaves((moved.params, moved.opt_state)),
                        jax.tree.leaves((want.params, want.opt_state))):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(exp, np.float32))
    assert int(moved.step) == 7 and int(moved.phase) == 1


def test_handoff_rejects_policy_and_layout_crossings():
    topo = Topology.hierarchical(("data", "pod"), (4, 2))
    cfg = AveragingConfig(group_size=2, bucket_bytes=4096)
    plan_all = compile_plan(topo, TREE, cfg, FSDP)
    st = _pod_state([jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), TREE)] * plan_all.P_eff,
        topo, plan_all)
    with pytest.raises(ValueError, match="cross sharding policies"):
        handoff_state(st, [0], old_plan=plan_all, new_plan=None)
    stream = ShardingPolicy.fsdp_within_pod("data", streamed=True)
    ltree = {"stem": {"emb": TREE["emb"]},
             "layers": ({"w": jax.ShapeDtypeStruct((650,), jnp.float32)},
                        {"w": jax.ShapeDtypeStruct((650,), jnp.float32)}),
             "head": {"h": TREE["h"]}}
    plan_stream = compile_plan(topo, ltree, cfg, stream)
    with pytest.raises(ValueError, match="streamed"):
        handoff_state(st, [0, 1], old_plan=plan_all, new_plan=plan_stream)
    with pytest.raises(ValueError, match="P_eff"):
        handoff_state(st, [0], old_plan=plan_all, new_plan=plan_all)


# ---------------------------------------------------------------------------
# Plan-cache hygiene on membership change
# ---------------------------------------------------------------------------

def test_evict_topology_drops_only_the_dead_world():
    topo_a = Topology.hierarchical(("data", "pod"), (4, 2))
    topo_b = resize_topology(topo_a, "data", 2)
    cfg = AveragingConfig(group_size=2, bucket_bytes=4096)
    pa = compile_plan(topo_a, TREE, cfg)
    pa_f = compile_plan(topo_a, TREE, cfg, FSDP)
    pb = compile_plan(topo_b, TREE, cfg)
    assert compile_plan(topo_a, TREE, cfg) is pa
    assert plan_mod.evict_topology(topo_a) >= 2     # plan + shard structs
    assert compile_plan(topo_a, TREE, cfg) is not pa
    assert compile_plan(topo_a, TREE, cfg, FSDP) is not pa_f
    assert compile_plan(topo_b, TREE, cfg) is pb    # survivor untouched
    assert plan_mod.evict_topology(topo_a) >= 1     # the recompiles above


def test_clear_plan_cache_delegates_to_layout_cache():
    bucketing.layout_for(TREE, max_bucket_bytes=4096)
    assert bucketing._LAYOUT_CACHE
    plan_mod.clear_plan_cache()
    assert not bucketing._LAYOUT_CACHE
    assert not plan_mod._PLAN_CACHE


# ---------------------------------------------------------------------------
# The kill/rejoin protocol on the CPU mesh (subprocess)
# ---------------------------------------------------------------------------

def test_kill_rejoin_training_survives_and_rejoiner_bit_identical():
    """A worker dies at t=2, announces its rejoin, the world shrinks 4->2
    and training continues; at the t=3 tau-sync the world regrows; at the
    final tau-sync the rejoiner's replica row is bit-identical to every
    survivor's.  Same code path as the ``python -m repro.launch.elastic``
    CI smoke."""
    out = _run_sub("""
        from repro.launch.elastic import kill_rejoin_demo

        rep = kill_rejoin_demo(log_every=0)
        assert rep["rejoin_bit_identical"]
        worlds = [r["world"] for r in rep["history"]]
        assert worlds == [4, 4, 2, 2, 4, 4, 4, 4], worlds
        epochs = [r["epoch"] for r in rep["history"]]
        assert epochs == [0, 0, 1, 1, 2, 2, 2, 2], epochs
        kinds = [e["kind"] for e in rep["epoch_log"]]
        assert kinds == ["shrink", "regrow"], kinds
        assert all(e["plans_evicted"] >= 1 for e in rep["epoch_log"])
        print("ELASTIC_KILL_REJOIN_OK")
    """, devices=8, timeout=600)
    assert "ELASTIC_KILL_REJOIN_OK" in out


# ---------------------------------------------------------------------------
# Property: controller invariants under adversarial interleavings
# ---------------------------------------------------------------------------

from hypothesis import given, settings, strategies as st  # noqa: E402

_OPS = ("leave", "join", "barrier")


def _drive_controller(ops, pool):
    """Replay an arbitrary leave/join/barrier interleaving and check every
    invariant the launch layer leans on after each op:

    * the active world is always a power of two >= min_world;
    * active/spares/pending are disjoint, no worker duplicated;
    * ``join`` never promotes — the active set only grows at the barrier;
    * a shrink's ``keep_rows`` maps old active rows onto the new world;
    * the epoch bumps exactly when the active set changes, and the
      history holds one snapshot per epoch;
    * rejected ops (unknown worker, below-min-world shrink) leave the
      controller untouched.
    """
    mc = MembershipController(range(pool), min_world=2)
    last_epoch = mc.epoch
    for op, w in ops:
        before = mc.membership
        try:
            if op == "leave":
                ev = mc.leave(w)
            elif op == "join":
                ev = mc.join(w)
                assert ev.kind in ("defer", "noop")
                assert mc.membership.active == before.active, \
                    "join promoted outside the sync barrier"
            else:
                ev = mc.at_sync_barrier()
        except (ValueError, RuntimeError):
            assert mc.membership == before, \
                "a rejected op must not mutate membership"
            continue
        m = mc.membership
        n = m.world_size
        assert n >= mc.min_world and n & (n - 1) == 0, m
        seen = list(m.active) + list(m.spares) + list(m.pending)
        assert len(seen) == len(set(seen)), m
        if ev.kind == "shrink":
            assert [before.active[i] for i in ev.keep_rows] == list(m.active)
        assert mc.epoch >= last_epoch
        if set(m.active) != set(before.active):
            assert mc.epoch == last_epoch + 1
            assert ev.kind in ("shrink", "regrow"), ev
        else:
            assert mc.epoch == last_epoch
        last_epoch = mc.epoch
    assert [h.epoch for h in mc.history] == list(range(mc.epoch + 1))


@given(ops=st.lists(st.tuples(st.sampled_from(_OPS), st.integers(0, 13)),
                    max_size=50),
       pool=st.integers(4, 12))
@settings(max_examples=80, deadline=None)
def test_membership_invariants_property(ops, pool):
    _drive_controller(ops, pool)


@pytest.mark.parametrize("seed", range(6))
def test_membership_invariants_seeded_interleavings(seed):
    """Fixed-seed complement to the property test: seeded random 60-op
    interleavings over a 4..12 pool."""
    rng = np.random.default_rng(seed)
    pool = int(rng.integers(4, 13))
    ops = [(_OPS[int(rng.integers(3))], int(rng.integers(14)))
           for _ in range(60)]
    _drive_controller(ops, pool)
