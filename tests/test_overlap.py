"""Overlapped bucket pipeline: schedule invariants + differential acceptance.

The wavefront scheduler (core/overlap.py, DESIGN.md §8) must (a) emit a
schedule that keeps every bucket's stage chain in order while issuing bucket
k+1's exchange before bucket k's combine, (b) produce bit-identical results
to the serial-bucketed and per-leaf paths on every phase offset of the
8-device CPU mesh (with the stacked simulator as the independent witness),
(c) never change the collective launch count — cross-checked both on the
jaxpr and against the compiled HLO via the bucket-layout-aware summary the
dry-run records.
"""

import numpy as np
import pytest

from subproc import run_sub as _run_sub

from repro.core import overlap


# ---------------------------------------------------------------------------
# Pure-python schedule properties (no mesh, fast)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_buckets,n_stages", [
    (1, 1), (1, 3), (2, 1), (2, 2), (3, 2), (4, 3), (5, 1), (7, 4), (16, 5)])
def test_schedule_invariants(n_buckets, n_stages):
    events = overlap.pipeline_schedule(n_buckets, n_stages)
    overlap.validate_schedule(events, n_buckets, n_stages)


def test_schedule_overlap_property_explicit():
    # the tentpole claim, spelled out: next bucket's wire before my combine
    events = overlap.pipeline_schedule(3, 2)
    pos = {e: i for i, e in enumerate(events)}
    for s in range(2):
        for k in range(2):
            assert pos[(overlap.EXCHANGE, k + 1, s)] < \
                pos[(overlap.COMBINE, k, s)]
    # and no stage barrier: bucket 0 exchanges stage 1 while bucket 2 has
    # not yet combined stage 0
    assert pos[(overlap.EXCHANGE, 0, 1)] < pos[(overlap.COMBINE, 2, 0)]


def test_combine_batches_cover_all_cells_once():
    events = overlap.pipeline_schedule(4, 3)
    batches = overlap.combine_batches(events)
    cells = [c for b in batches for c in b]
    assert sorted(cells) == [(k, s) for k in range(4) for s in range(3)]
    for batch in batches:   # batched combines must touch distinct buckets
        ks = [k for k, _ in batch]
        assert len(ks) == len(set(ks))


def test_empty_and_degenerate_schedules():
    assert overlap.pipeline_schedule(0, 3) == ()
    assert overlap.pipeline_schedule(3, 0) == ()
    overlap.validate_schedule(overlap.pipeline_schedule(1, 1), 1, 1)


def test_overlapped_stage_seconds_model():
    alpha, wire, combine = 1e-5, 10e-3, 3e-3
    serial = lambda b: b * alpha + wire + combine
    # one bucket: nothing to overlap, forms coincide
    np.testing.assert_allclose(
        overlap.overlapped_stage_seconds(wire, combine, 1, alpha), serial(1))
    # B >= 2 with nonzero combine: strictly cheaper than serial
    for b in (2, 4, 16):
        t = overlap.overlapped_stage_seconds(wire, combine, b, alpha)
        assert t < serial(b)
        # lower bound: can never beat the wire (plus launches + drain slot)
        assert t >= b * alpha + wire
    # wire-bound regime: combine fully hidden except the last bucket's drain
    t4 = overlap.overlapped_stage_seconds(wire, combine, 4, alpha)
    np.testing.assert_allclose(t4, 4 * alpha + wire + combine / 4)
    # combine-bound regime mirrors it
    t4c = overlap.overlapped_stage_seconds(combine, wire, 4, alpha)
    np.testing.assert_allclose(t4c, 4 * alpha + wire + combine / 4)


# ---------------------------------------------------------------------------
# Differential acceptance on the 8-device CPU mesh (subprocess)
# ---------------------------------------------------------------------------

_PREAMBLE = """
    from repro.core import bucketing, grouping
    from repro.core import group_allreduce as ga
    from repro.core import plan as plan_mod
    from repro.launch.hlo_analysis import collective_summary, count_ppermutes

    def flat_plan(local, names, sizes, S=None, **kw):
        return plan_mod.compile_plan(
            plan_mod.Topology.flat(names, sizes), local,
            plan_mod.AveragingConfig(group_size=S,
                                     average_dtype="float32", **kw))

    def mixed_tree(rng, P_dp):
        return {
            "emb": jnp.asarray(rng.normal(size=(P_dp, 33, 7)), jnp.float32),
            "w": jnp.asarray(rng.normal(size=(P_dp, 130)), jnp.float32),
            "s": jnp.asarray(rng.normal(size=(P_dp,)), jnp.float32),
            "h": jnp.asarray(rng.normal(size=(P_dp, 3, 5)),
                             jnp.float32).astype(jnp.bfloat16),
            "e": jnp.zeros((P_dp, 0, 4), jnp.float32),
        }
"""


def run_sub(body: str, devices: int = 8, timeout: int = 420):
    return _run_sub(body, devices=devices, timeout=timeout,
                    preamble=_PREAMBLE)


def test_overlapped_equals_serial_equals_per_leaf_every_offset():
    """Acceptance gate: overlapped == serial-bucketed == per-leaf == stacked
    simulator for every phase offset, bit-for-bit under fp32 accumulation."""
    out = run_sub("""
        P_dp, S = 8, 4
        mesh = make_mesh((2, 4), ("pod", "data"))
        names, sizes = ga.dp_axis_layout(("pod", "data"), dict(pod=2, data=4),
                                         ("pod", "data"))
        rng = np.random.default_rng(0)
        tree = mixed_tree(rng, P_dp)
        offsets = grouping.distinct_offsets(P_dp, S)
        assert len(offsets) > 1, offsets
        local = jax.tree.map(lambda a: a[0], tree)
        for t, off in enumerate(offsets):
            variants = {}
            for key, kw in [
                    ("overlap_pallas", dict(fused=True, use_pallas=True,
                                            overlap=True)),
                    ("overlap_jnp", dict(fused=True, use_pallas=False,
                                         overlap=True)),
                    ("serial_bucketed", dict(fused=True, use_pallas=True,
                                             overlap=False)),
                    ("per_leaf", dict(fused=False))]:
                pl = flat_plan(local, names, sizes, S=S, **kw)
                f = compat.shard_map(
                    lambda tr, pl=pl, off=off: pl.average_offset(tr, off),
                    mesh=mesh, in_specs=P(("pod", "data")),
                    out_specs=P(("pod", "data")),
                    axis_names={"pod", "data"})
                variants[key] = jax.jit(f)(tree)
            want = ga.group_average_stacked(tree, P=P_dp, S=S, t=t)
            for key, got in variants.items():
                for leaf in tree:
                    tol = 2e-2 if leaf == "h" else 1e-5
                    np.testing.assert_allclose(
                        np.asarray(got[leaf], np.float32),
                        np.asarray(want[leaf], np.float32),
                        rtol=tol, atol=tol,
                        err_msg=f"{key} vs stacked, offset {off}, {leaf}")
            # fp32-accumulation realisations agree bit-for-bit pairwise
            for key in ("overlap_pallas", "overlap_jnp", "serial_bucketed"):
                for leaf in tree:
                    np.testing.assert_array_equal(
                        np.asarray(variants[key][leaf], np.float32),
                        np.asarray(variants["per_leaf"][leaf], np.float32),
                        err_msg=f"{key} exactness, offset {off}, {leaf}")
        print("ALL_OFFSETS_MATCH", len(offsets))
    """)
    assert "ALL_OFFSETS_MATCH" in out


def test_overlap_preserves_launch_count_and_matches_hlo():
    """Wavefront reorders launches but never adds any: jaxpr ppermutes ==
    n_buckets * log2(S) under overlap, and the compiled HLO's
    collective-permute count matches the BucketLayout expectation (the
    dry-run cross-check, exercised end to end on a dp-only mesh)."""
    out = run_sub("""
        from repro.core import plan as plan_mod
        P_dp, S = 8, 4
        mesh = make_mesh((8,), ("data",))
        names, sizes = ga.dp_axis_layout(("data",), {"data": 8}, ("data",))
        rng = np.random.default_rng(1)
        tree = {f"l{i}": jnp.asarray(rng.normal(size=(8, 40)), jnp.float32)
                for i in range(6)}
        tree["h"] = jnp.asarray(rng.normal(size=(8, 16)),
                                jnp.float32).astype(jnp.bfloat16)
        local = jax.tree.map(lambda a: a[0], tree)
        pl = plan_mod.compile_plan(
            plan_mod.Topology.flat(names, sizes), local,
            plan_mod.AveragingConfig(group_size=S, average_dtype="float32"))
        stages = grouping.ilog2(S)
        expected = pl.expected_ppermutes(offset=0)
        assert expected == pl.class_layout(0).n_buckets * stages

        def make(overlap):
            plv = flat_plan(local, names, sizes, S=S, fused=True,
                            overlap=overlap)
            return jax.jit(compat.shard_map(
                lambda tr: plv.average_offset(tr, 0),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                axis_names={"data"}))

        for ov in (True, False):
            n = count_ppermutes(jax.make_jaxpr(make(ov))(tree).jaxpr)
            assert n == expected, (ov, n, expected)

        hlo = make(True).lower(tree).compile().as_text()
        counts = collective_summary(hlo)["counts_by_kind"]
        assert counts.get("collective-permute", 0) == expected, counts

        from repro.launch.dryrun import bucket_collective_summary
        from repro.core.wagma import WagmaAverager, WagmaConfig
        av = WagmaAverager(names, sizes, WagmaConfig(group_size=S))
        summary = bucket_collective_summary(av, local,
                                            collective_summary(hlo))
        assert summary["expected_ppermutes"] == expected, summary
        assert summary["match"], summary
        print("LAUNCHES_OK", expected)
    """)
    assert "LAUNCHES_OK" in out


def test_wagma_averager_overlap_round_trip():
    """WagmaConfig(overlap=...) end to end through the averager + sync."""
    out = run_sub("""
        from repro.core.wagma import WagmaAverager, WagmaConfig
        mesh = make_mesh((8,), ("data",))
        names, sizes = ga.dp_axis_layout(("data",), {"data": 8}, ("data",))
        rng = np.random.default_rng(4)
        tree = mixed_tree(rng, 8)
        results = {}
        for overlap in (True, False):
            av = WagmaAverager(names, sizes,
                               WagmaConfig(group_size=4, overlap=overlap))
            for ph in range(av.n_phases):
                f = compat.shard_map(lambda tr, p=ph, av=av: av.comm(tr, p),
                                     mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data"), axis_names={"data"})
                results[(overlap, ph)] = jax.jit(f)(tree)
            g = compat.shard_map(av.sync, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"), axis_names={"data"})
            results[(overlap, "sync")] = jax.jit(g)(tree)
        for key in [k for k in results if k[0]]:
            other = (False,) + key[1:]
            for name in tree:
                np.testing.assert_array_equal(
                    np.asarray(results[key][name], np.float32),
                    np.asarray(results[other][name], np.float32),
                    err_msg=str(key))
        print("WAGMA_OVERLAP_OK")
    """)
    assert "WAGMA_OVERLAP_OK" in out


@pytest.mark.parametrize("name", ["dpsgd", "sgp", "adpsgd", "allreduce"])
def test_baseline_averagers_overlap_matches_serial(name):
    out = run_sub(f"""
        from repro.core.baselines import make_averager
        mesh = make_mesh((8,), ("data",))
        names, sizes = ga.dp_axis_layout(("data",), {{"data": 8}}, ("data",))
        rng = np.random.default_rng(3)
        tree = {{"w": jnp.asarray(rng.normal(size=(8, 40)), jnp.float32),
                 "b": jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)}}
        for phase in range(2):
            got = {{}}
            for mode, kw in [("overlap", dict(fused=True, overlap=True)),
                             ("serial", dict(fused=True, overlap=False)),
                             ("per_leaf", dict(fused=False))]:
                av = make_averager({name!r}, names, sizes, **kw)
                f = compat.shard_map(
                    lambda tr, av=av, p=phase: av.comm(tr, p), mesh=mesh,
                    in_specs=P("data"), out_specs=P("data"),
                    axis_names={{"data"}})
                got[mode] = jax.jit(f)(tree)
            for k in tree:
                np.testing.assert_array_equal(
                    np.asarray(got["overlap"][k]),
                    np.asarray(got["serial"][k]))
                np.testing.assert_allclose(
                    np.asarray(got["overlap"][k]),
                    np.asarray(got["per_leaf"][k]), rtol=1e-5, atol=1e-6)
        print("BASELINE_OVERLAP_OK")
    """)
    assert "BASELINE_OVERLAP_OK" in out
