"""Differential tests for the bucketed fused group-averaging path.

Three independent realisations of the same math must agree on an 8-way
forced-host-device CPU mesh, for **every** phase offset of the butterfly:

    fused bucketed (Pallas combine)  ==  fused bucketed (jnp combine)
        ==  per-leaf reference  ==  stacked-simulator averaging matrix

plus the structural claim that makes the fused path worth having: ppermute
launches per step drop from ``n_leaves * log2(S)`` to ``n_buckets * log2(S)``.

Subprocess pattern (see tests/test_distributed.py): the forced device count
must not leak into the main pytest process.
"""

import pytest

from subproc import run_sub as _run_sub

_PREAMBLE = """
    from repro.core import bucketing, grouping
    from repro.core import group_allreduce as ga
    from repro.core import plan as plan_mod
    from repro.launch.hlo_analysis import count_ppermutes

    def flat_plan(local, names, sizes, S=None, **kw):
        return plan_mod.compile_plan(
            plan_mod.Topology.flat(names, sizes), local,
            plan_mod.AveragingConfig(group_size=S,
                                     average_dtype="float32", **kw))

    def mixed_tree(rng, P_dp):
        # mixed dtypes, a >1-lane leaf, a scalar-ish leaf, an empty leaf
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


def test_fused_equals_per_leaf_equals_stacked_every_offset():
    """The acceptance gate: all realisations agree on every phase offset."""
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
                    ("fused_pallas", dict(fused=True, use_pallas=True)),
                    ("fused_jnp", dict(fused=True, use_pallas=False)),
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
                for leaf_name in tree:
                    tol = 2e-2 if leaf_name == "h" else 1e-5
                    np.testing.assert_allclose(
                        np.asarray(got[leaf_name], np.float32),
                        np.asarray(want[leaf_name], np.float32),
                        rtol=tol, atol=tol,
                        err_msg=f"{key} vs stacked, offset {off}, {leaf_name}")
            # fp32-accumulation paths agree bit-for-bit with each other
            for leaf_name in tree:
                np.testing.assert_array_equal(
                    np.asarray(variants["fused_pallas"][leaf_name], np.float32),
                    np.asarray(variants["per_leaf"][leaf_name], np.float32),
                    err_msg=f"fused vs per-leaf exactness, offset {off}")
        print("ALL_OFFSETS_MATCH", len(offsets))
    """)
    assert "ALL_OFFSETS_MATCH" in out


def test_ppermute_count_drops_to_buckets_times_stages():
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
        # launch accounting now comes from the compiled plan: buckets are
        # laid out over the fp32-cast (accumulation-dtype) tree
        pl = plan_mod.compile_plan(
            plan_mod.Topology.flat(names, sizes),
            jax.tree.map(lambda a: a[0], tree),
            plan_mod.AveragingConfig(group_size=S, average_dtype="float32"))
        n_leaves = len(jax.tree.leaves(tree))
        stages = grouping.ilog2(S)

        def make(fused):
            plf = flat_plan(jax.tree.map(lambda a: a[0], tree), names, sizes,
                            S=S, fused=fused)
            return compat.shard_map(
                lambda tr: plf.average_offset(tr, 0),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                axis_names={"data"})

        n_fused = count_ppermutes(jax.make_jaxpr(make(True))(tree).jaxpr)
        n_leaf = count_ppermutes(jax.make_jaxpr(make(False))(tree).jaxpr)
        assert n_leaf == n_leaves * stages, (n_leaf, n_leaves, stages)
        assert n_fused == pl.expected_ppermutes(offset=0), \\
            (n_fused, pl.expected_ppermutes(offset=0))
        n_buckets = pl.class_layout(0).n_buckets
        assert n_fused == n_buckets * stages, (n_fused, n_buckets)
        assert n_buckets < n_leaves
        print("PPERMUTES", n_leaf, "->", n_fused)
    """)
    assert "PPERMUTES" in out


def test_global_average_fused_matches_per_leaf():
    out = run_sub("""
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(2)
        tree = mixed_tree(rng, 8)
        local = jax.tree.map(lambda a: a[0], tree)
        got = {}
        for fused in (True, False):
            pl = flat_plan(local, ("data",), (8,), fused=fused)
            f = compat.shard_map(
                lambda tr, pl=pl: pl.sync(tr),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                axis_names={"data"})
            got[fused] = jax.jit(f)(tree)
        for name in tree:
            a = np.asarray(got[True][name], np.float32)
            b = np.asarray(got[False][name], np.float32)
            tol = 2e-2 if name == "h" else 1e-6
            np.testing.assert_allclose(a, b, rtol=tol, atol=tol)
            if name != "e":
                want = np.asarray(tree[name], np.float32).mean(0)
                np.testing.assert_allclose(
                    a, np.broadcast_to(want, a.shape), rtol=tol, atol=tol)
        print("GLOBAL_OK")
    """)
    assert "GLOBAL_OK" in out


@pytest.mark.parametrize("name", ["dpsgd", "sgp", "adpsgd", "allreduce"])
def test_baseline_averagers_fused_matches_per_leaf(name):
    out = run_sub(f"""
        from repro.core.baselines import make_averager
        mesh = make_mesh((8,), ("data",))
        names, sizes = ga.dp_axis_layout(("data",), {{"data": 8}}, ("data",))
        rng = np.random.default_rng(3)
        tree = {{"w": jnp.asarray(rng.normal(size=(8, 40)), jnp.float32),
                 "b": jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)}}
        for phase in range(2):
            got = {{}}
            for fused in (True, False):
                av = make_averager({name!r}, names, sizes, fused=fused)
                f = compat.shard_map(
                    lambda tr, av=av, p=phase: av.comm(tr, p), mesh=mesh,
                    in_specs=P("data"), out_specs=P("data"),
                    axis_names={{"data"}})
                got[fused] = jax.jit(f)(tree)
            for k in tree:
                np.testing.assert_allclose(
                    np.asarray(got[True][k]), np.asarray(got[False][k]),
                    rtol=1e-5, atol=1e-6)
        print("BASELINE_OK")
    """)
    assert "BASELINE_OK" in out


def test_wagma_averager_fused_config_round_trip():
    """WagmaConfig(fused=...) end to end through the averager, incl. sync."""
    out = run_sub("""
        from repro.core.wagma import WagmaAverager, WagmaConfig
        mesh = make_mesh((8,), ("data",))
        names, sizes = ga.dp_axis_layout(("data",), {"data": 8}, ("data",))
        rng = np.random.default_rng(4)
        tree = mixed_tree(rng, 8)
        results = {}
        for fused in (True, False):
            av = WagmaAverager(names, sizes,
                               WagmaConfig(group_size=4, fused=fused))
            for ph in range(av.n_phases):
                f = compat.shard_map(lambda tr, p=ph, av=av: av.comm(tr, p),
                                     mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data"), axis_names={"data"})
                results[(fused, ph)] = jax.jit(f)(tree)
            g = compat.shard_map(av.sync, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"), axis_names={"data"})
            results[(fused, "sync")] = jax.jit(g)(tree)
        for key in [k for k in results if k[0]]:
            other = (False,) + key[1:]
            for name in tree:
                tol = 2e-2 if name == "h" else 1e-5
                np.testing.assert_allclose(
                    np.asarray(results[key][name], np.float32),
                    np.asarray(results[other][name], np.float32),
                    rtol=tol, atol=tol, err_msg=str(key))
        print("WAGMA_CFG_OK")
    """)
    assert "WAGMA_CFG_OK" in out
