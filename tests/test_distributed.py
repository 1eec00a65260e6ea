"""Distributed-path tests: run in subprocesses with forced host devices so
the main pytest process keeps the real single-device CPU view (the dry-run
flag must never be set globally — see the system design notes)."""

from subproc import run_sub


def test_butterfly_group_average_equals_stacked_simulator():
    out = run_sub("""
        from repro.core import group_allreduce as ga
        from repro.core.wagma import WagmaAverager, WagmaConfig
        mesh = make_mesh((2, 4), ("pod", "data"))
        names, sizes = ga.dp_axis_layout(("pod", "data"), dict(pod=2, data=4),
                                         ("pod", "data"))
        av = WagmaAverager(names, sizes, WagmaConfig(group_size=4))
        W = np.random.default_rng(0).normal(size=(8, 6, 5)).astype(np.float32)
        tree = {"w": jnp.asarray(W)}
        for t in range(5):
            ph = av.phase_for_step(t)
            f = compat.shard_map(lambda tr: av.comm(tr, ph), mesh=mesh,
                              in_specs=P(("pod", "data")),
                              out_specs=P(("pod", "data")),
                              axis_names={"pod", "data"})
            got = np.asarray(jax.jit(f)(tree)["w"])
            want = np.asarray(ga.group_average_stacked(tree, P=8, S=4, t=t)["w"])
            np.testing.assert_allclose(got, want, rtol=1e-5)
        print("MATCH")
    """)
    assert "MATCH" in out


def test_wagma_train_step_loss_decreases_and_sync_equalises():
    out = run_sub("""
        from repro.configs import get_config, SHAPES
        from repro.models.registry import build_model
        from repro.data import make_batch_fn
        from repro.optim import sgd
        from repro.core.baselines import make_averager
        from repro.core.group_allreduce import dp_axis_layout
        from repro.train import build_train_step, init_replica_state

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("qwen3-0.6b", smoke=True)
        model = build_model(cfg)
        names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape),
                                      ("data",))
        av = make_averager("wagma", names, sizes, group_size=2, tau=4)
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
                nb = {k: jnp.asarray(v)[:, :32] for k, v in bf(t, 0, 8).items()}
                batch = {k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
                         for k, v in nb.items()}
                state, m = steps[key](state, batch)
                losses.append(float(m["loss"]))
            assert int(state.step) == 8
            w = np.asarray(jax.tree.leaves(state.params)[0], np.float32)
            assert np.abs(w - w[0:1]).max() < 1e-4, "sync must equalise replicas"
            assert losses[-1] < losses[0], losses
            print("LOSSES", ["%.3f" % l for l in losses])
    """)
    assert "LOSSES" in out


def test_all_baseline_averagers_compile_and_preserve_mean():
    out = run_sub("""
        from repro.core.baselines import make_averager
        from repro.core.group_allreduce import dp_axis_layout
        mesh = make_mesh((8,), ("data",))
        names, sizes = dp_axis_layout(("data",), {"data": 8}, ("data",))
        W = np.random.default_rng(1).normal(size=(8, 40)).astype(np.float32)
        tree = {"w": jnp.asarray(W)}
        for name in ("dpsgd", "sgp", "adpsgd", "wagma"):
            av = make_averager(name, names, sizes)
            for ph in range(min(av.n_phases, 3)):
                f = compat.shard_map(lambda tr, p=ph: av.comm(tr, p), mesh=mesh,
                                  in_specs=P("data"), out_specs=P("data"),
                                  axis_names={"data"})
                got = np.asarray(jax.jit(f)(tree)["w"])
                np.testing.assert_allclose(got.mean(0), W.mean(0),
                                           rtol=1e-4, atol=1e-5)
        print("MEAN_OK")
    """)
    assert "MEAN_OK" in out


def test_grad_averager_allreduce_matches_single_worker_equivalent():
    """Allreduce-SGD with P replicas on the same data == single worker."""
    out = run_sub("""
        from repro.configs import get_config, SHAPES
        from repro.models.registry import build_model
        from repro.optim import sgd
        from repro.core.baselines import make_averager
        from repro.core.group_allreduce import dp_axis_layout
        from repro.train import build_train_step, init_replica_state

        mesh = make_mesh((4, 1), ("data", "model"))
        cfg = get_config("tinyllama-1.1b", smoke=True).variant(dtype="float32")
        model = build_model(cfg)
        names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape), ("data",))
        av = make_averager("allreduce", names, sizes)
        opt = sgd(0.1, momentum=0.9)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab, (1, 32)).astype(np.int32)
        # identical batch on every replica -> pmean(grads) == local grads
        batch_np = {"tokens": np.repeat(toks, 4, 0), "labels": np.repeat(toks, 4, 0)}
        with compat.set_mesh(mesh):
            state = init_replica_state(model, opt, av, mesh,
                                       jax.random.PRNGKey(0))
            step = build_train_step(model, opt, av, mesh, phase=0, sync=False)
            batch = {k: jax.device_put(jnp.asarray(v),
                                       NamedSharding(mesh, P("data", None)))
                     for k, v in batch_np.items()}
            state, _ = step(state, batch)
            w = np.asarray(jax.tree.leaves(state.params)[0])
        # single worker reference
        p0 = model.init(jax.random.PRNGKey(0))
        st0 = opt.init(p0)
        g = jax.grad(lambda p: model.loss(p, {"tokens": jnp.asarray(toks),
                                              "labels": jnp.asarray(toks)})[0])(p0)
        p1, _ = opt.update(g, st0, p0)
        ref = np.asarray(jax.tree.leaves(p1)[0])
        np.testing.assert_allclose(w[0], ref, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(w[1], ref, rtol=1e-4, atol=1e-5)
        print("EQUIV_OK")
    """)
    assert "EQUIV_OK" in out
