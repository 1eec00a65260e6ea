"""serve/decode.py coverage: cache-sharding heuristics + 8-device decode.

``cache_shardings`` places each cache leaf's batch dim on the dp axes and
its head/channel dim on the model axis — and must now refuse (loudly) to
replicate a cache none of whose dims divide the dp extent.  NamedSharding
needs a real multi-device mesh, so every case runs on the forced 8-device
host platform via the subprocess harness; the decode smoke additionally
pins that a batch-sharded ``build_serve_step`` produces the same tokens
as the unsharded path.
"""

from subproc import run_sub


def test_cache_sharding_heuristics_8dev():
    out = run_sub("""
        from repro.serve.decode import cache_shardings

        mesh = make_mesh((4, 2), ("data", "model"))

        def spec_of(shape, batch):
            leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
            return cache_shardings(mesh, {"x": leaf}, batch)["x"].spec

        # KV leaf (n_sb, B, S, KH, hd): batch over dp, hd on model
        assert spec_of((2, 8, 64, 2, 16), 8) == P(None, "data", None, None,
                                                  "model")
        # batch == 1 long context: KV *sequence* dim takes the dp axes
        assert spec_of((2, 1, 64, 2, 16), 1) == P(None, None, "data", None,
                                                  "model")
        # ambiguous seq == batch: canonical position (dim 1) wins
        assert spec_of((2, 4, 4, 2, 16), 4) == P(None, "data", None, None,
                                                 "model")
        # rank-2 recurrent vector (B, C): batch at dim 0
        assert spec_of((8, 32), 8) == P("data", "model")
        # head-count dim sized exactly B must NOT be mistaken for batch
        assert spec_of((2, 4, 64, 4, 16), 4) == P(None, "data", None, None,
                                                  "model")

        # nothing divides the dp extent -> loud failure, not silent
        # replication
        try:
            spec_of((3, 5, 7, 5, 6), 5)
        except ValueError as e:
            assert "refusing to silently replicate" in str(e)
        else:
            raise AssertionError("indivisible cache leaf did not raise")

        # hierarchical dp: (pod, data) both carry the batch dim
        mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
        leaf = jax.ShapeDtypeStruct((2, 8, 64, 2, 16), jnp.float32)
        spec = cache_shardings(mesh3, {"x": leaf}, 8)["x"].spec
        assert spec == P(None, ("pod", "data"), None, None, "model"), spec
        print("HEURISTICS-OK")
    """)
    assert "HEURISTICS-OK" in out


def test_serve_step_sharded_decode_8dev():
    out = run_sub("""
        from repro.configs import get_config
        from repro.models.registry import build_model
        from repro.serve.decode import (build_serve_step, cache_shardings,
                                        serve_param_shardings)

        mesh = make_mesh((8, 1), ("data", "model"))
        cfg = get_config("qwen3-0.6b", smoke=True)
        model = build_model(cfg)
        B, S = 8, 32
        with compat.set_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            rng = np.random.default_rng(0)
            prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, 8)), jnp.int32)
            _, caches = jax.jit(lambda p, b: model.prefill(p, b, S))(
                params, {"tokens": prompt})
            serve = build_serve_step(model, mesh)
            tok = jnp.zeros((B, 1), jnp.int32)

            t_plain, _, _ = serve(params, jax.tree.map(jnp.copy, caches),
                                  tok, jnp.asarray(8))

            cshard = cache_shardings(mesh, jax.eval_shape(lambda: caches), B)
            pshard = serve_param_shardings(mesh,
                                           jax.eval_shape(lambda: params))
            caches_s = jax.device_put(jax.tree.map(jnp.copy, caches), cshard)
            params_s = jax.device_put(params, pshard)
            tok_s = jax.device_put(tok, NamedSharding(mesh, P("data")))
            t_shard, _, _ = serve(params_s, caches_s, tok_s, jnp.asarray(8))

            np.testing.assert_array_equal(np.asarray(t_plain),
                                          np.asarray(t_shard))
            assert (np.asarray(t_plain) < cfg.vocab).all()
            print("DECODE-OK")
    """)
    assert "DECODE-OK" in out
