"""Paged KV cache + continuous-batching scheduler (DESIGN.md §14).

Host-side tests pin the BlockPool allocator invariants (unit + hypothesis
property sweep).  Single-process model tests pin the core serving claim:
the paged scheduler's outputs — ragged admission, bucket-padded decode
batches, recompute preemption under block pressure — are **bit-identical**
to each request decoded alone against the dense reference path
(``model.prefill`` + ``model.decode_step``).  The subprocess test repeats
the end-to-end claim on the 8-device host mesh and additionally checks the
sharded paged decode step (batch over dp, pool replicated) against the
unsharded one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from subproc import run_sub

from repro.configs import get_config
from repro.models import common as cm
from repro.models.registry import build_model
from repro.serve.kv_cache import NULL_BLOCK, BlockPool, OutOfBlocks
from repro.serve.scheduler import FINISHED, Request, ServeScheduler


# ---------------------------------------------------------------------------
# BlockPool allocator
# ---------------------------------------------------------------------------

def test_block_pool_alloc_free_evict():
    pool = BlockPool(n_blocks=8, block_size=4)
    assert pool.n_free == 7                       # block 0 reserved
    tbl = pool.allocate("a", 9)                   # ceil(9/4) = 3 blocks
    assert len(tbl) == 3 and NULL_BLOCK not in tbl
    assert pool.tokens_covered("a") == 9
    # growing to the same coverage takes nothing; never shrinks
    assert pool.allocate("a", 5) == tbl
    assert pool.tokens_covered("a") == 9
    pool.allocate("b", 16)
    assert pool.n_free == 0
    assert not pool.can_allocate("c", 1)
    with pytest.raises(OutOfBlocks):
        pool.allocate("c", 1)
    assert "c" not in pool._tables                # atomic: nothing taken
    assert pool.evict("b") == 4 and pool.evictions == 1
    assert pool.free("a") == 3
    assert pool.n_free == 7
    pool.check_invariants()


def test_block_pool_padded_table_and_validation():
    pool = BlockPool(n_blocks=6, block_size=2)
    pool.allocate(0, 3)
    padded = pool.padded_table(0, 4)
    assert padded.shape == (4,) and padded.dtype == np.int32
    assert list(padded[:2]) == pool.table(0)
    assert (padded[2:] == NULL_BLOCK).all()
    with pytest.raises(ValueError):
        pool.padded_table(0, 1)                   # table wider than max
    with pytest.raises(ValueError):
        BlockPool(n_blocks=1, block_size=4)       # no room beside null
    with pytest.raises(ValueError):
        BlockPool(n_blocks=4, block_size=0)


@settings(max_examples=200, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 3),
                          st.integers(1, 40)), max_size=60),
       st.integers(2, 12), st.integers(1, 5))
def test_block_pool_property(ops, n_blocks, block_size):
    """Arbitrary allocate/free/evict interleavings keep every invariant:
    no double ownership, the null block never handed out, freed blocks
    return, and each live table covers exactly its request's tokens."""
    pool = BlockPool(n_blocks=n_blocks, block_size=block_size)
    for rid, op, n_tokens in ops:
        if op == 0:
            try:
                tbl = pool.allocate(rid, n_tokens)
                assert len(tbl) == pool.blocks_for(pool.tokens_covered(rid))
            except OutOfBlocks:
                pass
        elif op == 1:
            pool.free(rid)
            assert pool.tokens_covered(rid) == 0 and pool.table(rid) == []
        else:
            pool.evict(rid)
        pool.check_invariants()
    for rid in list(pool._tables):
        pool.free(rid)
    assert pool.n_free == n_blocks - 1


# ---------------------------------------------------------------------------
# Scheduler vs the uncontended dense reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("qwen3-0.6b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def reference_decode(model, params, prompt, max_new, s_view):
    """Per-request uncontended greedy decode on the dense cache path —
    the bit-exactness oracle (same masked argmax as the paged builders)."""
    vocab = model.cfg.vocab
    pf = jax.jit(lambda p, b: model.prefill(p, b, s_view))
    step = jax.jit(model.decode_step)

    def pick(logits):
        lg = logits[0, -1]
        lg = jnp.where(jnp.arange(lg.shape[-1]) < vocab, lg, cm.NEG_INF)
        return int(jnp.argmax(lg))

    logits, caches = pf(params, {"tokens": jnp.asarray(prompt[None])})
    out = [pick(logits)]
    while len(out) < max_new:
        pos = prompt.shape[0] + len(out) - 1
        logits, caches = step(params, caches,
                              jnp.asarray([[out[-1]]], jnp.int32),
                              jnp.asarray(pos))
        out.append(pick(logits))
    return out


RAGGED = [(3, 6), (7, 4), (5, 9), (12, 5)]        # (prompt_len, max_new)


def _prompts(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (l,)).astype(np.int32) for l in lens]


def test_scheduler_bit_exact_and_bucketed(smoke_model):
    model, params = smoke_model
    bs, max_blocks = 4, 8
    sched = ServeScheduler(model, params, n_blocks=64, block_size=bs,
                           max_blocks_per_req=max_blocks, max_batch=4)
    prompts = _prompts(model.cfg, [l for l, _ in RAGGED])
    for i, (p, (_, n)) in enumerate(zip(prompts, RAGGED)):
        sched.submit(Request(i, p, n))
    outs = sched.run()
    assert sorted(outs) == [0, 1, 2, 3]
    for i, (p, (_, n)) in enumerate(zip(prompts, RAGGED)):
        ref = reference_decode(model, params, p, n, max_blocks * bs)
        assert outs[i] == ref, f"request {i} diverged from dense reference"
        assert sched.finished[i].state == FINISHED
    # decode only ever compiled at bucket-padded batch shapes
    assert sched.decode_shapes_compiled <= \
        {(b, max_blocks) for b in sched.batch_buckets}
    # everything returned to the pool
    assert sched.blocks.n_free == 63
    sched.blocks.check_invariants()


def test_scheduler_preemption_recompute_bit_exact(smoke_model):
    """Three requests whose joint footprint exceeds the pool: the LIFO
    recompute preemption must evict/re-admit and still produce bit-exact
    outputs (greedy decode is deterministic)."""
    model, params = smoke_model
    bs, max_blocks = 4, 8
    lens = [(9, 12), (8, 13), (10, 11)]
    sched = ServeScheduler(model, params, n_blocks=14, block_size=bs,
                           max_blocks_per_req=max_blocks, max_batch=4)
    prompts = _prompts(model.cfg, [l for l, _ in lens], seed=2)
    for i, (p, (_, n)) in enumerate(zip(prompts, lens)):
        sched.submit(Request(i, p, n))
    outs = sched.run()
    assert sched.blocks.evictions > 0, "pool pressure never triggered"
    assert any(r.preemptions > 0 for r in sched.finished.values())
    for i, (p, (_, n)) in enumerate(zip(prompts, lens)):
        ref = reference_decode(model, params, p, n, max_blocks * bs)
        assert outs[i] == ref, f"request {i} diverged after preemption"
    assert sched.blocks.n_free == 13
    sched.blocks.check_invariants()


def test_scheduler_eos_and_validation(smoke_model):
    model, params = smoke_model
    sched = ServeScheduler(model, params, n_blocks=16, block_size=4,
                           max_blocks_per_req=4, max_batch=2)
    with pytest.raises(ValueError):                # exceeds max context
        sched.submit(Request("big", np.zeros(10, np.int32), 8))
    p = _prompts(model.cfg, [5])[0]
    ref = reference_decode(model, params, p, 6, 16)
    eos = ref[2]                                   # force an early stop
    sched.submit(Request("e", p, 6, eos_id=eos))
    outs = sched.run()
    assert outs["e"] == ref[:3]
    # a single request bigger than the whole pool fails loudly
    sched2 = ServeScheduler(model, params, n_blocks=3, block_size=4,
                            max_blocks_per_req=4, max_batch=2)
    sched2.submit(Request("x", np.zeros(9, np.int32), 2))
    with pytest.raises(OutOfBlocks):
        sched2.run()


# ---------------------------------------------------------------------------
# 8-device end-to-end (acceptance): scheduler on the host mesh
# ---------------------------------------------------------------------------

def test_serving_e2e_8dev_bit_exact():
    out = run_sub("""
        from repro.configs import get_config
        from repro.models import common as cm
        from repro.models.registry import build_model
        from repro.serve import kv_cache
        from repro.serve.scheduler import Request, ServeScheduler

        mesh = make_mesh((8, 1), ("data", "model"))
        cfg = get_config("qwen3-0.6b", smoke=True)
        model = build_model(cfg)
        bs, max_blocks = 4, 8
        with compat.set_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            rng = np.random.default_rng(3)
            lens = [(3, 6), (7, 4), (5, 9), (12, 5), (9, 3), (4, 7)]
            prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
                       for l, _ in lens]
            sched = ServeScheduler(model, params, n_blocks=64, block_size=bs,
                                   max_blocks_per_req=max_blocks, max_batch=8)
            for i, (p, (_, n)) in enumerate(zip(prompts, lens)):
                sched.submit(Request(i, p, n))
            outs = sched.run()

            s_view = max_blocks * bs
            pf = jax.jit(lambda p, b: model.prefill(p, b, s_view))
            step = jax.jit(model.decode_step)
            def pick(logits):
                lg = logits[0, -1]
                lg = jnp.where(jnp.arange(lg.shape[-1]) < cfg.vocab, lg,
                               cm.NEG_INF)
                return int(jnp.argmax(lg))
            for i, (p, (_, n)) in enumerate(zip(prompts, lens)):
                logits, caches = pf(params, {"tokens": jnp.asarray(p[None])})
                ref = [pick(logits)]
                while len(ref) < n:
                    pos = len(p) + len(ref) - 1
                    logits, caches = step(params, caches,
                                          jnp.asarray([[ref[-1]]], jnp.int32),
                                          jnp.asarray(pos))
                    ref.append(pick(logits))
                assert outs[i] == ref, (i, outs[i], ref)
            assert sched.decode_shapes_compiled <= \\
                {(b, max_blocks) for b in sched.batch_buckets}, \\
                sched.decode_shapes_compiled

            # sharded paged decode (batch over dp, pool replicated) must
            # match the unsharded step bit-for-bit
            decode = kv_cache.build_paged_decode(model, block_size=bs)
            pool = kv_cache.init_paged_pool(model, 32, bs)
            blocks = kv_cache.BlockPool(32, bs)
            tables = np.zeros((8, max_blocks), np.int32)
            tokens = np.zeros((8,), np.int32)
            positions = np.zeros((8,), np.int32)
            prefill = kv_cache.build_paged_prefill(model, block_size=bs)
            for i in range(8):
                p = rng.integers(0, cfg.vocab, (3 + i,)).astype(np.int32)
                blocks.allocate(i, len(p) + 1)
                tables[i] = blocks.padded_table(i, max_blocks)
                pool, first = prefill(params, pool, jnp.asarray(p[None]),
                                      jnp.asarray(tables[i]))
                tokens[i] = int(first)
                positions[i] = len(p)
            rep = NamedSharding(mesh, P())
            dp = NamedSharding(mesh, P("data"))
            pool_a = jax.tree.map(jnp.copy, pool)
            pool_b = jax.device_put(jax.tree.map(jnp.copy, pool), rep)
            _, nxt_plain = decode(params, pool_a, jnp.asarray(tables),
                                  jnp.asarray(tokens), jnp.asarray(positions))
            _, nxt_shard = decode(jax.device_put(params, rep), pool_b,
                                  jax.device_put(jnp.asarray(tables), dp),
                                  jax.device_put(jnp.asarray(tokens), dp),
                                  jax.device_put(jnp.asarray(positions), dp))
            np.testing.assert_array_equal(np.asarray(nxt_plain),
                                          np.asarray(nxt_shard))
            print("E2E-OK", sorted(sched.decode_shapes_compiled))
    """)
    assert "E2E-OK" in out
