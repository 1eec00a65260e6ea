"""End-to-end behaviour: WAGMA-SGD convergence vs Allreduce under stragglers
(the paper's central claim, laptop scale), trainer driver, serving loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs import get_config
from repro.configs.base import InputShape, ModelConfig
from repro.core import staleness
from repro.core.group_allreduce import global_average_stacked
from repro.data import make_batch_fn
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model
from repro.optim import sgd

P, S, TAU = 8, 4, 5


def _run_sim(mode: str, steps: int = 60, seed: int = 0, stragglers: int = 2):
    cfg = ModelConfig(name="sys-lm", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                      dtype="float32")
    model = build_model(cfg)
    opt = sgd(0.4, momentum=0.9)
    p0 = model.init(jax.random.PRNGKey(seed))
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (P,) + a.shape),
                           p0)
    state = staleness.init_state(stacked)
    holder = {"opt": jax.vmap(opt.init)(stacked)}
    shape = InputShape("sys", 32, P * 2, "train")
    bf = make_batch_fn(cfg, shape, seed=seed)
    strag = staleness.StragglerModel(P, n_stragglers=stragglers, p_stall=0.25,
                                     seed=seed)

    def per_worker(p, st, tokens, labels):
        loss, g = jax.value_and_grad(
            lambda q: model.loss(q, {"tokens": tokens, "labels": labels})[0]
        )(p)
        newp, newst = opt.update(g, st, p)
        return newp, newst, loss

    upd = jax.jit(jax.vmap(per_worker))
    losses = []
    for t in range(steps):
        nb = bf(t, 0, P * 2)
        toks = jnp.asarray(nb["tokens"]).reshape(P, 2, -1)
        labs = jnp.asarray(nb["labels"]).reshape(P, 2, -1)

        def local_update(models):
            newp, newst, loss = upd(models, holder["opt"], toks, labs)
            holder["opt"] = newst
            holder["loss"] = loss
            return newp

        ready, completes = strag.sample()
        if mode == "wagma":
            state = staleness.wagma_sim_step(state, local_update, P=P, S=S,
                                             tau=TAU, ready=ready,
                                             completes=completes, t=t)
        else:
            newp = global_average_stacked(local_update(state.models), P=P)
            state = state._replace(models=newp)
        losses.append(float(holder["loss"].mean()))
    return losses


def test_wagma_converges_like_allreduce_under_stragglers():
    """Paper Fig. 5's claim at laptop scale: same-budget final quality of
    WAGMA within a few percent of the synchronous baseline."""
    wagma = _run_sim("wagma")
    allr = _run_sim("allreduce")
    f_w = float(np.mean(wagma[-8:]))
    f_a = float(np.mean(allr[-8:]))
    assert wagma[-1] < wagma[0] * 0.8
    assert f_w <= f_a * 1.06, (f_w, f_a)


def test_trainer_driver_end_to_end():
    """Single-device Trainer path (mesh 1x1): compiled-variant cache,
    metrics, consolidation."""
    from repro.launch.train import Trainer
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("qwen3-0.6b", smoke=True)
    tr = Trainer(cfg, mesh, averager="wagma", group_size=1, tau=3,
                 learning_rate=0.3, seq_len=32, global_batch=4)
    hist = tr.run(6, log_every=0)
    assert len(hist) == 6 and np.isfinite(hist).all()
    cons = tr.consolidated()
    assert jax.tree.leaves(cons)[0].ndim == \
        jax.tree.leaves(tr.params)[0].ndim - 1


def test_serving_greedy_decode_deterministic():
    from repro.serve import build_serve_step
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("tinyllama-1.1b", smoke=True)
    model = build_model(cfg)
    with compat.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
        _, caches = jax.jit(lambda p, b: model.prefill(p, b, 16))(
            params, {"tokens": prompt})
        serve = build_serve_step(model, mesh)
        caches2 = jax.tree.map(jnp.copy, caches)
        tok = jnp.zeros((2, 1), jnp.int32)
        t1, _, _ = serve(params, caches, tok, jnp.asarray(8))
        t2, _, _ = serve(params, caches2, tok, jnp.asarray(8))
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
        assert (np.asarray(t1) < cfg.vocab).all()
