"""Analytic FLOP model validated against XLA cost_analysis.

XLA counts a scan body once, so validation uses n_layers small enough that
the layer scan has trip count 1 (exact) and checks the analytic per-token
forward FLOPs against the compiled forward within tolerance (XLA adds
elementwise/softmax flops the matmul-level model ignores).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs.base import InputShape, ModelConfig
from repro.core import group_allreduce as ga
from repro.launch.costmodel import (averaging_comm_cost, decode_cost,
                                    fwd_flops_per_token, param_count,
                                    train_cost)
from repro.models.registry import build_model


def one_layer_cfg(**kw):
    base = dict(name="cm-test", family="dense", n_layers=1, d_model=128,
                n_heads=4, n_kv_heads=2, d_ff=512, vocab=512,
                dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("kw", [
    {},                                     # dense gated
    {"gated_mlp": False, "act": "gelu"},    # starcoder-style
    {"n_heads": 8, "n_kv_heads": 8},        # MHA
])
def test_dense_fwd_flops_vs_xla(kw):
    cfg = one_layer_cfg(**kw)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 64
    toks = jnp.zeros((B, S), jnp.int32)

    def fwd(p):
        return model.forward(p, {"tokens": toks}, remat=False)[0]

    ca = compat.cost_analysis(jax.jit(fwd).lower(params).compile())
    xla = ca["flops"]
    analytic = sum(fwd_flops_per_token(cfg, S).values()) * B * S
    # analytic counts matmuls only; XLA adds elementwise — expect within 35%
    assert 0.6 < analytic / xla < 1.35, (analytic, xla)


def test_param_count_matches_init():
    for arch_kw in [
        {},
        {"family": "moe", "n_experts": 4, "top_k": 2, "shared_expert": True,
         "first_dense": 1, "n_layers": 3},
    ]:
        cfg = one_layer_cfg(**arch_kw)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        est, _ = param_count(cfg)
        # vocab padding + norm scales are not in the estimate: within 12%
        assert abs(est - actual) / actual < 0.12, (cfg.family, est, actual)


def test_moe_active_params_scale_with_topk():
    cfg = one_layer_cfg(family="moe", n_layers=4, n_experts=8, top_k=2)
    total, active = param_count(cfg)
    assert active < total
    cfg2 = cfg.variant(top_k=4)
    _, active2 = param_count(cfg2)
    assert active2 > active


def test_train_cost_decomposition():
    cfg = one_layer_cfg(n_layers=12)
    shape = InputShape("t", 4096, 256, "train")
    rep = train_cost(cfg, shape, n_dp=16, n_model=16)
    assert rep.flops_per_device > 0 and rep.hbm_bytes_per_device > 0
    # remat multiplies forward by ~4/3 over no-remat
    rep2 = train_cost(cfg, shape, n_dp=16, n_model=16, remat=False)
    assert rep.flops_per_device > rep2.flops_per_device
    # model_flops <= hlo flops (padding/attention make HLO bigger)
    assert rep.model_flops <= rep.flops_per_device * 1.05


def test_decode_cost_cache_dominates_long_context():
    cfg = one_layer_cfg(n_layers=24, d_model=1024, n_heads=16, n_kv_heads=8,
                        d_ff=4096, vocab=32000)
    shape = InputShape("d", 32768, 128, "decode")
    rep = decode_cost(cfg, shape, n_dp=16, n_model=16)
    assert rep.breakdown["cache_read"] > 0
    # with a sliding window the cache read shrinks
    cfgw = cfg.with_sliding_window(1024)
    repw = decode_cost(cfgw, shape, n_dp=16, n_model=16)
    assert repw.breakdown["cache_read"] < rep.breakdown["cache_read"] / 4


# -- alpha-beta collective latency model -------------------------------------

def test_collective_time_alpha_beta_decomposition():
    alpha, beta = 20e-6, 1.0 / 10e9
    n_bytes, P, S = 50e6, 64, 8
    base = ga.collective_time(n_bytes, P, S, "wagma", n_buckets=1,
                              alpha=alpha, beta=beta)
    # bytes term is launch-count independent; alpha term scales linearly
    t300 = ga.collective_time(n_bytes, P, S, "wagma", n_buckets=300,
                              alpha=alpha, beta=beta)
    stages = ga.collective_stages(P, S, "wagma")
    assert stages == 3
    np.testing.assert_allclose(t300 - base, stages * 299 * alpha, rtol=1e-9)
    wire = ga.collective_bytes_per_device(n_bytes, P, S, "wagma")
    np.testing.assert_allclose(base, stages * alpha + wire * beta, rtol=1e-9)
    # zero-latency network: bucketing is a no-op in the model
    assert ga.collective_time(n_bytes, P, S, "wagma", n_buckets=300,
                              alpha=0.0, beta=beta) == \
        ga.collective_time(n_bytes, P, S, "wagma", n_buckets=1,
                           alpha=0.0, beta=beta)


def test_collective_stages_ordering():
    # group butterfly must be latency-cheaper than any global collective
    P, S = 64, 8
    assert ga.collective_stages(P, S, "wagma") < \
        ga.collective_stages(P, S, "butterfly_global") < \
        ga.collective_stages(P, S, "ring_allreduce")


def test_averaging_comm_cost_bucketing_speedup():
    cfg = one_layer_cfg(n_layers=24)
    rep = averaging_comm_cost(cfg, P=64, S=8, n_leaves=290)
    assert rep.n_buckets < rep.n_leaves
    assert rep.t_bucketed < rep.t_per_leaf
    assert rep.speedup > 1.0
    # explicit bucket count wins more with fewer buckets
    rep1 = averaging_comm_cost(cfg, P=64, S=8, n_leaves=290, n_buckets=1)
    assert rep1.t_bucketed <= rep.t_bucketed


def test_alpha_beta_overlap_variant():
    alpha, beta, gamma = 20e-6, 1e-10, 4e-12
    wire, stages = 150e6, 3
    serial = ga.alpha_beta_time(wire, stages, n_buckets=4, alpha=alpha,
                                beta=beta, gamma=gamma)
    # serial form: launches + wire + combine, additive
    np.testing.assert_allclose(
        serial, stages * 4 * alpha + wire * (beta + gamma), rtol=1e-12)
    over = ga.alpha_beta_time(wire, stages, n_buckets=4, alpha=alpha,
                              beta=beta, gamma=gamma, overlap=True)
    # overlapped: strictly cheaper with >1 bucket and a nonzero combine...
    assert over < serial
    # ...never cheaper than the pure-network time (combine can hide, wire
    # cannot), and identical when there is nothing to hide
    assert over >= ga.alpha_beta_time(wire, stages, n_buckets=4, alpha=alpha,
                                      beta=beta)
    np.testing.assert_allclose(
        ga.alpha_beta_time(wire, stages, n_buckets=1, alpha=alpha, beta=beta,
                           gamma=gamma, overlap=True),
        ga.alpha_beta_time(wire, stages, n_buckets=1, alpha=alpha, beta=beta,
                           gamma=gamma), rtol=1e-12)
    # gamma=0 keeps the classic formula under both schedules
    np.testing.assert_allclose(
        ga.alpha_beta_time(wire, stages, n_buckets=4, alpha=alpha, beta=beta,
                           overlap=True),
        ga.alpha_beta_time(wire, stages, n_buckets=4, alpha=alpha, beta=beta),
        rtol=1e-12)


def test_wagma_step_time_overlap_strictly_wins():
    kw = dict(tau=10, n_buckets=8, gamma=ga.DEFAULT_GAMMA)
    serial = ga.wagma_step_time(245e6, 64, 8, overlap=False, **kw)
    over = ga.wagma_step_time(245e6, 64, 8, overlap=True, **kw)
    assert over < serial
    # the hidden time is bounded by the group combine term
    hidden = serial - over
    group_combine = ga.collective_bytes_per_device(245e6, 64, 8, "wagma") \
        * ga.DEFAULT_GAMMA * 9 / 10
    assert hidden <= group_combine + 1e-12


def test_choose_bucket_bytes_minimises_model():
    from repro.core import bucketing
    payload = 245_000_000
    chosen = bucketing.choose_bucket_bytes(payload, P=64, S=8)
    assert chosen in bucketing.BUCKET_BYTES_CANDIDATES
    t_chosen = ga.wagma_step_time(
        payload, 64, 8, tau=10, n_buckets=max(1, -(-payload // chosen)),
        gamma=ga.DEFAULT_GAMMA, overlap=True)
    for cand in bucketing.BUCKET_BYTES_CANDIDATES:
        t = ga.wagma_step_time(
            payload, 64, 8, tau=10, n_buckets=max(1, -(-payload // cand)),
            gamma=ga.DEFAULT_GAMMA, overlap=True)
        assert t_chosen <= t + 1e-15, (chosen, cand)
    # alpha-dominated network: one huge bucket must win
    lazy = bucketing.choose_bucket_bytes(payload, P=64, S=8, alpha=10.0,
                                         beta=0.0, gamma=0.0)
    assert lazy == max(bucketing.BUCKET_BYTES_CANDIDATES)


def test_averaging_comm_cost_overlap_fields():
    from repro.core import bucketing
    # big enough that every candidate budget still yields several buckets —
    # the regime the overlap win exists in
    cfg = one_layer_cfg(n_layers=24, d_model=1024, n_heads=8, n_kv_heads=8,
                        d_ff=4096, vocab=32000)
    rep = averaging_comm_cost(cfg, P=64, S=8, n_leaves=290)
    assert rep.t_overlapped > 0
    assert rep.overlap_speedup > 1.0
    assert rep.chosen_bucket_bytes in bucketing.BUCKET_BYTES_CANDIDATES
    assert rep.n_buckets_overlapped >= 1
    # tiny payload: a single bucket, nothing to hide, speedup ~1 — the
    # report must degrade gracefully rather than promise a win
    small = averaging_comm_cost(one_layer_cfg(), P=64, S=8, n_leaves=10)
    assert small.n_buckets_overlapped == 1
    np.testing.assert_allclose(small.overlap_speedup, 1.0, rtol=1e-9)


def test_cluster_sim_overlap_win():
    import os, sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    from cluster_sim import overlap_win
    win = overlap_win(P=64, model_bytes=245e6, n_buckets=8)
    assert win["speedup"] > 1.0
    assert win["combine_hidden_s"] > 0.0
    assert win["overlapped_comm_s"] < win["serial_comm_s"]


def test_cluster_sim_bucketing_win():
    import os, sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    from cluster_sim import bucketing_win, comm_time
    win = bucketing_win(P=64, n_leaves=300, n_buckets=4)
    assert win["speedup"] > 1.0
    # same payload, fewer launches -> strictly cheaper step in the model
    assert comm_time(50e6, 64, 8, "wagma", n_buckets=4) < \
        comm_time(50e6, 64, 8, "wagma", n_buckets=300)


def test_chip_peaks_table_keyed_by_device_kind():
    """The roofline peaks come from one table keyed by ``device_kind``; a
    chip with no published entry is an error, never a v5e default."""
    from repro.launch import roofline
    from repro.launch.mesh import V5E, chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert V5E == "TPU v5 lite" and roofline.PEAKS == v5e
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                      16 * 2**30)
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")
