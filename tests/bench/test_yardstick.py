"""The yardstick's FLOP and byte counts against hand counts, and no
fallback without a chip."""

import os
import subprocess
import sys

import pytest

import _paths  # noqa: F401
from _paths import ROOT
from bench import harness, yardstick

MANIFEST = harness.load_manifest()


def _cell(name):
    return harness.resolve(MANIFEST, name)


def test_qwen_flops_match_a_hand_count():
    r = _cell("qwen3-0.6b.train-2k.1chip")
    # per layer: projections 2*(1024*2048 + 2*1024*1024 + 2048*1024)
    # = 12582912; scores 4 * 1024.5 * 16 * 128 = 8392704; SwiGLU
    # 2 * 1024 * 3072 * 3 = 18874368; 28 layers; unembedding 2*1024*151936
    per_token = 28 * (12582912 + 8392704 + 18874368) + 311164928
    fam = harness.family_module(r["spec"])
    assert fam.fwd_flops_per_token(r["spec"], 2048) == per_token
    assert per_token == 1426964480
    assert yardstick.train_flops_per_step(r["spec"], r["traffic"]) == \
        3 * 4 * 2048 * per_token


def test_wmt_flops_match_a_hand_count():
    r = _cell("transformer-wmt.train.1chip")
    # target token, per decoder layer: self projections 4*2*512*512,
    # self scores 4 * 32.5 * 512, cross q and o 2*2*512*512, cross scores
    # 4 * 64 * 512, MLP 2*2*512*2048; unembedding 2 * 512 * 37000
    dec = 6 * (2097152 + 66560 + 1048576 + 131072 + 4194304) + 37888000
    # source token: encoder layer (projections, 4 * 64 * 512 scores, MLP),
    # and the cross k and v projections of every decoder layer
    enc = 6 * (2097152 + 131072 + 4194304) + 6 * 1048576
    pair = 64 * dec + 64 * enc
    fam = harness.family_module(r["spec"])
    assert fam.pair_fwd_flops(r["spec"], 64, 64) == pair
    assert yardstick.train_flops_per_step(r["spec"], r["traffic"]) == \
        3 * 48 * pair
    four = dict(r["traffic"], workers=4)
    assert yardstick.train_flops_per_step(r["spec"], four) == \
        4 * 3 * 48 * pair


def _n_params(spec):
    import numpy as np
    import jax
    fam = harness.family_module(spec)
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
        fam.shapes(spec)))


def test_qwen_at_head_dim_128_counts_596m_parameters():
    spec = _cell("qwen3-0.6b.train-2k.1chip")["spec"]
    assert spec["head_dim"] == 128
    assert _n_params(spec) == 596180992


def test_wmt_at_the_published_vocabulary_counts_its_parameters():
    spec = _cell("transformer-wmt.train.1chip")["spec"]
    assert spec["vocab_size"] == 37000
    # 6 encoder layers of 3147776 (four 512 x 512 projections, the MLP,
    # two LayerNorms of scale and bias), 6 decoder layers of 4197376 (and
    # cross-attention and a third LayerNorm), two 37120 x 512 tables (the
    # vocabulary padded to 256), 4096 x 512 encoder positions, two final
    # LayerNorms
    assert _n_params(spec) == 6 * 3147776 + 6 * 4197376 + 2 * 19005440 \
        + 2097152 + 2048
    assert _n_params(spec) == 84180992


def test_program_builds_the_benchmarks_parameter_tree():
    import jax
    from repro.models.registry import build_model
    for cell in ("qwen3-0.6b.train-2k.1chip", "transformer-wmt.train.1chip"):
        spec = _cell(cell)["spec"]
        model = build_model(harness.program_config(spec))
        prog = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        ours = harness.family_module(spec).shapes(spec)
        assert jax.tree.structure(prog) == jax.tree.structure(ours)
        for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ours)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        yardstick.peaks("TPU v99")
    assert yardstick.peaks("TPU v5 lite").flops == 197e12


def test_no_accelerator_exits_non_zero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "transformer-wmt.train.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert '"metrics"' not in p.stdout


# flops_per_step of the committed cells, as the FLOP count read them before
# it moved into the family modules
PINNED_FLOPS = {
    "qwen3-0.6b.train-2k.1chip": 35069079060480.0,
    "transformer-wmt.train.1chip": 1179100643328.0,
    "transformer-wmt.allreduce.4chip": 4716402573312.0,
}


@pytest.mark.parametrize("cell", sorted(PINNED_FLOPS))
def test_flops_per_step_is_pinned(cell):
    r = _cell(cell)
    assert yardstick.train_flops_per_step(r["spec"], r["traffic"]) == \
        PINNED_FLOPS[cell]


def test_a_family_without_a_flop_count_names_its_module():
    spec = dict(_cell("qwen3-0.6b.train-2k.1chip")["spec"],
                reference="common", family="nameless")
    with pytest.raises(ValueError, match=r"bench\.reference\.common"):
        yardstick.train_flops_per_step(
            spec, _cell("qwen3-0.6b.train-2k.1chip")["traffic"])


def test_roofline_s_takes_the_bound_that_binds():
    p = yardstick.peaks("TPU v5 lite")
    assert yardstick.roofline_s(197e12, 0, p) == pytest.approx(1.0)
    assert yardstick.roofline_s(197e9, 819e9, p) == pytest.approx(1.0)
    assert yardstick.roofline_s(0, 0, p) == 0


def test_attention_pass_counts_causal_pairs_and_each_tensor_once():
    # B 2, S 4, H 4, KH 2, hd 8: 10 causal pairs per head, 8 heads of rows
    q, kv, lse = 2 * 4 * 4 * 8 * 2, 2 * 4 * 2 * 8 * 2, 2 * 4 * 4 * 4
    # the forward: QK^T and PV; q k v -> o lse
    assert yardstick.attention_pass("fwd", 2, 4, 4, 2, 8) == \
        (2 * 2 * 10 * 8 * 4 * 2, 2 * q + 2 * kv + lse)
    # the backward: five products; q k v o dO lse -> dq dk dv
    assert yardstick.attention_pass("bwd", 2, 4, 4, 2, 8) == \
        (2 * 5 * 10 * 8 * 4 * 2, 4 * q + 4 * kv + lse)


def test_qwen_attention_kernels_are_bound_by_flops():
    r = _cell("qwen3-0.6b.train-2k.1chip")
    s, mix = r["spec"], r["traffic"]
    p = yardstick.peaks("TPU v5 lite")
    for kind in yardstick.ATTENTION_MATMULS:
        flops, nbytes = yardstick.attention_pass(
            kind, mix["batch_per_worker"], mix["seq_len"],
            s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"])
        assert flops / p.flops > 2 * nbytes / p.hbm_bw
