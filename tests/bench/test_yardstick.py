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
    assert yardstick.decoder_fwd_flops_per_token(r["spec"], 2048) == per_token
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
    assert yardstick.encdec_fwd_flops(r["spec"], 64, 64) == pair
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
