"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


# Splash attention (kernels/splash_attention.py) against the model's
# blocked_attention and the naive oracle: output and q/k/v gradients, causal
# GQA with rep 2 and hd 128, tiles clipped to S.
SPLASH_CASES = [
    # (b, s, h, kh, block_q, block_k, causal, dtype)
    (1, 256, 4, 2, 128, 256, True, jnp.float32),
    (2, 256, 4, 2, 128, 128, True, jnp.float32),
    (1, 512, 4, 2, 256, 256, True, jnp.float32),
    (1, 256, 4, 2, 512, 1024, False, jnp.float32),   # tiles clipped to S
    (1, 256, 4, 2, 128, 256, True, jnp.bfloat16),
    (2, 256, 4, 2, 128, 128, True, jnp.bfloat16),
    (1, 512, 4, 2, 256, 256, True, jnp.bfloat16),
    (2, 512, 4, 2, 256, 128, True, jnp.bfloat16),
]
# Relative error, in the norm of the whole tensor, against the float32 oracle
# on the same (rounded) inputs.  float32: accumulation order only.  bfloat16:
# the kernel's MXU operands (q scaled, p) round to bfloat16, ~2^-8 each.
SPLASH_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _out_and_grads(fn, q, k, v, cot):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * cot)
    return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", SPLASH_CASES,
                         ids=lambda c: "-".join(map(str, c[:7])) + "-"
                         + jnp.dtype(c[7]).name)
def test_splash_attention_matches_blocked_and_ref(case):
    from repro.models.common import blocked_attention
    b, s, h, kh, bq, bk, causal, dtype = case
    hd = 128
    q, k, v = (randn(b, s, n, hd, dtype=dtype) for n in (h, kh, kh))
    cot = randn(b, s, h, hd)
    tiles = dict(block_q=bq, block_k=bk)
    got = _out_and_grads(lambda q, k, v: ops.splash_attention(
        q, k, v, causal=causal, **tiles), q, k, v, cot)
    blocked = _out_and_grads(lambda q, k, v: blocked_attention(
        q, k, v, causal=causal, **tiles), q, k, v, cot)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = _out_and_grads(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, causal=causal), *f32, cot)
    tol = SPLASH_TOL[dtype]
    assert got[0].shape == q.shape and got[0].dtype == dtype
    for name, g, bl, w in zip(("out", "dq", "dk", "dv"),
                              (got[0], *got[1]), (blocked[0], *blocked[1]),
                              (want[0], *want[1])):
        assert g.shape == w.shape, name
        assert _rel_err(g, w) < tol, (name, _rel_err(g, w))
        assert _rel_err(g, bl) < tol, (name, _rel_err(g, bl))


QWEN3_Q, QWEN3_KV = (4, 2048, 16, 128), (4, 2048, 8, 128)
ROUTES = [
    # (id, q shape, k shape, kwargs, backend, mesh, path)
    ("qwen3", QWEN3_Q, QWEN3_KV, {}, "tpu", None, "kernel"),
    ("qwen3-mesh-1x1", QWEN3_Q, QWEN3_KV, {}, "tpu", ((1, 1), ("data", "model")),
     "kernel"),
    ("qwen3-cpu", QWEN3_Q, QWEN3_KV, {}, "cpu", None, "blocked"),
    ("wmt", (48, 64, 8, 64), (48, 64, 8, 64), {}, "tpu", None, "blocked"),
    ("windowed", QWEN3_Q, QWEN3_KV, {"window": 1024}, "tpu", None, "blocked"),
    ("q-offset", QWEN3_Q, QWEN3_KV, {"q_offset": 512}, "tpu", None, "blocked"),
    ("sq-ne-sk", (4, 1024, 16, 128), QWEN3_KV, {}, "tpu", None, "blocked"),
    ("model-axis-2", QWEN3_Q, QWEN3_KV, {}, "tpu",
     ((1, 2), ("data", "model")), "blocked"),
]


@pytest.mark.parametrize("case", ROUTES, ids=[r[0] for r in ROUTES])
def test_attention_path(case):
    from jax.sharding import AbstractMesh
    from repro.models.common import attention_path
    _, qs, ks, kw, backend, mesh, path = case
    mesh = None if mesh is None else AbstractMesh(*mesh)
    kw = {"window": None, "q_offset": 0, **kw}
    assert attention_path(qs, ks, block_q=512, block_k=1024, backend=backend,
                          mesh=mesh, **kw) == path


def test_attention_counts_and_runs_what_it_routed(monkeypatch):
    """On a backend named TPU the dispatcher routes a long call to the kernel
    (run here in interpret mode) and a short one to blocked_attention, counts
    each, and both agree with blocked_attention."""
    from repro.models import common as cm
    monkeypatch.setattr(ops, "_default_interpret", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = dict(cm.ATTENTION_PATHS)
    for s, path in ((512, "kernel"), (64, "blocked")):
        q, k, v = (randn(1, s, n, 128) for n in (4, 2, 2))
        out = cm.attention(q, k, v, block_q=256, block_k=512)
        grown = {p: cm.ATTENTION_PATHS[p] - before.get(p, 0)
                 for p in ("kernel", "blocked")}
        assert grown == {"kernel": 1, "blocked": int(path == "blocked")}
        want = cm.blocked_attention(q, k, v, block_q=256, block_k=512)
        assert _rel_err(out, want) < SPLASH_TOL[jnp.float32]


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 5000), inv_s=st.sampled_from([0.5, 0.25, 1 / 3.0]),
       seed=st.integers(0, 100))
def test_group_average_combine_property(n, inv_s, seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal(n), jnp.float32)
    r = jnp.asarray(rng.standard_normal(n), jnp.float32)
    out = ops.group_average_combine(w, r, inv_s)
    want = ref.group_average_ref(w, r, inv_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("shape,dtype", [
    ((3, 5), jnp.float32), ((33, 257), jnp.bfloat16), ((1,), jnp.float32),
    ((2, 3, 4, 5), jnp.float32)])
def test_group_average_combine_shapes(shape, dtype):
    w = randn(*shape, dtype=dtype)
    r = randn(*shape, dtype=dtype)
    out = ops.group_average_combine(w, r, 0.5)
    want = ref.group_average_ref(w, r, 0.5)
    assert out.shape == shape and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2)


# -- group_average_combine: the fused butterfly-combine kernel --------------
# Direct interpret-mode sweeps (no TPU needed — marked `cpu` so CI always
# runs them): non-divisible sizes exercise the lane/row padding path,
# small block_rows forces multi-block grids, bf16 checks the fp32-accumulate
# + downcast contract, and inv_s sweeps the static scale.

from repro.kernels.group_average import group_average_combine as raw_combine

COMBINE_SIZES = [1, 5, 127, 128, 129, 1000, 8 * 128, 8 * 128 + 3, 4096 + 77]


@pytest.mark.cpu
@pytest.mark.parametrize("n", COMBINE_SIZES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_group_average_combine_interpret_padding_sweep(n, dtype):
    rng = np.random.default_rng(n)
    w = jnp.asarray(rng.standard_normal(n), jnp.float32).astype(dtype)
    r = jnp.asarray(rng.standard_normal(n), jnp.float32).astype(dtype)
    out = raw_combine(w, r, 0.5, block_rows=8, interpret=True)
    want = ref.group_average_ref(w, r, 0.5)
    assert out.shape == w.shape and out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.cpu
@pytest.mark.parametrize("inv_s", [1.0, 0.5, 0.25, 1 / 3.0, 0.125])
def test_group_average_combine_inv_s_sweep(inv_s):
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.standard_normal(777), jnp.float32)
    r = jnp.asarray(rng.standard_normal(777), jnp.float32)
    out = raw_combine(w, r, inv_s, interpret=True)
    want = ref.group_average_ref(w, r, inv_s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@pytest.mark.cpu
def test_group_average_combine_fp32_accumulation_beats_bf16():
    # large + tiny in bf16: accumulating in fp32 then rounding once must
    # match the fp32 reference rounded to bf16 (the kernel's whole point)
    w = jnp.full((256,), 256.0, jnp.bfloat16)
    r = jnp.full((256,), 0.75, jnp.bfloat16)
    out = raw_combine(w, r, 0.5, interpret=True)
    want = ((jnp.asarray(w, jnp.float32) + jnp.asarray(r, jnp.float32))
            * 0.5).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.cpu
def test_group_average_combine_empty_and_nd_shapes():
    e = jnp.zeros((0, 4), jnp.float32)
    out = raw_combine(e, e, 0.5, interpret=True)
    assert out.shape == (0, 4) and out.dtype == jnp.float32
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((3, 5, 7)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((3, 5, 7)), jnp.float32)
    out = raw_combine(w, r, 0.25, block_rows=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.group_average_ref(w, r, 0.25)),
                               rtol=1e-6)


# -- group_average_combine_multi: one launch per wavefront tick -------------
# The overlapped scheduler batches independent bucket combines into a single
# pallas_call whose grid walks buckets x row-tiles; ragged (lane-unaligned)
# bucket sizes exercise the per-bucket row padding.

from repro.kernels.group_average import group_average_combine_multi

RAGGED_BATCHES = [
    [1],                          # single bucket delegates to the pair kernel
    [1, 130, 128],                # unaligned / unaligned / aligned
    [5, 127, 129, 1000, 37],      # many small ragged buckets
    [8 * 128, 3, 4096 + 77],      # one multi-block + tiny + unaligned
]


@pytest.mark.cpu
@pytest.mark.parametrize("sizes", RAGGED_BATCHES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_group_average_combine_multi_ragged(sizes, dtype):
    rng = np.random.default_rng(sum(sizes))
    ws = [jnp.asarray(rng.standard_normal(n), jnp.float32).astype(dtype)
          for n in sizes]
    rs = [jnp.asarray(rng.standard_normal(n), jnp.float32).astype(dtype)
          for n in sizes]
    outs = group_average_combine_multi(ws, rs, 0.25, block_rows=8,
                                       interpret=True)
    assert len(outs) == len(ws)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    for w, r, o in zip(ws, rs, outs):
        assert o.shape == w.shape and o.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(o, np.float32),
            np.asarray(ref.group_average_ref(w, r, 0.25), np.float32),
            rtol=tol, atol=tol)


@pytest.mark.cpu
def test_group_average_combine_multi_matches_singles_bitwise():
    # batching must not change the math: same kernel body, same fp32
    # accumulate, so each bucket's result equals its solo-launch result
    rng = np.random.default_rng(11)
    sizes = [130, 999, 128]
    ws = [jnp.asarray(rng.standard_normal(n), jnp.float32) for n in sizes]
    rs = [jnp.asarray(rng.standard_normal(n), jnp.float32) for n in sizes]
    batched = group_average_combine_multi(ws, rs, 0.5, block_rows=8,
                                          interpret=True)
    for w, r, got in zip(ws, rs, batched):
        solo = raw_combine(w, r, 0.5, block_rows=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(solo))


@pytest.mark.cpu
def test_group_average_combine_multi_rejects_mixed_dtypes():
    w32 = jnp.zeros((4,), jnp.float32)
    w16 = jnp.zeros((4,), jnp.bfloat16)
    with pytest.raises(ValueError):
        group_average_combine_multi([w32, w16], [w32, w16], 0.5,
                                    interpret=True)
    with pytest.raises(ValueError):
        group_average_combine_multi([], [], 0.5, interpret=True)


RGLRU_CASES = [
    (3, 200, 96, True), (1, 17, 130, False), (8, 128, 128, True),
    (2, 300, 64, False),
]


@pytest.mark.parametrize("case", RGLRU_CASES)
def test_rglru_scan_allclose(case):
    b, s, w, with_h0 = case
    rng = np.random.default_rng(hash(case) % 2**31)
    a = jnp.asarray(rng.uniform(0.5, 0.999, (b, s, w)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, s, w)) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((b, w)), jnp.float32) if with_h0 else None
    out = ops.rglru_scan(a, x, h0)
    want = ref.rglru_scan_ref(a, x, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_rglru_kernel_matches_model_associative_scan():
    from repro.models.rglru import rglru_scan as assoc
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.uniform(0.5, 0.99, (2, 64, 32)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 64, 32)) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((2, 32)), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.rglru_scan(a, x, h0)),
                               np.asarray(assoc(a, x, h0)),
                               rtol=1e-4, atol=1e-4)


def test_mlstm_sequential_reference_stability():
    """mLSTM oracle stays finite under extreme gate pre-activations."""
    b, s, h, dh = 1, 32, 2, 16
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    i_pre = jnp.asarray(rng.uniform(-30, 30, (b, s, h)), jnp.float32)
    f_pre = jnp.asarray(rng.uniform(-30, 30, (b, s, h)), jnp.float32)
    out = ref.mlstm_chunk_ref(q, k, v, i_pre, f_pre)
    assert np.isfinite(np.asarray(out)).all()
