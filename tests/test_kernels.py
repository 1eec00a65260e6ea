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


ATTN_CASES = [
    # (b, sq, sk, h, kh, hd, causal, window, dtype)
    (2, 128, 128, 4, 2, 64, True, None, jnp.float32),
    (1, 256, 256, 4, 4, 32, True, 64, jnp.float32),
    (2, 100, 100, 2, 1, 64, False, None, jnp.float32),
    (1, 128, 256, 4, 2, 128, True, None, jnp.float32),
    (1, 64, 64, 2, 2, 64, True, None, jnp.bfloat16),
    (1, 72, 72, 3, 1, 48, True, 16, jnp.float32),   #非-128-aligned
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_allclose(case):
    b, sq, sk, h, kh, hd, causal, window, dtype = case
    q = randn(b, sq, h, hd, dtype=dtype)
    k = randn(b, sk, kh, hd, dtype=dtype)
    v = randn(b, sk, kh, hd, dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_matches_model_blocked_attention():
    from repro.models.common import blocked_attention
    q = randn(2, 96, 4, 64)
    k = randn(2, 96, 2, 64)
    v = randn(2, 96, 2, 64)
    for window in (None, 32):
        a = ops.flash_attention(q, k, v, causal=True, window=window,
                                block_q=32, block_k=32)
        bopt = blocked_attention(q, k, v, causal=True, window=window,
                                 block_q=32, block_k=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(bopt),
                                   rtol=2e-4, atol=2e-4)


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
