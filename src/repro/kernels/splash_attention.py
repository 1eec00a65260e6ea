"""Self-attention on the TPU through JAX's splash-attention Pallas kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``), for training and
prefill at long sequences.

The kernel carries its own backward: dq and dk/dv kernels behind a custom
VJP whose residuals are q, k, v, the output and the logsumexp, so no score
tensor reaches HBM.  Its mask info skips the blocks that lie wholly above the
causal diagonal in the forward and in both backward kernels.

GQA: q is grouped (B, KH, rep, S, hd) and the single-KV-head (MQA) kernel is
vmapped over batch and KV heads, so K/V are never repeated.  The MXU operands
are the inputs' dtype; accumulation and the softmax statistics are float32.

On a TPU the kernel lowers to Mosaic; off-TPU it runs in interpret mode
(``kernels/ops.py`` resolves the switch), which the CPU tests use.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as sa


@functools.lru_cache(maxsize=None)
def _mask(seq: int, rep: int, causal: bool):
    one = sa.CausalMask((seq, seq)) if causal else sa.FullMask((seq, seq))
    return sa.MultiHeadMask([one] * rep)


def _kernel(seq: int, rep: int, causal: bool, block_q: int, block_k: int,
            interpret: bool):
    """The kernel for one static shape.  The mask info is computed once per
    (mask, tiles): the mask is cached here and splash caches its processing.
    The kernel is made inside the caller's trace, so its mask-info arrays are
    that trace's constants (nothing runs eagerly, as a compile for a described
    TPU requires)."""
    sizes = sa.BlockSizes(
        block_q=block_q, block_kv=block_k, block_kv_compute=block_k,
        block_q_dkv=block_q, block_kv_dkv=block_k, block_kv_dkv_compute=block_k,
        block_q_dq=block_q, block_kv_dq=block_k)
    return sa.make_splash_mqa_single_device(
        _mask(seq, rep, causal), block_sizes=sizes, interpret=interpret)


def splash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                     block_k: int = 1024, interpret: bool = False):
    """q (B,S,H,hd), k/v (B,S,KH,hd) -> (B,S,H,hd).

    Tiles are ``block_q`` x ``block_k`` clipped to S; S must be a multiple of
    both.  Splash takes no scale: 1/sqrt(hd) is applied to q in float32
    before the cast back to q's dtype.
    """
    b, s, h, hd = q.shape
    kh = k.shape[2]
    rep = h // kh
    kernel = _kernel(s, rep, causal, min(block_q, s), min(block_k, s),
                     interpret)
    q = (q.astype(jnp.float32) * (1.0 / math.sqrt(hd))).astype(q.dtype)
    qg = q.reshape(b, s, kh, rep, hd).transpose(0, 2, 3, 1, 4)
    kg = k.transpose(0, 2, 1, 3)                        # (B,KH,S,hd)
    vg = v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kernel))(qg, kg, vg)        # (B,KH,rep,S,hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
