"""The yardstick: chip peaks, the FLOP conventions and kernel work, from
shapes.

Everything a per-layer metric divides by lives here or in the family's
reference module, with its source, so that no change to the program can
move it.

Conventions (each family module's ``fwd_flops`` keeps them):

* Model FLOPs count one multiply-add as 2 FLOPs and only the matrix
  products the architecture requires (projections, attention scores and
  values, MLP, unembedding).  A training step is 3x the forward (forward,
  and the two products of the backward).  Recomputation (remat) is not
  counted.
* Causal self-attention counts the mean attended context, (S + 1) / 2 keys
  per query; non-causal attention (encoder, cross-attention) counts every
  key.  This is the work the model needs, not what the blocked attention
  path happens to visit.
* The unembedding counts the published vocabulary, not the padded table.

A kernel's roofline time is the larger of its FLOPs over the chip's peak
and its bytes over the HBM bandwidth (``roofline_s``), of the least work
its layer needs (``attention_pass``), however an implementation splits it
into calls; ``scopes.roofline_share`` divides it by the device time of the
ops under the kernel's scope.
"""

from __future__ import annotations

import importlib
from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float        # dense bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # bytes/s of one inter-chip link
    hbm_bytes: int


# Keyed by jax.Device.device_kind.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                         hbm_bytes=16 * 2**30),
}
PEAKS_SOURCE = ('Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip (4 links)')


def peaks(device_kind: str) -> Peaks:
    """Published peaks of ``device_kind``; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None


def attn_proj_flops(d, h, kh, hd):
    """FLOPs per token of the q, k, v and o projections."""
    return 2 * (d * h * hd + 2 * d * kh * hd + h * hd * d)


def train_flops_per_step(c: dict, traffic: dict) -> float:
    """Model FLOPs of one training step over all workers (3x the forward).

    The forward's count is the family's: ``fwd_flops(spec, traffic)`` of
    ``bench/reference/<spec["reference"]>.py``, over every row of a global
    step."""
    name = f"bench.reference.{c['reference']}"
    fwd_flops = getattr(importlib.import_module(name), "fwd_flops", None)
    if fwd_flops is None:
        raise ValueError(f"{name} has no fwd_flops(spec, traffic): no FLOP "
                         f"count for family {c['family']!r}")
    return 3.0 * fwd_flops(c, traffic)


# -- kernels -------------------------------------------------------------------

def roofline_s(flops: float, nbytes: float, p: Peaks) -> float:
    """The least time the chip could take for ``flops`` and ``nbytes``."""
    return max(flops / p.flops, nbytes / p.hbm_bw)


# the least matrix products over the attended pairs of one attention pass:
# the forward (or its recompute) QK^T and PV; the backward QK^T (for P),
# dO V^T, P^T dO (dV), dS K (dQ) and dS^T Q (dK)
ATTENTION_MATMULS = {"fwd": 2, "bwd": 5}
ATTENTION_ITEMSIZE = 2     # q, k, v, o and their gradients in bfloat16


def attention_pass(kind: str, batch: int, seq: int, heads: int,
                   kv_heads: int, head_dim: int) -> tuple:
    """(FLOPs, bytes) of one causal self-attention pass (``fwd`` or
    ``bwd``) over ``batch`` rows: its matrix products over the S (S + 1) / 2
    attended pairs per head alone, and each tensor it must read or write
    once: the forward q, k, v -> o and the float32 logsumexp; the backward
    q, k, v, o, dO and the logsumexp -> dq, dk, dv."""
    pairs = seq * (seq + 1) // 2
    flops = 2 * ATTENTION_MATMULS[kind] * pairs * head_dim * heads * batch
    q = batch * seq * heads * head_dim * ATTENTION_ITEMSIZE   # also o, dO, dq
    kv = batch * seq * kv_heads * head_dim * ATTENTION_ITEMSIZE  # k, v, dk, dv
    lse = batch * heads * seq * 4
    nbytes = {"fwd": 2 * q + 2 * kv + lse,
              "bwd": 4 * q + 4 * kv + lse}[kind]
    return flops, nbytes
