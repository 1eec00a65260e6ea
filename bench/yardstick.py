"""The yardstick: chip peaks and model FLOPs, from shapes.

Everything a per-layer metric divides by lives here, with its source, so
that no change to the program can move it.

Conventions:

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
"""

from __future__ import annotations

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


def _attn_proj(d, h, kh, hd):
    """FLOPs per token of the q, k, v and o projections."""
    return 2 * (d * h * hd + 2 * d * kh * hd + h * hd * d)


def decoder_fwd_flops_per_token(c: dict, seq_len: int) -> float:
    """Dense decoder (qwen3 family), one token of a causal sequence."""
    d, h, kh = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, ff, v = c["head_dim"], c["intermediate_size"], c["vocab_size"]
    score = 2 * 2 * ((seq_len + 1) / 2) * h * hd
    mlp = 2 * d * ff * (3 if c["hidden_act"] == "silu" else 2)
    return c["num_hidden_layers"] * (_attn_proj(d, h, kh, hd) + score + mlp) \
        + 2 * d * v


def encdec_fwd_flops(c: dict, tgt_len: int, src_len: int) -> float:
    """Encoder-decoder, one (source, target) pair."""
    d, h, hd, ff, v = (c["d_model"], c["num_heads"], c["d_k"], c["d_ff"],
                       c["vocab_size"])
    mlp = 2 * 2 * d * ff
    enc_tok = (_attn_proj(d, h, h, hd) + 2 * 2 * src_len * h * hd + mlp)
    cross_kv_tok = 2 * 2 * d * h * hd           # per source token, per layer
    dec_tok = (_attn_proj(d, h, h, hd) + 2 * 2 * ((tgt_len + 1) / 2) * h * hd
               + 2 * 2 * d * h * hd + 2 * 2 * src_len * h * hd + mlp)
    enc = src_len * (c["num_encoder_layers"] * enc_tok
                     + c["num_decoder_layers"] * cross_kv_tok)
    dec = tgt_len * (c["num_decoder_layers"] * dec_tok + 2 * d * v)
    return enc + dec


def train_flops_per_step(c: dict, traffic: dict) -> float:
    """Model FLOPs of one training step over all workers (3x the forward)."""
    rows = traffic["batch_per_worker"] * traffic["workers"]
    if c["family"] == "decoder":
        fwd = rows * traffic["seq_len"] * decoder_fwd_flops_per_token(
            c, traffic["seq_len"])
    elif c["family"] == "encdec":
        fwd = rows * encdec_fwd_flops(c, traffic["seq_len"],
                                      traffic["source_len"])
    else:
        raise ValueError(f"no FLOP count for family {c['family']!r}")
    return 3.0 * fwd

