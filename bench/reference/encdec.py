"""Plain float32 reference of the encoder-decoder Transformer.

Encoder: source embedding plus learned positions, then per layer
pre-LayerNorm, non-causal self-attention with RoPE, residual, LayerNorm,
ReLU MLP, residual; a final LayerNorm.  Decoder: target embedding, then
per layer pre-LayerNorm, causal self-attention with RoPE, residual,
LayerNorm, cross-attention over the encoder output (no RoPE), residual,
LayerNorm, ReLU MLP, residual; a final LayerNorm and the unembedding tied
to the target embedding, over every row of the (padded) table.  The loss is the mean next-token cross-entropy
over every target position.

The parameter tree is the program's layout, built here from the
configuration's sizes (``shapes``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import yardstick
from bench.reference import common as C

# the cut of a cell of this family to a size a CPU test run can hold:
# the configuration's sizes, the program's fields and the traffic's
TINY = {
    "spec": dict(d_model=64, d_ff=128, num_heads=4, d_k=16, d_v=16,
                 vocab_size=500, embedding_rows=512, num_encoder_layers=2,
                 num_decoder_layers=2),
    "program": dict(d_model=64, d_ff=128, n_layers=2, encoder_layers=2,
                    n_heads=4, n_kv_heads=4, head_dim=16, vocab=500),
    "traffic": dict(seq_len=16, source_len=16, batch_per_worker=4,
                    distinct_batches=4),
}


def shapes(spec: dict, dtype=jnp.bfloat16):
    d, h, hd, ff = spec["d_model"], spec["num_heads"], spec["d_k"], \
        spec["d_ff"]
    v = spec["embedding_rows"]
    sds = lambda *s: jax.ShapeDtypeStruct(s, dtype)

    def norm(n):
        return {"scale": sds(*n, d), "bias": sds(*n, d)}

    def attn(n):
        return {"wq": sds(*n, d, h * hd), "wk": sds(*n, d, h * hd),
                "wv": sds(*n, d, h * hd), "wo": sds(*n, h * hd, d)}

    def layer(n):
        return {"ln1": norm(n), "ln2": norm(n), "attn": attn(n),
                "mlp": {"w1": sds(*n, d, ff), "w2": sds(*n, ff, d)}}

    ne, nd = (spec["num_encoder_layers"],), (spec["num_decoder_layers"],)
    dec = layer(nd)
    dec["cross"] = attn(nd)
    dec["ln_x"] = norm(nd)
    return {"enc_blocks": layer(ne), "dec_blocks": dec,
            "emb": sds(v, d), "enc_pos": sds(spec["encoder_positions"], d),
            "ln_enc": norm(()), "ln_f": norm(()), "src_emb": sds(v, d)}


def _self_block(spec, mm, x, p, causal):
    eps, h, hd = spec["layer_norm_eps"], spec["num_heads"], spec["d_k"]
    s = x.shape[0]
    a = C.layer_norm(x, p["ln1"], eps)
    q = C.rope(mm(a, p["attn"]["wq"]).reshape(s, h, hd), spec["rope_theta"])
    k = C.rope(mm(a, p["attn"]["wk"]).reshape(s, h, hd), spec["rope_theta"])
    v = mm(a, p["attn"]["wv"]).reshape(s, h, hd)
    o = C.attention(mm, q, k, v, causal=causal).reshape(s, h * hd)
    return x + mm(o, p["attn"]["wo"])


def _mlp(spec, mm, x, p):
    m = C.layer_norm(x, p["ln2"], spec["layer_norm_eps"])
    return x + mm(jax.nn.relu(mm(m, p["mlp"]["w1"])), p["mlp"]["w2"])


def row_nll_sum(spec, mm, params, src, tokens, labels):
    h, hd, eps = spec["num_heads"], spec["d_k"], spec["layer_norm_eps"]
    e = params["src_emb"][src] + params["enc_pos"][:src.shape[0]]

    def enc_layer(x, p):
        return _mlp(spec, mm, _self_block(spec, mm, x, p, False), p), None

    e, _ = jax.lax.scan(enc_layer, e, params["enc_blocks"])
    e = C.layer_norm(e, params["ln_enc"], eps)

    def dec_layer(x, p):
        x = _self_block(spec, mm, x, p, True)
        a = C.layer_norm(x, p["ln_x"], eps)
        s, f = x.shape[0], e.shape[0]
        q = mm(a, p["cross"]["wq"]).reshape(s, h, hd)
        k = mm(e, p["cross"]["wk"]).reshape(f, h, hd)
        v = mm(e, p["cross"]["wv"]).reshape(f, h, hd)
        o = C.attention(mm, q, k, v, causal=False).reshape(s, h * hd)
        x = x + mm(o, p["cross"]["wo"])
        return _mlp(spec, mm, x, p), None

    x, _ = jax.lax.scan(dec_layer, params["emb"][tokens],
                        params["dec_blocks"])
    x = C.layer_norm(x, params["ln_f"], eps)
    return C.nll_sum(mm, x, params["emb"], labels, chunk=x.shape[0])


def loss_and_grad(spec, mm, params, batch):
    """Mean NLL over every target position of ``batch`` and its gradient."""
    n_tok = batch["tokens"].size

    def total(p):
        per_row = jax.vmap(
            lambda s, t, l: row_nll_sum(spec, mm, p, s, t, l))(
                batch["src"], batch["tokens"], batch["labels"])
        return per_row.sum() / n_tok

    return jax.value_and_grad(total)(params)


def pair_fwd_flops(c: dict, tgt_len: int, src_len: int) -> float:
    """One (source, target) pair (``bench/yardstick.py``'s conventions)."""
    d, h, hd, ff, v = (c["d_model"], c["num_heads"], c["d_k"], c["d_ff"],
                       c["vocab_size"])
    proj = yardstick.attn_proj_flops(d, h, h, hd)
    mlp = 2 * 2 * d * ff
    enc_tok = (proj + 2 * 2 * src_len * h * hd + mlp)
    cross_kv_tok = 2 * 2 * d * h * hd           # per source token, per layer
    dec_tok = (proj + 2 * 2 * ((tgt_len + 1) / 2) * h * hd
               + 2 * 2 * d * h * hd + 2 * 2 * src_len * h * hd + mlp)
    enc = src_len * (c["num_encoder_layers"] * enc_tok
                     + c["num_decoder_layers"] * cross_kv_tok)
    dec = tgt_len * (c["num_decoder_layers"] * dec_tok + 2 * d * v)
    return enc + dec


def fwd_flops(spec: dict, traffic: dict) -> float:
    """Model FLOPs of the forward over every pair of a global step."""
    rows = traffic["batch_per_worker"] * traffic["workers"]
    return rows * pair_fwd_flops(spec, traffic["seq_len"],
                                 traffic["source_len"])
