"""Encoder-decoder transformer: whisper-medium backbone + transformer_wmt.

Per the assignment, the whisper *modality frontend* (mel-spectrogram + conv
feature extractor) is a STUB: ``input_specs`` provides precomputed frame
embeddings (B, encoder_frames, d_model). For transformer_wmt (the paper's own
61M model) the encoder consumes source-token embeddings instead.

Decoder self-attention uses RoPE (deviation from whisper's learned positions,
noted in DESIGN.md) so decode_32k's 32k-position decoder context needs no
position table. Cross-attention K/V are computed once at prefill and cached.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import transformer as tfm


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_cross(cfg, key, dtype):
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = cm.split(key, 4)
    return {
        "wq": cm.dense_init(ks[0], d, h * hd, dtype),
        "wk": cm.dense_init(ks[1], d, kh * hd, dtype),
        "wv": cm.dense_init(ks[2], d, kh * hd, dtype),
        "wo": cm.dense_init(ks[3], h * hd, d, dtype),
    }


def init_dec_layer(cfg, key, dtype):
    k1, k2 = cm.split(key, 2)
    p = tfm.init_layer(cfg, k1, dtype)
    p["cross"] = init_cross(cfg, k2, dtype)
    p["ln_x"] = tfm._norm_init(cfg, cfg.d_model, dtype)
    return p


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    ks = cm.split(key, 6)
    enc_cfg = cfg.variant(causal=False)
    params = {
        "enc_blocks": jax.vmap(lambda k: tfm.init_layer(enc_cfg, k, dtype))(
            cm.split(ks[0], cfg.encoder_layers)),
        "dec_blocks": jax.vmap(lambda k: init_dec_layer(cfg, k, dtype))(
            cm.split(ks[1], cfg.n_layers)),
        "emb": cm.embed_init(ks[2], cfg.vocab_padded, cfg.d_model, dtype),
        "enc_pos": (jax.random.normal(ks[3], (cfg.encoder_frames or 4096,
                                               cfg.d_model), jnp.float32)
                    * 0.02).astype(dtype),
        "ln_enc": tfm._norm_init(cfg, cfg.d_model, dtype),
        "ln_f": tfm._norm_init(cfg, cfg.d_model, dtype),
    }
    if cfg.encoder_frames == 0:           # wmt: token encoder
        params["src_emb"] = cm.embed_init(ks[4], cfg.vocab_padded, cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

def encode(cfg, params, enc_input, remat: bool = True):
    """enc_input: frame embeddings (B,F,d) [audio stub] or tokens (B,F) [wmt]."""
    if enc_input.ndim == 2:
        x = params["src_emb"][enc_input]
    else:
        x = enc_input.astype(jnp.dtype(cfg.dtype))
    f = x.shape[1]
    x = x + params["enc_pos"][:f]
    positions = jnp.broadcast_to(jnp.arange(f), x.shape[:2])
    enc_cfg = cfg.variant(causal=False)

    def layer(x, p):
        return tfm.attn_layer(enc_cfg, p, x, positions, None), None

    body = jax.remat(lambda x, p: layer(x, p)) if remat else layer
    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return tfm.norm_apply(cfg, x, params["ln_enc"])


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------

def _cross_attn(cfg, p, x, enc_kv):
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.hd)
    ek, ev = enc_kv
    out = cm.attention(q, ek, ev, causal=False,
                       block_q=cfg.attn_block_q,
                       block_k=cfg.attn_block_k)
    return out.reshape(b, s, -1) @ p["wo"]


def _enc_kv(cfg, p, enc_out):
    b, f, _ = enc_out.shape
    ek = (enc_out @ p["wk"]).reshape(b, f, cfg.n_kv_heads, cfg.hd)
    ev = (enc_out @ p["wv"]).reshape(b, f, cfg.n_kv_heads, cfg.hd)
    return ek, ev


def dec_layer(cfg, p, x, positions, enc_out):
    # self- and cross-attention each under the scope that names them in
    # the trace (model.attention_ms)
    with jax.named_scope("attention"):
        h = tfm.norm_apply(cfg, x, p["ln1"])
        q, k, v = tfm._qkv(cfg, p["attn"], h)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
        out = cm.attention(q, k, v, causal=True,
                           block_q=cfg.attn_block_q,
                           block_k=cfg.attn_block_k)
        b, s = x.shape[:2]
        x = x + out.reshape(b, s, -1) @ p["attn"]["wo"]
    with jax.named_scope("attention"):
        hx = tfm.norm_apply(cfg, x, p["ln_x"])
        x = x + _cross_attn(cfg, p["cross"], hx,
                            _enc_kv(cfg, p["cross"], enc_out))
    x = x + tfm.mlp(cfg, p["mlp"], tfm.norm_apply(cfg, x, p["ln2"]))
    return x


def forward(cfg, params, tokens, enc_input=None, prefix_embeds=None,
            remat: bool = True):
    """(enc_input, dec tokens) -> decoder logits. prefix_embeds aliases
    enc_input for the uniform registry API (audio stub embeddings)."""
    enc_input = enc_input if enc_input is not None else prefix_embeds
    enc_out = encode(cfg, params, enc_input, remat=remat)
    x = tfm.embed(cfg, params, tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def layer(x, p):
        return dec_layer(cfg, p, x, positions, enc_out), None

    body = jax.remat(lambda x, p: layer(x, p)) if remat else layer
    x, _ = jax.lax.scan(body, x, params["dec_blocks"])
    x = tfm.norm_apply(cfg, x, params["ln_f"])
    return tfm.unembed(cfg, params, x), {}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    f = cfg.encoder_frames or 128
    self_cache = cm.init_kv_cache(cfg.n_layers, batch, max_len,
                                  cfg.n_kv_heads, cfg.hd, dtype)
    cross = cm.init_kv_cache(cfg.n_layers, batch, f,
                             cfg.n_kv_heads, cfg.hd, dtype)
    return {"self": self_cache, "cross": cross}


def prefill(cfg, params, tokens, enc_input=None, max_len=None,
            prefix_embeds=None, remat: bool = True):
    """Encode source, precompute cross K/V, consume prompt tokens (B,S)."""
    enc_input = enc_input if enc_input is not None else prefix_embeds
    enc_out = encode(cfg, params, enc_input, remat=remat)
    x = tfm.embed(cfg, params, tokens)
    b, s, _ = x.shape
    max_len = max_len or s
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def layer(x, p):
        h = tfm.norm_apply(cfg, x, p["ln1"])
        q, k, v = tfm._qkv(cfg, p["attn"], h)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
        out = cm.attention(q, k, v, causal=True,
                           block_q=cfg.attn_block_q,
                           block_k=cfg.attn_block_k)
        x = x + out.reshape(b, s, -1) @ p["attn"]["wo"]
        hx = tfm.norm_apply(cfg, x, p["ln_x"])
        ek, ev = _enc_kv(cfg, p["cross"], enc_out)
        x = x + _cross_attn(cfg, p["cross"], hx, (ek, ev))
        x = x + tfm.mlp(cfg, p["mlp"], tfm.norm_apply(cfg, x, p["ln2"]))
        if max_len > s:
            pad = ((0, 0), (0, max_len - s), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        return x, (k, v, ek, ev)

    body = jax.remat(layer) if remat else layer
    x, (k, v, ek, ev) = jax.lax.scan(lambda c, p: body(c, p), x,
                                     params["dec_blocks"])
    x = tfm.norm_apply(cfg, x, params["ln_f"])
    logits = tfm.unembed(cfg, params, x[:, -1:])
    return logits, {"self": {"k": k, "v": v}, "cross": {"k": ek, "v": ev}}


def decode_step(cfg, params, caches, token, pos, prefix_embeds=None):
    x = tfm.embed(cfg, params, token)
    b = x.shape[0]

    def layer(x, args):
        p, ck, cv, xk, xv = args
        h = tfm.norm_apply(cfg, x, p["ln1"])
        q, k, v = tfm._qkv(cfg, p["attn"], h)
        posv = jnp.broadcast_to(pos[None], (b, 1)) if jnp.ndim(pos) == 0 else pos
        q = cm.apply_rope(q, posv, cfg.rope_theta)
        k = cm.apply_rope(k, posv, cfg.rope_theta)
        ck, cv = cm.cache_update(ck, cv, k, v, pos)
        out = cm.decode_attention(q, ck, cv, length=pos + 1)
        x = x + out.reshape(b, 1, -1) @ p["attn"]["wo"]
        hx = tfm.norm_apply(cfg, x, p["ln_x"])
        qx = (hx @ p["cross"]["wq"]).reshape(b, 1, cfg.n_heads, cfg.hd)
        xo = cm.decode_attention(qx, xk, xv, length=xk.shape[1])
        x = x + xo.reshape(b, 1, -1) @ p["cross"]["wo"]
        x = x + tfm.mlp(cfg, p["mlp"], tfm.norm_apply(cfg, x, p["ln2"]))
        return x, (ck, cv)

    x, (ck, cv) = jax.lax.scan(
        layer, x, (params["dec_blocks"], caches["self"]["k"],
                   caches["self"]["v"], caches["cross"]["k"],
                   caches["cross"]["v"]))
    x = tfm.norm_apply(cfg, x, params["ln_f"])
    return tfm.unembed(cfg, params, x), {"self": {"k": ck, "v": cv},
                                         "cross": caches["cross"]}
