"""Dense decoder-only transformer family.

Covers qwen3 (qk-norm GQA), starcoder2 (LN + plain-gelu MLP), tinyllama,
gemma3 (5-local:1-global sliding-window pattern), the internvl2 language
backbone, and the uniform-`swa` long-context variants.

Layer stacks compile as ``lax.scan`` over *super-blocks* so the HLO stays
compact on 61-layer models:

    local_per_global == 0, no window  -> super-block = 1 global layer
    sliding_window, local_per_global==0 -> super-block = 1 windowed layer
    local_per_global == k             -> super-block = k windowed + 1 global

The module exposes three entry points used by train/serve:
    init_params(cfg, key)
    forward(cfg, params, tokens, prefix_embeds=None) -> logits
    prefill(cfg, params, tokens)  -> (last_logits, caches)
    decode_step(cfg, params, caches, token, pos) -> (logits, caches)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common as cm


# ---------------------------------------------------------------------------
# Structure helpers
# ---------------------------------------------------------------------------

def superblock_layout(cfg):
    """(n_superblocks, locals_per_block, has_global) covering cfg.n_layers."""
    if cfg.local_per_global > 0:
        k = cfg.local_per_global
        assert cfg.n_layers % (k + 1) == 0, (cfg.n_layers, k)
        return cfg.n_layers // (k + 1), k, True
    if cfg.sliding_window is not None:
        return cfg.n_layers, 1, False       # uniform windowed
    return cfg.n_layers, 0, True            # uniform global


def norm_apply(cfg, x, p):
    if cfg.norm == "ln":
        x32 = x.astype(jnp.float32)
        mu = x32.mean(-1, keepdims=True)
        var = x32.var(-1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        return (out * (1.0 + p["scale"].astype(jnp.float32))
                + p["bias"].astype(jnp.float32)).astype(x.dtype)
    return cm.rms_norm(x, p["scale"], cfg.norm_eps)


def _norm_init(cfg, d, dtype):
    p = {"scale": jnp.zeros((d,), dtype)}
    if cfg.norm == "ln":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_layer(cfg, key, dtype):
    d, h, kh, hd, ff = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff
    ks = cm.split(key, 8)
    p = {
        "ln1": _norm_init(cfg, d, dtype),
        "ln2": _norm_init(cfg, d, dtype),
        "attn": {
            "wq": cm.dense_init(ks[0], d, h * hd, dtype),
            "wk": cm.dense_init(ks[1], d, kh * hd, dtype),
            "wv": cm.dense_init(ks[2], d, kh * hd, dtype),
            "wo": cm.dense_init(ks[3], h * hd, d, dtype),
        },
        "mlp": {
            "w1": cm.dense_init(ks[4], d, ff, dtype),
            "w2": cm.dense_init(ks[5], ff, d, dtype),
        },
    }
    if cfg.gated_mlp:
        p["mlp"]["w3"] = cm.dense_init(ks[6], d, ff, dtype)
    if cfg.qk_norm:
        p["attn"]["q_norm"] = jnp.zeros((hd,), dtype)
        p["attn"]["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    n_sb, n_local, has_global = superblock_layout(cfg)
    keys = cm.split(key, 4)

    def stack_layers(key, n):
        return jax.vmap(lambda k: init_layer(cfg, k, dtype))(cm.split(key, n))

    blocks = {}
    if n_local:
        # (n_sb, n_local, ...) stacked local layers
        blocks["local"] = jax.vmap(
            lambda k: stack_layers(k, n_local))(cm.split(keys[0], n_sb))
    if has_global:
        blocks["global"] = stack_layers(keys[1], n_sb)

    params = {
        "emb": cm.embed_init(keys[2], cfg.vocab_padded, cfg.d_model, dtype),
        "blocks": blocks,
        "ln_f": _norm_init(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.embed_init(keys[3], cfg.vocab_padded, cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# Layer compute
# ---------------------------------------------------------------------------

def _qkv(cfg, p, h):
    b, s, d = h.shape
    q = (h @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.hd)
    k = (h @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = (h @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    q = cm.wsc(q, None, None, "model", None)   # head-sharded (Megatron col.)
    k = cm.wsc(k, None, None, "model", None)
    v = cm.wsc(v, None, None, "model", None)
    if cfg.qk_norm:
        q = cm.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = cm.rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_layer(cfg, p, x, positions, window: Optional[int]):
    # the scope names the block's ops in the trace (model.attention_ms)
    with jax.named_scope("attention"):
        h = norm_apply(cfg, x, p["ln1"])
        q, k, v = _qkv(cfg, p["attn"], h)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
        out = cm.attention(q, k, v, causal=cfg.causal, window=window,
                           block_q=cfg.attn_block_q,
                           block_k=cfg.attn_block_k)
        b, s = x.shape[:2]
        x = x + out.reshape(b, s, -1) @ p["attn"]["wo"]
    x = cm.wsc(x, None, None, None)          # replicated between blocks
    x = x + mlp(cfg, p["mlp"], norm_apply(cfg, x, p["ln2"]))
    x = cm.wsc(x, None, None, None)
    return x


def mlp(cfg, p, h):
    act = cm.act_fn(cfg.act)
    if cfg.gated_mlp:
        return (act(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]
    return act(h @ p["w1"]) @ p["w2"]


def _superblock(cfg, bp, x, positions, n_local, has_global):
    if n_local:
        def local_body(x, lp):
            return attn_layer(cfg, lp, x, positions, cfg.sliding_window), None
        x, _ = jax.lax.scan(local_body, x, bp["local"])
    if has_global:
        x = attn_layer(cfg, bp["global"], x, positions, None)
    return x


# ---------------------------------------------------------------------------
# Forward (train / scoring)
# ---------------------------------------------------------------------------

def embed(cfg, params, tokens):
    x = params["emb"][tokens]
    if cfg.emb_scale:
        x = x * jnp.asarray(jnp.sqrt(float(cfg.d_model)), x.dtype)
    return x


def unembed(cfg, params, x):
    table = params.get("lm_head", params["emb"])
    logits = jnp.einsum("bsd,vd->bsv", x, table)
    return cm.wsc(logits, None, None, "model")


def forward(cfg, params, tokens, prefix_embeds=None, remat: bool = True,
            return_hidden: bool = False):
    """tokens (B,S) -> logits (B,S',V); prefix_embeds (B,Np,d) prepended.
    return_hidden=True returns the final-norm hidden states instead of
    logits (the chunked-CE loss path unembeds per sequence chunk)."""
    x = embed(cfg, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    n_sb, n_local, has_global = superblock_layout(cfg)

    body = functools.partial(_superblock, cfg, n_local=n_local,
                             has_global=has_global)
    if remat:
        body = jax.remat(body, static_argnums=())

    def scan_body(x, bp):
        return body(bp, x, positions), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    x = norm_apply(cfg, x, params["ln_f"])
    if return_hidden:
        return x
    return unembed(cfg, params, x)


# ---------------------------------------------------------------------------
# Layered decomposition (layer-streamed FSDP execution, DESIGN.md §11)
# ---------------------------------------------------------------------------

def split_layered(cfg, params):
    """Full param tree -> ``{"stem", "layers", "head"}`` (pure slicing).

    One span per superblock — the same unit ``forward``'s scan consumes —
    so ``span_apply(k, ...)`` composed over k reproduces the scan exactly.
    Exact inverse of :func:`merge_layered`.
    """
    n_sb, _, _ = superblock_layout(cfg)
    spans = tuple(jax.tree.map(lambda a: a[k], params["blocks"])
                  for k in range(n_sb))
    head = {"ln_f": params["ln_f"]}
    if "lm_head" in params:
        head["lm_head"] = params["lm_head"]
    return {"stem": {"emb": params["emb"]}, "layers": spans, "head": head}


def merge_layered(cfg, layered):
    """``{"stem", "layers", "head"}`` -> the canonical stacked param tree."""
    blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *layered["layers"])
    params = {"emb": layered["stem"]["emb"], "blocks": blocks,
              "ln_f": layered["head"]["ln_f"]}
    if "lm_head" in layered["head"]:
        params["lm_head"] = layered["head"]["lm_head"]
    return params


def stem_apply(cfg, stem, tokens, prefix_embeds=None):
    """Embedding stem: tokens -> (x, positions) — ``forward``'s prologue."""
    x = embed(cfg, {"emb": stem["emb"]}, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return x, positions


def span_apply(cfg, span_params, x, positions, remat: bool = True):
    """Apply ONE superblock — the body ``forward``'s scan runs per slice.

    The streamed engine threads the train step's ``remat`` flag through to
    its backward per-span VJPs: remat does not change values, but it DOES
    change which fused reductions XLA emits for the parameter gradients
    (probed: ~1e-6 drift on qk-norm/w* grads remat vs not), so streamed
    bwd must remat exactly when the gather-all reference path
    (``model.loss(remat=True)``'s scan body) does to stay bit-identical.
    """
    n_sb, n_local, has_global = superblock_layout(cfg)
    body = functools.partial(_superblock, cfg, n_local=n_local,
                             has_global=has_global)
    if remat:
        body = jax.remat(body, static_argnums=())
    return body(span_params, x, positions)


def head_params_for_unembed(stem, head):
    """Pseudo param tree :func:`unembed` reads (tied or explicit lm_head)."""
    up = {"emb": stem["emb"]}
    if "lm_head" in head:
        up["lm_head"] = head["lm_head"]
    return up


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with KV caches
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int):
    """Per-superblock caches: ring buffers for local, full for global."""
    dtype = jnp.dtype(cfg.dtype)
    n_sb, n_local, has_global = superblock_layout(cfg)
    caches = {}
    if n_local:
        w = min(cfg.sliding_window, max_len)
        caches["local"] = cm.init_kv_cache(
            n_sb * n_local, batch, w, cfg.n_kv_heads, cfg.hd, dtype)
        caches["local"] = jax.tree.map(
            lambda a: a.reshape((n_sb, n_local) + a.shape[1:]), caches["local"])
    if has_global:
        caches["global"] = cm.init_kv_cache(
            n_sb, batch, max_len, cfg.n_kv_heads, cfg.hd, dtype)
    return caches


def _decode_layer(cfg, p, x, ck, cv, pos, window: Optional[int]):
    """One decode layer; x (B,1,d); cache (B,S,KH,hd). Returns x, ck, cv."""
    h = norm_apply(cfg, x, p["ln1"])
    q, k, v = _qkv(cfg, p["attn"], h)
    b = x.shape[0]
    posv = jnp.broadcast_to(pos[None], (b, 1)) if jnp.ndim(pos) == 0 else pos
    q = cm.apply_rope(q, posv, cfg.rope_theta)
    k = cm.apply_rope(k, posv, cfg.rope_theta)
    ring = window is not None
    ck, cv = cm.cache_update(ck, cv, k, v, pos, ring=ring)
    length = jnp.minimum(pos + 1, ck.shape[1])
    out = cm.decode_attention(q, ck, cv, length=length, window=window)
    x = x + out.reshape(b, 1, -1) @ p["attn"]["wo"]
    x = x + mlp(cfg, p["mlp"], norm_apply(cfg, x, p["ln2"]))
    return x, ck, cv


def decode_step(cfg, params, caches, token, pos, prefix_embeds=None):
    """token (B,1) int, pos scalar int -> (logits (B,1,V), caches)."""
    x = embed(cfg, params, token)
    n_sb, n_local, has_global = superblock_layout(cfg)

    def sb_body(x, inputs):
        bp, cache = inputs
        new_cache = {}
        if n_local:
            def loc(xc, args):
                lp, lck, lcv = args
                x, ck, cv = _decode_layer(cfg, lp, xc, lck, lcv, pos,
                                          cfg.sliding_window)
                return x, (ck, cv)
            x, (lk, lv) = jax.lax.scan(
                loc, x, (bp["local"], cache["local"]["k"], cache["local"]["v"]))
            new_cache["local"] = {"k": lk, "v": lv}
        if has_global:
            x, gk, gv = _decode_layer(cfg, bp["global"], x,
                                      cache["global"]["k"], cache["global"]["v"],
                                      pos, None)
            new_cache["global"] = {"k": gk, "v": gv}
        return x, new_cache

    x, new_caches = jax.lax.scan(sb_body, x, (params["blocks"], caches))
    x = norm_apply(cfg, x, params["ln_f"])
    return unembed(cfg, params, x), new_caches


def prefill(cfg, params, tokens, max_len: Optional[int] = None,
            prefix_embeds=None, remat: bool = True):
    """Fill caches for tokens (B,S); returns (last-token logits, caches).

    Runs the blocked forward while capturing each layer's K/V (the cache is
    the product of prefill). Local layers keep only the trailing window.
    """
    x = embed(cfg, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    max_len = max_len or s
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    n_sb, n_local, has_global = superblock_layout(cfg)

    def capture_layer(p, x, window):
        h = norm_apply(cfg, x, p["ln1"])
        q, k, v = _qkv(cfg, p["attn"], h)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
        out = cm.attention(q, k, v, causal=True, window=window,
                           block_q=cfg.attn_block_q,
                           block_k=cfg.attn_block_k)
        x = x + out.reshape(b, s, -1) @ p["attn"]["wo"]
        x = x + mlp(cfg, p["mlp"], norm_apply(cfg, x, p["ln2"]))
        if window is not None:
            w = min(window, max_len)
            # ring order: slot j holds the latest position p with p % w == j,
            # i.e. p_j = s-1 - ((s-1-j) % w); slots without a position yet
            # (s < w) are zeroed and masked by `length` during decode.
            j = jnp.arange(w)
            p_j = (s - 1) - ((s - 1 - j) % w)
            valid = (p_j >= 0)[None, :, None, None]
            kw = jnp.where(valid, jnp.take(k, jnp.clip(p_j, 0, s - 1), axis=1), 0)
            vw = jnp.where(valid, jnp.take(v, jnp.clip(p_j, 0, s - 1), axis=1), 0)
            return x, kw, vw
        if max_len > s:
            pad = ((0, 0), (0, max_len - s), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        return x, k, v

    body = jax.remat(capture_layer, static_argnums=(2,)) if remat else capture_layer

    def sb_body(x, bp):
        cache = {}
        if n_local:
            def loc(xc, lp):
                x, kw, vw = body(lp, xc, cfg.sliding_window)
                return x, {"k": kw, "v": vw}
            x, cache["local"] = jax.lax.scan(loc, x, bp["local"])
        if has_global:
            x, gk, gv = body(bp["global"], x, None)
            cache["global"] = {"k": gk, "v": gv}
        return x, cache

    x, caches = jax.lax.scan(sb_body, x, params["blocks"])
    x = norm_apply(cfg, x, params["ln_f"])
    logits = unembed(cfg, params, x[:, -1:])
    return logits, caches
