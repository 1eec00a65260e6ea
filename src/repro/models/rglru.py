"""RecurrentGemma / Griffin (arXiv:2402.19427): RG-LRU + local attention, 1:2.

26 layers, pattern (recurrent, recurrent, local-attention) x 8 + a trailing
(recurrent, recurrent) pair. Each residual block = temporal mixing + gated MLP.

RG-LRU recurrence (linear, gated):
    r_t = sigmoid(W_r u_t);  i_t = sigmoid(W_i u_t)
    a_t = exp(-c * softplus(Lambda) * r_t)              (c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * u_t)

Training uses ``jax.lax.associative_scan`` over time (the recurrence is linear
in h — O(log S) depth on TPU); decode keeps (h, conv) state — ``long_500k``
runs natively. A Pallas kernel for the scan lives in kernels/rglru_scan.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import transformer as tfm

C_FACTOR = 8.0
ATTN_WINDOW = 2048    # Griffin's local attention window


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_recurrent(cfg, key, dtype):
    d = cfg.d_model
    w = cfg.lru_width or d
    ks = cm.split(key, 7)
    return {
        "ln": {"scale": jnp.zeros((d,), dtype)},
        "w_x": cm.dense_init(ks[0], d, w, dtype),          # recurrence branch
        "w_gate": cm.dense_init(ks[1], d, w, dtype),       # gelu gate branch
        "conv_w": (jax.random.normal(ks[2], (cfg.conv_width, w), jnp.float32)
                   * 0.1).astype(dtype),
        "w_r": cm.dense_init(ks[3], w, w, dtype, scale=0.01),
        "w_i": cm.dense_init(ks[4], w, w, dtype, scale=0.01),
        "lam": jnp.linspace(0.9, 0.999, w).astype(jnp.float32),  # Lambda param
        "w_out": cm.dense_init(ks[5], w, d, dtype),
        "mlp": _mlp_init(cfg, ks[6], dtype),
    }


def _mlp_init(cfg, key, dtype):
    ks = cm.split(key, 3)
    return {
        "ln": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        "w1": cm.dense_init(ks[0], cfg.d_model, cfg.d_ff, dtype),
        "w3": cm.dense_init(ks[1], cfg.d_model, cfg.d_ff, dtype),
        "w2": cm.dense_init(ks[2], cfg.d_ff, cfg.d_model, dtype),
    }


def init_attn(cfg, key, dtype):
    k1, k2 = cm.split(key, 2)
    p = tfm.init_layer(cfg, k1, dtype)
    return p


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    n_sb = cfg.n_layers // 3                # 8 full (rec, rec, attn) blocks
    tail = cfg.n_layers - 3 * n_sb          # 2 trailing recurrent blocks
    ks = cm.split(key, 5)
    params = {
        "emb": cm.embed_init(ks[0], cfg.vocab_padded, cfg.d_model, dtype),
        "blocks": {
            "rec1": jax.vmap(lambda k: init_recurrent(cfg, k, dtype))(cm.split(ks[1], n_sb)),
            "rec2": jax.vmap(lambda k: init_recurrent(cfg, k, dtype))(cm.split(ks[2], n_sb)),
            "attn": jax.vmap(lambda k: init_attn(cfg, k, dtype))(cm.split(ks[3], n_sb)),
        },
        "ln_f": {"scale": jnp.zeros((cfg.d_model,), dtype)},
    }
    if tail:
        params["tail"] = jax.vmap(
            lambda k: init_recurrent(cfg, k, dtype))(cm.split(ks[4], tail))
    return params


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------

def _gates(p, u):
    r = jax.nn.sigmoid((u @ p["w_r"]).astype(jnp.float32))
    i = jax.nn.sigmoid((u @ p["w_i"]).astype(jnp.float32))
    log_a = -C_FACTOR * jax.nn.softplus(p["lam"]) * r       # (B,S,w) fp32
    a = jnp.exp(log_a)
    gated_in = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12, 1.0)) \
        * (i * u.astype(jnp.float32))
    return a, gated_in


def rglru_scan(a, x, h0=None):
    """h_t = a_t h_{t-1} + x_t via associative scan; a,x (B,S,w) fp32."""
    if h0 is not None:
        x = x.at[:, 0].add(a[:, 0] * h0)
    def op(ca, cb):
        a1, b1 = ca
        a2, b2 = cb
        return a1 * a2, a2 * b1 + b2
    _, h = jax.lax.associative_scan(op, (a, x), axis=1)
    return h


def conv1d_causal(u, w, state=None):
    """Depthwise causal conv, width K. u (B,S,w); state (B,K-1,w) history."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((u.shape[0], K - 1, u.shape[2]), u.dtype)
    else:
        pad = state.astype(u.dtype)
    up = jnp.concatenate([pad, u], axis=1)
    out = sum(up[:, k:k + u.shape[1]] * w[k] for k in range(K))
    new_state = up[:, -(K - 1):]
    return out, new_state


def recurrent_block(cfg, p, x, state=None):
    """state = (h (B,w) fp32, conv (B,K-1,w)) or None. Returns (x, state)."""
    h = cm.rms_norm(x, p["ln"]["scale"], cfg.norm_eps)
    gate = jax.nn.gelu(h @ p["w_gate"])
    u = h @ p["w_x"]
    h0, conv_state = (None, None) if state is None else state
    u, conv_state = conv1d_causal(u, p["conv_w"], conv_state)
    a, gin = _gates(p, u)
    hs = rglru_scan(a, gin, h0)                             # (B,S,w) fp32
    y = (hs.astype(x.dtype) * gate) @ p["w_out"]
    x = x + y
    x = x + tfm.mlp(cfg, p["mlp"], cm.rms_norm(x, p["mlp"]["ln"]["scale"],
                                               cfg.norm_eps))
    new_state = (hs[:, -1], conv_state)
    return x, new_state


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg, params, tokens, prefix_embeds=None, remat: bool = True,
            return_hidden: bool = False):
    x = tfm.embed(cfg, params, tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def sb(x, bp):
        x, _ = recurrent_block(cfg, bp["rec1"], x)
        x, _ = recurrent_block(cfg, bp["rec2"], x)
        x = tfm.attn_layer(cfg, bp["attn"], x, positions, ATTN_WINDOW)
        return x, None

    body = jax.remat(lambda c, bp: sb(c, bp)) if remat else sb
    x, _ = jax.lax.scan(body, x, params["blocks"])
    if "tail" in params:
        def tail_body(x, tp):
            x, _ = recurrent_block(cfg, tp, x)
            return x, None
        x, _ = jax.lax.scan(tail_body, x, params["tail"])
    x = cm.rms_norm(x, params["ln_f"]["scale"], cfg.norm_eps)
    if return_hidden:
        return x, {}
    return tfm.unembed(cfg, params, x), {}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int):
    n_sb = cfg.n_layers // 3
    tail = cfg.n_layers - 3 * n_sb
    w = cfg.lru_width or cfg.d_model
    K = cfg.conv_width
    dtype = jnp.dtype(cfg.dtype)

    def rec_state(n):
        return (jnp.zeros((n, batch, w), jnp.float32),
                jnp.zeros((n, batch, K - 1, w), dtype))

    win = min(ATTN_WINDOW, max_len)
    caches = {
        "rec1": rec_state(n_sb),
        "rec2": rec_state(n_sb),
        "attn": cm.init_kv_cache(n_sb, batch, win, cfg.n_kv_heads, cfg.hd, dtype),
    }
    if tail:
        caches["tail"] = rec_state(tail)
    return caches


def decode_step(cfg, params, caches, token, pos, prefix_embeds=None):
    x = tfm.embed(cfg, params, token)

    def sb(x, args):
        bp, r1, r2, ck, cv = args
        x, r1 = recurrent_block(cfg, bp["rec1"], x, state=r1)
        x, r2 = recurrent_block(cfg, bp["rec2"], x, state=r2)
        x, ck, cv = tfm._decode_layer(cfg, bp["attn"], x, ck, cv, pos,
                                      ATTN_WINDOW)
        return x, (r1, r2, ck, cv)

    x, (r1, r2, ck, cv) = jax.lax.scan(
        sb, x, (params["blocks"], caches["rec1"], caches["rec2"],
                caches["attn"]["k"], caches["attn"]["v"]))
    new = {"rec1": r1, "rec2": r2, "attn": {"k": ck, "v": cv}}
    if "tail" in params:
        def tail_body(x, args):
            tp, st = args
            x, st = recurrent_block(cfg, tp, x, state=st)
            return x, st
        x, ts = jax.lax.scan(tail_body, x, (params["tail"], caches["tail"]))
        new["tail"] = ts
    x = cm.rms_norm(x, params["ln_f"]["scale"], cfg.norm_eps)
    return tfm.unembed(cfg, params, x), new


def prefill(cfg, params, tokens, max_len=None, prefix_embeds=None,
            remat: bool = True):
    x = tfm.embed(cfg, params, tokens)
    b, s, _ = x.shape
    max_len = max_len or s
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    win = min(ATTN_WINDOW, max_len)

    def capture_attn(p, x):
        h = tfm.norm_apply(cfg, x, p["ln1"])
        q, k, v = tfm._qkv(cfg, p["attn"], h)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
        out = cm.attention(q, k, v, causal=True, window=ATTN_WINDOW,
                           block_q=cfg.attn_block_q,
                           block_k=cfg.attn_block_k)
        x = x + out.reshape(b, s, -1) @ p["attn"]["wo"]
        x = x + tfm.mlp(cfg, p["mlp"], tfm.norm_apply(cfg, x, p["ln2"]))
        j = jnp.arange(win)
        p_j = (s - 1) - ((s - 1 - j) % win)
        valid = (p_j >= 0)[None, :, None, None]
        kw = jnp.where(valid, jnp.take(k, jnp.clip(p_j, 0, s - 1), axis=1), 0)
        vw = jnp.where(valid, jnp.take(v, jnp.clip(p_j, 0, s - 1), axis=1), 0)
        return x, kw, vw

    body = jax.remat(capture_attn) if remat else capture_attn

    def sb(x, bp):
        x, r1 = recurrent_block(cfg, bp["rec1"], x)
        x, r2 = recurrent_block(cfg, bp["rec2"], x)
        x, kw, vw = body(bp["attn"], x)
        return x, (r1, r2, kw, vw)

    x, (r1, r2, kw, vw) = jax.lax.scan(sb, x, params["blocks"])
    caches = {"rec1": r1, "rec2": r2, "attn": {"k": kw, "v": vw}}
    if "tail" in params:
        def tail_body(x, tp):
            x, st = recurrent_block(cfg, tp, x)
            return x, st
        x, ts = jax.lax.scan(tail_body, x, params["tail"])
        caches["tail"] = ts
    x = cm.rms_norm(x, params["ln_f"]["scale"], cfg.norm_eps)
    return tfm.unembed(cfg, params, x[:, -1:]), caches
