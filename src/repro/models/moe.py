"""Mixture-of-Experts decoder (llama4-maverick 128e top-1, kimi-k2 384e top-8).

Expert-parallel design: expert weight tensors (E, d, ff) are sharded over the
`model` mesh axis (E/16 experts per device). Token dispatch is capacity-based
(Switch-style) but *chunked*: tokens are processed in ``cfg.moe_chunks``
sequential chunks with a running per-expert slot counter carried through a
``lax.scan``, so the dispatch one-hot and gather/scatter temporaries stay
O(T/chunks) instead of O(T). Combine gathers per top-k choice (k small,
unrolled) to avoid a (T*k, d) transient.

GSPMD turns the scatter/gather against the expert-sharded buffer into
mask+psum collectives over the model axis — the all-to-all-equivalent traffic
the paper's Table I archs pay; the §Perf log iterates on it.

Layer layout:
  llama4: moe_every=2  -> super-block = (dense layer, moe layer), scanned
  kimi:   first_dense=1 -> 1 unrolled dense layer + scan over moe layers
Both use a shared expert (always-on) added to the routed output.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.models import common as cm
from repro.models import transformer as tfm


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_moe_ffn(cfg, key, dtype):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = cm.split(key, 7)
    p = {
        "router": cm.dense_init(ks[0], d, E, jnp.float32, scale=0.02),
        "we1": jax.vmap(lambda k: cm.dense_init(k, d, ff, dtype))(cm.split(ks[1], E)),
        "we3": jax.vmap(lambda k: cm.dense_init(k, d, ff, dtype))(cm.split(ks[2], E)),
        "we2": jax.vmap(lambda k: cm.dense_init(k, ff, d, dtype))(cm.split(ks[3], E)),
    }
    if cfg.shared_expert:
        p["shared"] = {
            "w1": cm.dense_init(ks[4], d, ff, dtype),
            "w3": cm.dense_init(ks[5], d, ff, dtype),
            "w2": cm.dense_init(ks[6], ff, d, dtype),
        }
    return p


def init_moe_layer(cfg, key, dtype):
    """Attention block + MoE FFN."""
    k1, k2 = cm.split(key, 2)
    p = tfm.init_layer(cfg, k1, dtype)
    del p["mlp"]
    p["moe"] = init_moe_ffn(cfg, k2, dtype)
    return p


def init_params(cfg, key):
    dtype = jnp.dtype(cfg.dtype)
    keys = cm.split(key, 5)
    blocks = {}
    if cfg.moe_every == 2:
        n_sb = (cfg.n_layers - cfg.first_dense) // 2
        blocks["dense"] = jax.vmap(
            lambda k: tfm.init_layer(cfg, k, dtype))(cm.split(keys[0], n_sb))
        blocks["moe"] = jax.vmap(
            lambda k: init_moe_layer(cfg, k, dtype))(cm.split(keys[1], n_sb))
    else:
        n_sb = cfg.n_layers - cfg.first_dense
        blocks["moe"] = jax.vmap(
            lambda k: init_moe_layer(cfg, k, dtype))(cm.split(keys[1], n_sb))
    params = {
        "emb": cm.embed_init(keys[2], cfg.vocab_padded, cfg.d_model, dtype),
        "blocks": blocks,
        "ln_f": {"scale": jnp.zeros((cfg.d_model,), dtype)},
    }
    if cfg.first_dense:
        params["first"] = jax.vmap(
            lambda k: tfm.init_layer(cfg, k, dtype))(cm.split(keys[3], cfg.first_dense))
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.embed_init(keys[4], cfg.vocab_padded, cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# Routing + dispatch
# ---------------------------------------------------------------------------

def router_topk(cfg, logits):
    """logits (T,E) fp32 -> (idx (T,k), gate (T,k), aux losses dict)."""
    E, k = cfg.n_experts, cfg.top_k
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + router z-loss
    f = jnp.zeros((E,), jnp.float32)
    f = f.at[idx.reshape(-1)].add(1.0) / (logits.shape[0] * k)
    pmean = probs.mean(0)
    aux = {
        "load_balance": E * jnp.sum(f * pmean),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1))),
    }
    return idx, gate.astype(jnp.float32), aux


def moe_ffn_slotmap(cfg, p, h, capacity: Optional[int] = None):
    """Slot-map MoE dispatch/combine (§Perf iteration, the default).

    The onehot_scatter baseline below lets GSPMD partition token-indexed
    scatters/gathers against the expert-sharded buffer, which it lowers to
    full-buffer all-reduces and a per-layer all-gather of the expert weights
    (measured: 94 TiB/step on kimi prefill_32k). This formulation routes via
    a tiny (E, C) *slot map* of token indices instead:

      dispatch: buf[e,c,:] = x[slotmap[e,c]]      gather from the replicated
                activations — each device materialises only its local
                experts' rows: ZERO communication;
      combine:  y.at[slotmap].add(obuf * gate)    scatter-add of expert-
                sharded rows into a replicated (Tc,d) accumulator — GSPMD
                merges the per-device partials with ONE all-reduce of
                (Tc, d) per chunk, the information-theoretic floor for
                replicated-token expert parallelism.

    Capacity semantics (first-come-first-served in flat order, drops beyond
    C) are identical to the baseline, so the two paths agree numerically
    whenever nothing is dropped (pinned by tests/test_moe_impls.py).
    """
    b, s, d = h.shape
    T = b * s
    E, k = cfg.n_experts, cfg.top_k
    x = h.reshape(T, d)
    n_chunks = min(cfg.moe_chunks, T) if T >= cfg.moe_chunks else 1
    while T % n_chunks:
        n_chunks -= 1
    Tc = T // n_chunks
    if capacity is None:
        capacity = max(int(T * k / E * cfg.capacity_factor), 8)

    logits = x.astype(jnp.float32) @ p["router"]
    idx, gate, aux = router_topk(cfg, logits)

    xc = x.reshape(n_chunks, Tc, d)
    idxc = idx.reshape(n_chunks, Tc, k)
    gatec = gate.reshape(n_chunks, Tc, k)
    act = cm.act_fn(cfg.act)
    we1, we2, we3 = p["we1"], p["we2"], p["we3"]

    def chunk_body(counts, inp):
        xi, ei, gi = inp                           # (Tc,d), (Tc,k), (Tc,k)
        # --- routing bookkeeping (tiny tensors, fully replicated) ----------
        oh = jax.nn.one_hot(ei.reshape(-1), E, dtype=jnp.int32)   # (Tc*k, E)
        within = jnp.cumsum(oh, axis=0) - oh
        pos = (within * oh).sum(-1).reshape(Tc, k) + counts[ei]
        keep = pos < capacity
        posc = jnp.clip(pos, 0, capacity - 1)
        flat_tok = jnp.broadcast_to(jnp.arange(Tc)[:, None], (Tc, k))
        # slot map (E, C): token index feeding each expert slot (-1 empty)
        slot_tok = jnp.full((E, capacity), 0, jnp.int32)
        slot_val = jnp.zeros((E, capacity), jnp.float32)
        eflat = ei.reshape(-1)
        kflat = keep.reshape(-1)
        # dropped assignments get an out-of-bounds slot -> mode="drop"
        # discards them (clipping would overwrite the slot's real occupant)
        pflat = jnp.where(kflat, posc.reshape(-1), capacity)
        slot_tok = slot_tok.at[eflat, pflat].set(
            flat_tok.reshape(-1), mode="drop")
        slot_val = slot_val.at[eflat, pflat].set(
            gi.reshape(-1).astype(jnp.float32), mode="drop")
        # --- dispatch: local gather into the expert-sharded buffer ---------
        buf = xi[slot_tok] * (slot_val > 0)[..., None].astype(xi.dtype)
        buf = cm.wsc(buf, "model", None, None)
        # --- expert compute (expert-parallel) -------------------------------
        hbuf = act(jnp.einsum("ecd,edf->ecf", buf, we1)) \
            * jnp.einsum("ecd,edf->ecf", buf, we3)
        hbuf = cm.wsc(hbuf, "model", None, None)
        obuf = jnp.einsum("ecf,efd->ecd", hbuf, we2)
        obuf = cm.wsc(obuf, "model", None, None)
        # --- combine: weighted scatter-add, one psum of (Tc,d) -------------
        # keep the expert axis explicit through the scatter (flattening it
        # gave GSPMD a conflicted [8,2] update sharding -> full-buffer ARs)
        contrib = obuf.astype(jnp.float32) * slot_val[..., None]
        contrib = cm.wsc(contrib, "model", None, None)
        y = jnp.zeros((Tc, d), jnp.float32)
        y = y.at[slot_tok].add(contrib, mode="drop")
        y = cm.wsc(y, None, None)
        counts = counts + oh.sum(0)
        dropped = 1.0 - kflat.mean()
        return counts, (y, dropped)

    counts0 = jnp.zeros((E,), jnp.int32)
    _, (yc, dropc) = jax.lax.scan(chunk_body, counts0, (xc, idxc, gatec))
    out = yc.reshape(b, s, d).astype(h.dtype)

    if cfg.shared_expert:
        sp = p["shared"]
        out = out + (act(x @ sp["w1"]) * (x @ sp["w3"]) @ sp["w2"]
                     ).reshape(b, s, d)
    aux = dict(aux, dropped=dropc.mean())
    return out, aux


def _shardmap_available(cfg):
    mesh = compat.get_abstract_mesh()
    return (not mesh.empty and "model" in mesh.axis_names
            and mesh.shape["model"] > 1
            and cfg.n_experts % mesh.shape["model"] == 0)


def moe_ffn_shardmap(cfg, p, h, capacity: Optional[int] = None):
    """Expert-parallel MoE with *explicit* collectives (§Perf iterations 3-4).

    GSPMD's scatter/gather partitioning of the expert buffer produced
    full-buffer all-reduces even in the slotmap formulation (measured 8.5 TiB
    residual on kimi prefill_32k). This path nests a ``shard_map`` that is
    manual over the ``model`` axis AND over any data-parallel axes that are
    still auto (the pure-pjit serve path — in the train path they are already
    manual in the outer shard_map): routing is *per data shard* (as in
    training), each model shard gathers/computes/combines only its E/16
    experts, and the token outputs are merged with exactly ONE fp32 psum of
    (Tc_local, d) per chunk — the information-theoretic floor for
    replicated-token expert parallelism.
    """
    if not _shardmap_available(cfg):
        return moe_ffn_slotmap(cfg, p, h, capacity)
    from jax.sharding import PartitionSpec as P

    mesh = compat.get_abstract_mesh()
    types = compat.mesh_axis_types(mesh)
    b, s, d = h.shape
    T = b * s
    E, k = cfg.n_experts, cfg.top_k
    n_model = mesh.shape["model"]
    E_loc = E // n_model
    # dp axes still auto (serve path) -> make them manual here, with tokens
    # sharded across them; in the train path they are already Manual.
    dp_auto = tuple(a for a in ("pod", "data")
                    if a in types and str(types[a]).endswith("Auto")
                    and mesh.shape[a] > 1)
    n_dp = 1
    for a in dp_auto:
        n_dp *= mesh.shape[a]
    if T % n_dp or T < n_dp:
        dp_auto, n_dp = (), 1
    T_loc = T // n_dp
    dp_spec = (dp_auto if len(dp_auto) > 1 else dp_auto[0]) if dp_auto else None

    x = h.reshape(T, d)
    # router + aux stay outside (small auto matmul over the model axis)
    logits = x.astype(jnp.float32) @ p["router"]
    idx, gate, aux = router_topk(cfg, logits)

    n_chunks = min(cfg.moe_chunks, T_loc) if T_loc >= cfg.moe_chunks else 1
    while T_loc % n_chunks:
        n_chunks -= 1
    Tc = T_loc // n_chunks
    if capacity is None:
        capacity = max(int(T_loc * k / E * cfg.capacity_factor), 8)
    act = cm.act_fn(cfg.act)

    def experts_inner(w1, w3, w2, stok_all, sval_all, xc):
        """Manual over 'model': stok/sval_all (n_chunks, E_loc, C) arrive
        pre-sharded via in_specs; one fp32 psum of (Tc, d) per chunk."""
        def chunk(_, inp):
            stok, sval, xi = inp
            buf = xi[stok] * (sval > 0)[..., None].astype(xi.dtype)
            hb = act(jnp.einsum("ecd,edf->ecf", buf, w1)) \
                * jnp.einsum("ecd,edf->ecf", buf, w3)
            ob = jnp.einsum("ecf,efd->ecd", hb, w2)
            contrib = ob.astype(jnp.float32) * sval[..., None]
            y = jnp.zeros((Tc, d), jnp.float32)
            y = y.at[stok].add(contrib, mode="drop")
            return None, jax.lax.psum(y, "model")

        _, yc = jax.lax.scan(chunk, None, (stok_all, sval_all, xc))
        return yc

    def routed(xl, il, gl, w1, w3, w2):
        """Per-dp-shard routing + expert compute. xl (T_loc, d)."""
        xc = xl.reshape(n_chunks, Tc, d)
        ic = il.reshape(n_chunks, Tc, k)
        gc = gl.reshape(n_chunks, Tc, k)

        def bookkeep(counts, inp):
            ei, gi = inp
            oh = jax.nn.one_hot(ei.reshape(-1), E, dtype=jnp.int32)
            within = jnp.cumsum(oh, axis=0) - oh
            pos = (within * oh).sum(-1).reshape(Tc, k) + counts[ei]
            keep = pos < capacity
            posc = jnp.clip(pos, 0, capacity - 1)
            flat_tok = jnp.broadcast_to(jnp.arange(Tc)[:, None], (Tc, k))
            eflat = ei.reshape(-1)
            kflat = keep.reshape(-1)
            pflat = jnp.where(kflat, posc.reshape(-1), capacity)
            stok = jnp.zeros((E, capacity), jnp.int32).at[eflat, pflat].set(
                flat_tok.reshape(-1), mode="drop")
            sval = jnp.zeros((E, capacity), jnp.float32).at[eflat, pflat].set(
                gi.reshape(-1).astype(jnp.float32), mode="drop")
            return counts + oh.sum(0), (stok, sval, 1.0 - kflat.mean())

        _, (stok_all, sval_all, dropc) = jax.lax.scan(
            bookkeep, jnp.zeros((E,), jnp.int32), (ic, gc))

        inner = compat.shard_map(
            experts_inner, mesh=compat.get_abstract_mesh(),
            in_specs=(P("model", None, None), P("model", None, None),
                      P("model", None, None), P(None, "model", None),
                      P(None, "model", None), P(None, None, None)),
            out_specs=P(None, None, None),
            axis_names={"model"}, check_vma=False)
        yc = inner(w1, w3, w2, stok_all, sval_all, xc)
        return yc.reshape(T_loc, d), dropc.mean()

    if dp_auto:
        sm = compat.shard_map(
            routed, mesh=mesh,
            in_specs=(P(dp_spec, None), P(dp_spec, None), P(dp_spec, None),
                      P(None, None, None), P(None, None, None),
                      P(None, None, None)),
            out_specs=(P(dp_spec, None), P()),
            axis_names=set(dp_auto), check_vma=False)
        y, dropped = sm(x, idx, gate, p["we1"], p["we3"], p["we2"])
        y = y.reshape(T, d)
    else:
        y, dropped = routed(x, idx, gate, p["we1"], p["we3"], p["we2"])

    out = y.reshape(b, s, d).astype(h.dtype)
    if cfg.shared_expert:
        sp = p["shared"]
        out = out + (act(x @ sp["w1"]) * (x @ sp["w3"]) @ sp["w2"]
                     ).reshape(b, s, d)
    return out, dict(aux, dropped=dropped)


def moe_ffn(cfg, p, h, capacity: Optional[int] = None):
    """h (B,S,d) -> (out (B,S,d), aux). Chunked capacity dispatch."""
    if cfg.moe_impl == "shardmap":
        return moe_ffn_shardmap(cfg, p, h, capacity)
    if cfg.moe_impl == "slotmap":
        return moe_ffn_slotmap(cfg, p, h, capacity)
    b, s, d = h.shape
    T = b * s
    E, k = cfg.n_experts, cfg.top_k
    x = h.reshape(T, d)
    n_chunks = min(cfg.moe_chunks, T) if T >= cfg.moe_chunks else 1
    while T % n_chunks:
        n_chunks -= 1
    Tc = T // n_chunks
    if capacity is None:
        capacity = max(int(T * k / E * cfg.capacity_factor), 8)

    logits = x.astype(jnp.float32) @ p["router"]
    logits = cm.wsc(logits, None, "model")
    idx, gate, aux = router_topk(cfg, logits)

    xc = x.reshape(n_chunks, Tc, d)
    idxc = idx.reshape(n_chunks, Tc, k)
    gatec = gate.reshape(n_chunks, Tc, k)

    # --- dispatch: scan over chunks, carry (per-expert counts, buffer) -----
    def dispatch(carry, inp):
        counts, buf = carry
        xi, ei = inp                                   # (Tc,d), (Tc,k)
        oh = jax.nn.one_hot(ei.reshape(-1), E, dtype=jnp.int32)   # (Tc*k, E)
        within = jnp.cumsum(oh, axis=0) - oh
        pos = (within * oh).sum(-1).reshape(Tc, k) + counts[ei]   # (Tc,k)
        keep = pos < capacity
        posc = jnp.clip(pos, 0, capacity - 1)
        for j in range(k):                             # k small: unrolled
            upd = jnp.where(keep[:, j, None], xi, 0).astype(buf.dtype)
            buf = buf.at[ei[:, j], posc[:, j]].add(upd, mode="drop")
        counts = counts + oh.sum(0)
        return (counts, buf), (posc, keep)

    buf0 = jnp.zeros((E, capacity, d), h.dtype)
    buf0 = cm.wsc(buf0, "model", None, None)
    counts0 = jnp.zeros((E,), jnp.int32)
    (counts, buf), (pos_all, keep_all) = jax.lax.scan(
        dispatch, (counts0, buf0), (xc, idxc))

    # --- expert compute (expert-parallel over the model axis) --------------
    act = cm.act_fn(cfg.act)
    hbuf = act(jnp.einsum("ecd,edf->ecf", buf, p["we1"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["we3"])
    obuf = jnp.einsum("ecf,efd->ecd", hbuf, p["we2"])
    obuf = cm.wsc(obuf, "model", None, None)

    # --- combine: gather per chunk, weight per choice -----------------------
    def combine(_, inp):
        ei, posi, keepi, gi = inp                      # (Tc,k) each
        y = jnp.zeros((Tc, d), jnp.float32)
        for j in range(k):
            got = obuf[ei[:, j], posi[:, j]].astype(jnp.float32)
            y = y + got * (gi[:, j] * keepi[:, j])[:, None]
        return None, y

    _, yc = jax.lax.scan(combine, None, (idxc, pos_all, keep_all, gatec))
    out = yc.reshape(b, s, d).astype(h.dtype)

    if cfg.shared_expert:
        sp = p["shared"]
        out = out + (act(x @ sp["w1"]) * (x @ sp["w3"]) @ sp["w2"]
                     ).reshape(b, s, d)

    frac_dropped = 1.0 - (keep_all.sum() / (T * k))
    aux = dict(aux, dropped=frac_dropped)
    return out, aux


# ---------------------------------------------------------------------------
# Layers / forward
# ---------------------------------------------------------------------------

def moe_attn_layer(cfg, p, x, positions, capacity=None):
    h = tfm.norm_apply(cfg, x, p["ln1"])
    q, kk, v = tfm._qkv(cfg, p["attn"], h)
    q = cm.apply_rope(q, positions, cfg.rope_theta)
    kk = cm.apply_rope(kk, positions, cfg.rope_theta)
    out = cm.attention(q, kk, v, causal=True,
                       window=cfg.sliding_window,
                       block_q=cfg.attn_block_q,
                       block_k=cfg.attn_block_k)
    b, s = x.shape[:2]
    x = x + out.reshape(b, s, -1) @ p["attn"]["wo"]
    ffn_in = tfm.norm_apply(cfg, x, p["ln2"])
    y, aux = moe_ffn(cfg, p["moe"], ffn_in, capacity)
    return x + y, aux


def forward(cfg, params, tokens, prefix_embeds=None, remat: bool = True,
            return_hidden: bool = False):
    x = tfm.embed(cfg, params, tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def dense_body(p, x):
        return tfm.attn_layer(cfg, p, x, positions, cfg.sliding_window)

    def moe_body(p, x):
        return moe_attn_layer(cfg, p, x, positions)

    d_body = jax.remat(dense_body) if remat else dense_body
    m_body = jax.remat(moe_body) if remat else moe_body

    if cfg.first_dense:
        def first_scan(x, p):
            return d_body(p, x), None
        x, _ = jax.lax.scan(first_scan, x, params["first"])

    def sb(x, bp):
        if "dense" in bp:
            x = d_body(bp["dense"], x)
        x, aux = m_body(bp["moe"], x)
        return x, aux

    x, auxs = jax.lax.scan(sb, x, params["blocks"])
    x = tfm.norm_apply(cfg, x, params["ln_f"])
    aux = {k: v.mean() for k, v in auxs.items()}
    if return_hidden:
        return x, aux
    return tfm.unembed(cfg, params, x), aux


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    w = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
    caches = {}
    if cfg.first_dense:
        caches["first"] = cm.init_kv_cache(cfg.first_dense, batch, w,
                                           cfg.n_kv_heads, cfg.hd, dtype)
    n_sb = (cfg.n_layers - cfg.first_dense) // (2 if cfg.moe_every == 2 else 1)
    per = 2 if cfg.moe_every == 2 else 1
    caches["blocks"] = cm.init_kv_cache(n_sb * per, batch, w,
                                        cfg.n_kv_heads, cfg.hd, dtype)
    caches["blocks"] = jax.tree.map(
        lambda a: a.reshape((n_sb, per) + a.shape[1:]), caches["blocks"])
    return caches


def _decode_one(cfg, p, x, ck, cv, pos, moe: bool):
    h = tfm.norm_apply(cfg, x, p["ln1"])
    q, kk, v = tfm._qkv(cfg, p["attn"], h)
    b = x.shape[0]
    posv = jnp.broadcast_to(pos[None], (b, 1)) if jnp.ndim(pos) == 0 else pos
    q = cm.apply_rope(q, posv, cfg.rope_theta)
    kk = cm.apply_rope(kk, posv, cfg.rope_theta)
    ring = cfg.sliding_window is not None
    ck, cv = cm.cache_update(ck, cv, kk, v, pos, ring=ring)
    length = jnp.minimum(pos + 1, ck.shape[1])
    out = cm.decode_attention(q, ck, cv, length=length)
    x = x + out.reshape(b, 1, -1) @ p["attn"]["wo"]
    h2 = tfm.norm_apply(cfg, x, p["ln2"])
    if moe:
        y, _ = moe_ffn(cfg, p["moe"], h2, capacity=max(x.shape[0], 8))
    else:
        y = tfm.mlp(cfg, p["mlp"], h2)
    return x + y, ck, cv


def prefill(cfg, params, tokens, max_len=None, prefix_embeds=None,
            remat: bool = True):
    """Forward over the prompt capturing per-layer K/V caches."""
    x = tfm.embed(cfg, params, tokens)
    b, s, _ = x.shape
    max_len = max_len or s
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    w = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len

    def capture(p, x, moe_layer):
        h = tfm.norm_apply(cfg, x, p["ln1"])
        q, kk, v = tfm._qkv(cfg, p["attn"], h)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        kk = cm.apply_rope(kk, positions, cfg.rope_theta)
        out = cm.attention(q, kk, v, causal=True,
                           window=cfg.sliding_window,
                           block_q=cfg.attn_block_q,
                           block_k=cfg.attn_block_k)
        x = x + out.reshape(b, s, -1) @ p["attn"]["wo"]
        h2 = tfm.norm_apply(cfg, x, p["ln2"])
        if moe_layer:
            y, _ = moe_ffn(cfg, p["moe"], h2)
        else:
            y = tfm.mlp(cfg, p["mlp"], h2)
        x = x + y
        if cfg.sliding_window:
            j = jnp.arange(w)
            p_j = (s - 1) - ((s - 1 - j) % w)
            valid = (p_j >= 0)[None, :, None, None]
            kk = jnp.where(valid, jnp.take(kk, jnp.clip(p_j, 0, s - 1), axis=1), 0)
            v = jnp.where(valid, jnp.take(v, jnp.clip(p_j, 0, s - 1), axis=1), 0)
        elif max_len > s:
            pad = ((0, 0), (0, max_len - s), (0, 0), (0, 0))
            kk, v = jnp.pad(kk, pad), jnp.pad(v, pad)
        return x, kk, v

    body = jax.remat(capture, static_argnums=(2,)) if remat else capture

    if cfg.first_dense:
        def first(x, p):
            x, kk, v = body(p, x, False)
            return x, {"k": kk, "v": v}
        x, first_cache = jax.lax.scan(first, x, params["first"])

    per = 2 if cfg.moe_every == 2 else 1

    def sb(x, bp):
        ks, vs = [], []
        if per == 2:
            x, kk, v = body(bp["dense"], x, False)
            ks.append(kk)
            vs.append(v)
        x, kk, v = body(bp["moe"], x, True)
        ks.append(kk)
        vs.append(v)
        return x, (jnp.stack(ks), jnp.stack(vs))

    x, (bk, bv) = jax.lax.scan(sb, x, params["blocks"])
    caches = {"blocks": {"k": bk, "v": bv}}
    if cfg.first_dense:
        caches["first"] = first_cache
    x = tfm.norm_apply(cfg, x, params["ln_f"])
    return tfm.unembed(cfg, params, x[:, -1:]), caches


def decode_step(cfg, params, caches, token, pos, prefix_embeds=None):
    x = tfm.embed(cfg, params, token)
    per = 2 if cfg.moe_every == 2 else 1

    if cfg.first_dense:
        def first(xc, args):
            p, ck, cv = args
            x, ck, cv = _decode_one(cfg, p, xc, ck, cv, pos, moe=False)
            return x, (ck, cv)
        x, (fk, fv) = jax.lax.scan(
            first, x, (params["first"], caches["first"]["k"],
                       caches["first"]["v"]))
        new_first = {"k": fk, "v": fv}

    def sb(xc, args):
        bp, ck, cv = args                              # ck (per,B,S,KH,hd)
        i = 0
        if per == 2:
            xc, k0, v0 = _decode_one(cfg, bp["dense"], xc, ck[0], cv[0], pos,
                                     moe=False)
            i = 1
        xc, k1, v1 = _decode_one(cfg, bp["moe"], xc, ck[i], cv[i], pos,
                                 moe=True)
        nk = jnp.stack([k0, k1]) if per == 2 else k1[None]
        nv = jnp.stack([v0, v1]) if per == 2 else v1[None]
        return xc, (nk, nv)

    x, (bk, bv) = jax.lax.scan(
        sb, x, (params["blocks"], caches["blocks"]["k"], caches["blocks"]["v"]))
    new_caches = {"blocks": {"k": bk, "v": bv}}
    if cfg.first_dense:
        new_caches["first"] = new_first
    x = tfm.norm_apply(cfg, x, params["ln_f"])
    return tfm.unembed(cfg, params, x), new_caches
