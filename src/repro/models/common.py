"""Shared building blocks: norms, RoPE, GQA attention (blocked / windowed /
decode-with-cache), losses, init + sharding-spec helpers.

Memory discipline: training/prefill attention never materialises the full
(S, S) score matrix. ``attention`` routes long unwindowed self-attention on a
TPU to the splash Pallas kernel (kernels/splash_attention.py: flash forward
and backward, causal blocks skipped) and everything else to
``blocked_attention``, an online-softmax scan over KV blocks. Sliding-window
layers slice only the in-window KV blocks so local attention costs O(S*W),
not O(S^2).
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro import compat

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Per-layer apply decomposition (layer-streamed FSDP engine, DESIGN.md §11)
# ---------------------------------------------------------------------------

class LayeredModel(NamedTuple):
    """Per-layer apply decomposition of a model.

    The layer-streamed FSDP execution engine (core/streaming.py) consumes
    parameters one **span** (scan unit — a superblock for the dense
    family) at a time, so the model must expose its forward as
    stem -> span* -> head over a *layered* param tree

        {"stem": {...}, "layers": (span_0, ..., span_{n-1}), "head": {...}}

    produced by ``split`` (pure slicing of the canonical stacked tree;
    ``merge`` is its exact inverse).  ``stem(stem_tree, batch) ->
    (carry, aux)`` — ``carry`` is the differentiable activation threaded
    through the spans, ``aux`` is non-differentiable side data (positions);
    ``span(k, span_tree, carry, aux) -> carry`` applies span k;
    ``head_loss(head_tree, stem_tree, carry, aux, batch) ->
    (loss, metrics)`` mirrors the registry loss bit-for-bit (the stem tree
    is passed through for tied unembeddings).  The composition
    ``head_loss(..., span(n-1, ..., span(0, ..., stem(...))))`` must equal
    ``ModelAPI.loss`` exactly — the streamed/gather-all differential tests
    pin it.
    """
    n_spans: int
    split: Callable                 # params -> layered tree
    merge: Callable                 # layered tree -> params (exact inverse)
    stem: Callable                  # (stem_tree, batch) -> (carry, aux)
    span: Callable                  # (k, span_tree, carry, aux, remat=True) -> carry
    head_loss: Callable             # (head, stem, carry, aux, batch) -> (loss, metrics)


def wsc(x, *spec):
    """with_sharding_constraint that (a) no-ops when no mesh is set (CPU
    tests) or named axes are absent, and (b) drops spec entries whose dim is
    not divisible by the mesh axis (e.g. 4 KV heads on a 16-way model axis —
    constraining those forces involuntary remat in the SPMD partitioner)."""
    mesh = compat.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    used = {s for s in jax.tree.leaves(list(spec)) if isinstance(s, str)}
    if not used.issubset(names):
        return x
    fixed = []
    for i, entry in enumerate(spec):
        if entry is None:
            fixed.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        fixed.append(entry if x.shape[i] % n == 0 and x.shape[i] >= n else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))


# ---------------------------------------------------------------------------
# Param init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def split(key, n):
    return jax.random.split(key, n)


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x: (..., T, H, hd); positions: (..., T) int."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., T, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked online-softmax attention (train / prefill)
# ---------------------------------------------------------------------------

def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, t, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, h, n_rep, d)
                            ).reshape(b, t, h * n_rep, d)


def blocked_attention(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None,
                      block_q: int = 512, block_k: int = 1024,
                      q_offset: int = 0):
    """Online-softmax attention; q (B,Sq,H,hd), k/v (B,Sk,KH,hd).

    ``window``: sliding-window width (None = full). For windowed layers only
    the KV blocks intersecting [q_pos - window + 1, q_pos] are visited, via a
    scan over a *relative* block range and ``dynamic_slice`` — O(S*W) FLOPs.
    ``q_offset``: absolute position of q[0] (prefill continuation / decode).
    """
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    n_rep = h // kh
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # pad seq dims to block multiples
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k
    scale = 1.0 / math.sqrt(hd)

    q_blocks = qp.reshape(b, nq, block_q, h, hd).transpose(1, 0, 3, 2, 4)
    k_all = kp.transpose(0, 2, 1, 3)                    # (B,H,Sk,hd)
    v_all = vp.transpose(0, 2, 1, 3)
    q_blocks = wsc(q_blocks, None, None, "model", None, None)  # heads on TP
    k_all = wsc(k_all, None, "model", None, None)
    v_all = wsc(v_all, None, "model", None, None)

    if window is not None:
        # visit only ceil((window+block_q)/block_k)+1 KV blocks per q block
        n_vis = (window + block_q) // block_k + 1
    else:
        n_vis = nk

    def q_block_body(qi, qblk):
        # qblk: (B,H,block_q,hd)
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)

        def kv_step(carry, kj_rel):
            m, l, acc = carry
            if window is not None:
                # first visited block starts at the window's left edge
                first = jnp.maximum(
                    (q_offset + qi * block_q - (window - 1)) // block_k, 0)
                kj_unclipped = first + kj_rel
            else:
                kj_unclipped = kj_rel
            kj = jnp.clip(kj_unclipped, 0, nk - 1)
            visit_ok = kj_unclipped < nk                        # guard clip dup
            kblk = jax.lax.dynamic_slice_in_dim(k_all, kj * block_k, block_k, 2)
            vblk = jax.lax.dynamic_slice_in_dim(v_all, kj * block_k, block_k, 2)
            s = jnp.einsum("bhqd,bhkd->bhqk", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            k_pos = kj * block_k + jnp.arange(block_k)
            mask = (k_pos[None, :] < sk) & visit_ok             # padding/dup
            if causal:
                mask = mask & (k_pos[None, :] <= q_pos[:, None])
            if window is not None:
                mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        a0 = jnp.zeros((b, h, block_q, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_vis))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out                                           # (B,H,block_q,hd)

    outs = jax.lax.map(lambda args: q_block_body(*args),
                       (jnp.arange(nq), q_blocks))           # (nq,B,H,bq,hd)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, nq * block_q, h, hd)
    return out[:, :sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention dispatch (train / prefill): the splash kernel where shapes allow
# ---------------------------------------------------------------------------

# Trace-time count of attention calls by the path they were routed to
# ("kernel" or "blocked"), for tests and for checking a compiled program.
ATTENTION_PATHS: collections.Counter = collections.Counter()

KERNEL_MIN_SEQ = 512


def attention_path(q_shape, k_shape, *, window, q_offset, block_q: int,
                   block_k: int, backend: str, mesh) -> str:
    """``"kernel"`` (the splash Pallas kernel) or ``"blocked"`` for one call.

    A pure function of the shapes, the backend's name and the (abstract)
    mesh.  The kernel takes self-attention from position 0 with no window,
    at least ``KERNEL_MIN_SEQ`` long, tiled evenly by the clipped lane-aligned
    tiles, with a lane-wide head, on a TPU whose ``model`` axis (if any) is 1.
    """
    _, sq, _, hd = q_shape
    sk = k_shape[1]
    tq, tk = min(block_q, sq), min(block_k, sk)
    model = 1 if mesh is None or mesh.empty else dict(mesh.shape).get("model", 1)
    fits = (backend == "tpu" and window is None
            and isinstance(q_offset, int) and q_offset == 0
            and sq == sk and sq >= KERNEL_MIN_SEQ
            and sq % tq == 0 and sq % tk == 0
            and tq % 128 == 0 and tk % 128 == 0
            and hd % 128 == 0 and model <= 1)
    return "kernel" if fits else "blocked"


def _manual_over_auto_axes(fn, mesh, *xs):
    """Call ``fn(*xs)`` manual over every mesh axis (a Mosaic kernel cannot be
    partitioned by GSPMD; ``core/plan`` does the same for the combine kernel).

    The leading (batch) dim is split over the remaining Auto axes where it
    divides; the dispatcher only comes here with ``model`` at 1.
    """
    auto = () if mesh.empty else tuple(
        a for a, t in zip(mesh.axis_names, mesh.axis_types)
        if t != AxisType.Manual)
    if not auto:
        return fn(*xs)
    n = math.prod(mesh.shape[a] for a in auto)
    spec = P(auto) if xs[0].shape[0] % n == 0 else P()
    return compat.shard_map(fn, mesh=mesh, in_specs=(spec,) * len(xs),
                            out_specs=spec,
                            axis_names=set(mesh.axis_names))(*xs)


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              block_q: int = 512, block_k: int = 1024, q_offset: int = 0):
    """Train / prefill attention; q (B,Sq,H,hd), k/v (B,Sk,KH,hd).

    Routes by ``attention_path`` to the splash kernel (tiles ``block_q`` x
    ``block_k`` clipped to S) or to ``blocked_attention``, which takes the
    same arguments.
    """
    mesh = compat.get_abstract_mesh()
    path = attention_path(q.shape, k.shape, window=window, q_offset=q_offset,
                          block_q=block_q, block_k=block_k,
                          backend=jax.default_backend(), mesh=mesh)
    ATTENTION_PATHS[path] += 1
    if path == "blocked":
        return blocked_attention(q, k, v, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 q_offset=q_offset)
    from repro.kernels import ops      # Pallas loads only where it runs
    kernel = functools.partial(ops.splash_attention, causal=causal,
                               block_q=block_q, block_k=block_k)
    with jax.named_scope("attention_kernel"):
        return _manual_over_auto_axes(kernel, mesh, q, k, v)


def decode_attention(q, k_cache, v_cache, *, length, window: Optional[int] = None,
                     pos=None):
    """One-token attention against a cache. q (B,1,H,hd); cache (B,S,KH,hd).

    ``length``: number of valid cache entries (traced ok). For ring-buffer
    window caches, S == window and all entries < length are valid.
    """
    b, _, h, hd = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    n_rep = h // kh
    k = _repeat_kv(k_cache, n_rep)
    v = _repeat_kv(v_cache, n_rep)
    scale = 1.0 / math.sqrt(hd)
    sc = jnp.einsum("bohd,bshd->bhos", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale          # (B,H,1,S)
    idx = jnp.arange(s)
    valid = idx[None, None, None, :] < length
    sc = jnp.where(valid, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhos,bshd->bohd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(n_layers: int, batch: int, max_len: int, n_kv: int, hd: int,
                  dtype) -> dict:
    shape = (n_layers, batch, max_len, n_kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update(cache_k, cache_v, k_new, v_new, pos, ring: bool = False):
    """Insert (B,1,KH,hd) at position pos (ring-buffer modulo for windows)."""
    s = cache_k.shape[1]
    idx = jnp.mod(pos, s) if ring else pos
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, idx, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, idx, axis=1)
    return ck, cv


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits, labels, mask=None):
    """logits (B,S,V) [model-axis shardable], labels (B,S) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - lab
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


# ---------------------------------------------------------------------------
# Sharding-spec rules (model axis; dp handled by the step builder)
# ---------------------------------------------------------------------------

def shard_rules(path_leaf_shapes, model_axis: str = "model"):
    """Build a PartitionSpec tree from param path names.

    Conventions (path component -> placement):
      emb / lm_head     (V, d)        -> (model, None)       vocab-sharded
      wq/wk/wv          (d, H*hd)     -> (None, model)       head-sharded
      wo                (H*hd, d)     -> (model, None)
      w1/w3 (mlp up)    (d, ff)       -> (None, model)
      w2   (mlp down)   (ff, d)       -> (model, None)
      experts.*w1       (E, d, ff)    -> (model, None, None)  expert-parallel
      experts.*w2       (E, ff, d)    -> (model, None, None)
      scan-stacked params get a leading None prepended automatically
      everything else replicated
    """
    raise NotImplementedError("use spec_for_param per-model instead")


# base (unstacked) rank and model-axis placement per param name; spec entries
# apply to the TRAILING dims, leading scan-stack dims get None automatically.
_PARAM_RULES = {
    # name: (base_rank, spec_on_base_dims)
    "emb": (2, ("model", None)),          # vocab-sharded (logits matmul)
    "lm_head": (2, ("model", None)),
    "src_emb": (2, ("model", None)),
    "enc_pos": (2, (None, None)),
    "wq": (2, (None, "model")),
    "wk": (2, (None, "model")),
    "wv": (2, (None, "model")),
    "wo": (2, ("model", None)),
    "w1": (2, (None, "model")),
    "w3": (2, (None, "model")),
    "w2": (2, ("model", None)),
    "w_up": (2, (None, "model")),
    "w_down": (2, ("model", None)),
    "wg": (2, (None, "model")),
    "wif": (2, (None, None)),
    "w_x": (2, (None, "model")),
    "w_gate": (2, (None, "model")),
    "w_r": (2, (None, None)),             # lru gates: square (w,w); keep rep
    "w_i": (2, (None, None)),
    "w_out": (2, ("model", None)),
    "conv_w": (2, (None, "model")),
    "router": (2, (None, "model")),
    "we1": (3, ("model", None, None)),    # experts (E, d, ff): expert-parallel
    "we2": (3, ("model", None, None)),
    "we3": (3, ("model", None, None)),
    "r": (3, (None, None, None)),         # slstm per-head recurrent
}


def spec_for_param(path: str, shape: Tuple[int, ...],
                   model_axis: str = "model") -> P:
    """Model-axis placement by param name; leading stack dims -> None."""
    name = path.split("/")[-1]
    rule = _PARAM_RULES.get(name)
    if rule is None:
        return P(*([None] * len(shape)))
    base_rank, spec = rule
    lead = len(shape) - base_rank
    if lead < 0:
        return P(*([None] * len(shape)))
    entries = [None] * lead + [model_axis if s == "model" else None
                               for s in spec]
    # drop non-divisible placements (e.g. 36 heads * hd not % 16 is still ok
    # on the flattened dim; but guard tiny dims)
    return P(*entries)


def tree_specs(params_or_shapes, model_axis="model"):
    """PartitionSpec tree matching a params tree (rank-aware stacking)."""
    import jax.tree_util as jtu

    def visit(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", None))) for k in path]
        return spec_for_param("/".join(keys), leaf.shape, model_axis)

    return jtu.tree_map_with_path(visit, params_or_shapes)
