"""Building blocks of the plain references: matmul modes, norms, RoPE,
attention, cross-entropy and the benchmark's weight initialisation."""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm_f32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _qdq_int8(x):
    """Symmetric per-tensor int8 quantise-dequantise."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def _mm_int8(a, b):
    return _mm_f32(_qdq_int8(a), _qdq_int8(b))


def _mm_int8_fwd(a, b):
    qa, qb = _qdq_int8(a), _qdq_int8(b)
    return _mm_f32(qa, qb), (qa, qb)


def _mm_int8_bwd(res, g):
    qa, qb = res
    qg = _qdq_int8(g)
    return (_mm_f32(qg, jnp.swapaxes(qb, -1, -2)),
            _mm_f32(jnp.swapaxes(qa, -1, -2), qg))


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)

# "reference": float32 at the highest precision.  "int8": every matrix
# product, forward and backward, on int8-quantised operands with float32
# accumulation -- the precision below the configuration's bfloat16.
MATMULS = {"reference": _mm_f32, "int8": _mm_int8}


def matmul(mode: str):
    return MATMULS[mode]


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"]) \
        + p["bias"]


def rope(x, theta):
    """x (S, H, hd): rotate the two halves of each head by position."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(mm, q, k, v, causal: bool):
    """q (Sq, H, hd), k/v (Sk, KH, hd); query head i reads kv head
    i // (H / KH)."""
    sq, h, hd = q.shape
    sk, kh, _ = k.shape
    rep = h // kh
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qh, kh_, vh = (a.transpose(1, 0, 2) for a in (q, k, v))   # (H, S, hd)
    s = mm(qh, kh_.transpose(0, 2, 1)) / math.sqrt(hd)
    if causal:
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm(p, vh).transpose(1, 0, 2)                         # (Sq, H, hd)


def nll_sum(mm, x, table, labels, chunk: int):
    """Sum over positions of -log softmax(x @ table.T)[label], computed
    ``chunk`` positions at a time so the logits never exist whole."""
    s = x.shape[0]
    chunk = min(chunk, s)
    n = s // chunk
    xs = x[:n * chunk].reshape(n, chunk, -1)
    ls = labels[:n * chunk].reshape(n, chunk)

    @jax.checkpoint
    def one(xc, lc):
        logits = mm(xc, table.T)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return (lse - lab).sum()

    def body(tot, inp):
        return tot + one(*inp), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    if n * chunk < s:
        tot = tot + one(x[n * chunk:], labels[n * chunk:])
    return tot


# -- the benchmark's weights ---------------------------------------------

ZERO_LEAVES = ("scale", "bias", "q_norm", "k_norm")
TABLE_LEAVES = ("emb", "src_emb", "enc_pos")
MATRIX_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")


def seed_key(seed: int):
    """PRNG key of an arbitrary non-negative integer seed."""
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    while True:
        key = jax.random.fold_in(key, seed % 2**31)
        seed //= 2**31
        if not seed:
            return key


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def init_leaf(key, path: str, shape, dtype):
    """The benchmark's initialisation of one parameter leaf, by its name.

    Norm parameters start at their neutral value (the program stores
    ``1 + scale``), matrices at N(0, 1/fan_in), tables at N(0, 0.02^2).
    Each leaf's key is the seed's key folded with a hash of its path, so a
    leaf's values do not depend on which other leaves exist.
    """
    name = path.split("/")[-1]
    key = jax.random.fold_in(key, zlib.crc32(path.encode()) % 2**31)
    if name in ZERO_LEAVES:
        return jnp.zeros(shape, dtype)
    if name in TABLE_LEAVES:
        std = 0.02
    elif name in MATRIX_LEAVES:
        std = 1.0 / math.sqrt(shape[-2])
    else:
        raise ValueError(f"no initialisation rule for parameter {path!r}")
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_tree(shapes, key):
    """Initialise a tree of ShapeDtypeStructs leaf by leaf (inside a jit)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, s: init_leaf(key, path_name(p), s.shape, s.dtype), shapes)
