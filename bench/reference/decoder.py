"""Plain float32 reference of the dense decoder (the Qwen3 family).

Per layer: RMSNorm, q/k/v projections, RMSNorm of q and k over the head,
RoPE, causal grouped-query attention, output projection, residual;
RMSNorm, SwiGLU MLP, residual.  Then the final RMSNorm and the tied
unembedding over every row of the embedding table.  The loss is the mean
next-token cross-entropy over every position.

The parameter tree is the program's layout, built here from the
configuration's published sizes (``shapes``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common as C


def shapes(spec: dict, dtype=jnp.bfloat16):
    d, h, kh = (spec["hidden_size"], spec["num_attention_heads"],
                spec["num_key_value_heads"])
    hd, ff, n = spec["head_dim"], spec["intermediate_size"], \
        spec["num_hidden_layers"]
    sds = lambda *s: jax.ShapeDtypeStruct(s, dtype)
    layer = {
        "ln1": {"scale": sds(n, d)},
        "ln2": {"scale": sds(n, d)},
        "attn": {"wq": sds(n, d, h * hd), "wk": sds(n, d, kh * hd),
                 "wv": sds(n, d, kh * hd), "wo": sds(n, h * hd, d),
                 "q_norm": sds(n, hd), "k_norm": sds(n, hd)},
        "mlp": {"w1": sds(n, d, ff), "w2": sds(n, ff, d),
                "w3": sds(n, d, ff)},
    }
    return {"emb": sds(spec["embedding_rows"], d),
            "blocks": {"global": layer},
            "ln_f": {"scale": sds(d)}}


def _layer(spec, mm, x, p):
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    h, kh, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                 spec["head_dim"])
    s = x.shape[0]
    a = C.rms_norm(x, p["ln1"]["scale"], eps)
    q = mm(a, p["attn"]["wq"]).reshape(s, h, hd)
    k = mm(a, p["attn"]["wk"]).reshape(s, kh, hd)
    v = mm(a, p["attn"]["wv"]).reshape(s, kh, hd)
    q = C.rope(C.rms_norm(q, p["attn"]["q_norm"], eps), theta)
    k = C.rope(C.rms_norm(k, p["attn"]["k_norm"], eps), theta)
    o = C.attention(mm, q, k, v, causal=True).reshape(s, h * hd)
    x = x + mm(o, p["attn"]["wo"])
    m = C.rms_norm(x, p["ln2"]["scale"], eps)
    return x + mm(jax.nn.silu(mm(m, p["mlp"]["w1"])) * mm(m, p["mlp"]["w3"]),
                  p["mlp"]["w2"])


def row_nll_sum(spec, mm, params, tokens, labels):
    """Summed next-token NLL of one sequence (float32 params)."""
    x = params["emb"][tokens]

    def body(x, p):
        return jax.checkpoint(lambda x, p: _layer(spec, mm, x, p))(x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["global"])
    x = C.rms_norm(x, params["ln_f"]["scale"], spec["rms_norm_eps"])
    return C.nll_sum(mm, x, params["emb"], labels, chunk=512)


def loss_and_grad(spec, mm, params, batch):
    """Mean NLL over every position of ``batch`` and its gradient,
    one sequence at a time so that the activations of one fit."""
    n_tok = batch["tokens"].size
    vg = jax.value_and_grad(lambda p, t, l: row_nll_sum(spec, mm, p, t, l))

    def body(carry, row):
        tot, g = carry
        v, gr = vg(params, *row)
        return (tot + v, jax.tree.map(jnp.add, g, gr)), None

    g0 = jax.tree.map(jnp.zeros_like, params)
    (tot, g), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), g0),
        (batch["tokens"], batch["labels"]))
    return tot / n_tok, jax.tree.map(lambda a: a / n_tok, g)
