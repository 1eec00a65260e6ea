"""Serving example: prefill a batched prompt, then greedy-decode with KV
caches (ring-buffer windows on local layers) on the gemma3-pattern model.

    PYTHONPATH=src python examples/serve_decode.py [--arch gemma3-12b]
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model
from repro.serve import build_serve_step
from repro import compat


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config(args.arch, smoke=True)
    model = build_model(cfg)

    with compat.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompt = jnp.asarray(
            rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)),
            jnp.int32)
        max_len = args.prompt_len + args.gen

        batch = {"tokens": prompt}
        if cfg.family == "audio":
            batch["frames"] = jnp.asarray(rng.standard_normal(
                (args.batch, cfg.encoder_frames, cfg.d_model)),
                jnp.float32) * 0.02
        logits, caches = jax.jit(
            lambda p, b: model.prefill(p, b, max_len))(params, batch)
        print(f"prefilled {args.prompt_len} tokens; cache leaves:",
              len(jax.tree.leaves(caches)))

        serve_step = build_serve_step(model, mesh)
        tok = jnp.argmax(logits[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
        out = [tok]
        for pos in range(args.prompt_len, max_len - 1):
            tok, logits, caches = serve_step(params, caches, tok,
                                             jnp.asarray(pos))
            out.append(tok)
        gen = jnp.concatenate(out, axis=1)
        print("generated token ids (batch 0):", np.asarray(gen[0]))
        assert gen.shape == (args.batch, args.gen)
        assert (np.asarray(gen) < cfg.vocab).all()
        print("greedy decode OK — one serve_step per token against the cache")


if __name__ == "__main__":
    main()
