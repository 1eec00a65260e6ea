"""End-to-end driver: train a ~100M-param llama-style model with WAGMA-SGD.

Full run (a few hundred steps, as the paper's training-kind dictates):

    PYTHONPATH=src python examples/train_100m.py --steps 300 --seq-len 1024

The default invocation is scaled down (CPU-friendly smoke: 30 steps, seq 128)
but exercises the identical production path: shard_map-manual dp butterfly,
GSPMD model axis, compiled step-variant cache, checkpointing, consolidation.
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

import jax

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.launch.train import Trainer
from repro.checkpoint import save_checkpoint


def config_100m() -> ModelConfig:
    return ModelConfig(
        name="llama-100m", family="dense",
        n_layers=10, d_model=640, n_heads=10, n_kv_heads=5,
        d_ff=1792, vocab=32000, tie_embeddings=True,
        source="examples/train_100m.py (llama2-style ~100M)",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--group-size", type=int, default=2)
    ap.add_argument("--ckpt", default="/tmp/wagma_100m_ckpt")
    ap.add_argument("--sharding", default="replicated",
                    choices=["replicated", "fsdp"],
                    help="fsdp: FSDP-within-pod sharded replicas on a "
                         "(pod, data) dp mesh — params/opt shard over the "
                         "intra-pod data axis, group averaging runs "
                         "pod-to-pod (DESIGN.md §10)")
    args = ap.parse_args()

    if args.sharding == "fsdp":
        # fsdp needs a pod axis to average over once data carries shards
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    else:
        mesh = make_mesh((4, 2), ("data", "model"))
    cfg = config_100m()
    import numpy as np
    n_params = None

    tr = Trainer(cfg, mesh, averager="wagma", group_size=args.group_size,
                 tau=args.tau, optimizer="sgd", learning_rate=0.2,
                 seq_len=args.seq_len, global_batch=args.global_batch,
                 sharding=args.sharding)
    print(tr.plan().describe())
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(
                       jax.eval_shape(tr.model.init, jax.random.PRNGKey(0))))
    print(f"model: {n_params/1e6:.1f}M params, P_dp={tr.n_dp}, "
          f"S={tr.averager.S}, tau={args.tau}")
    hist = tr.run(args.steps, log_every=max(args.steps // 10, 1))
    # at 100M params the loss visibly decreases over a few hundred steps
    # (full invocation in the module docstring); the smoke default only
    # checks the pipeline end-to-end.
    if args.steps >= 100:
        assert min(hist[-10:]) < hist[0], "loss must decrease"

    consolidated = tr.consolidated()
    save_checkpoint(args.ckpt, consolidated, step=args.steps,
                    metadata={"arch": cfg.name, "averager": "wagma"})
    print(f"consolidated (replica-averaged) checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
