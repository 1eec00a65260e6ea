#!/usr/bin/env python3
"""Drive the WAGMA train step and the paged serving engine once on the TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the butterfly across four chips

One chip: ``Trainer`` takes 6 WAGMA + SGD-momentum steps on qwen3-0.6b at
its published widths (seq 2048, global batch 4, random weights from a
seed); the first step of the smoke-width model is run on the chip and on
the host CPU and the two losses must agree within bf16 tolerance; the
trained state becomes serving weights and ``ServeScheduler`` answers 4
ragged prompts.

``--four-chips``: only the path across chips and what it is compared with —
WAGMA (data=4, group size 2, tau 3) against the allreduce averager on the
same data, then WAGMA under layer-streamed FSDP on a pod=2 x data=2 mesh
with the hierarchical topology (depth cut to ``FSDP_LAYERS``).  The group
step's compiled HLO must hold the butterfly (``collective-permute``) and the
native combine kernel (``tpu_custom_call``); replica rows must be
bit-identical after a tau-sync and form groups of identical rows after a
group step.

Everything is printed on earlier lines; the last line of stdout is one JSON
object ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any
failed phase, and a run where JAX finds no TPU, exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-0.6b"
SEQ = 2048
BATCH_PER_CHIP = 4
# Depth of the layer-streamed FSDP phase (published widths, 28 -> 4 layers):
# its step program unrolls one gather/scatter span per layer, and at full
# depth each of its compiled variants takes minutes to compile.
FSDP_LAYERS = 4
# relative tolerance of the chip-vs-host loss check: 4 bf16 ulps
BF16_RTOL = 4 * 2.0 ** -8


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class CompileStats:
    """Backend compile time and persistent-cache hits, from jax.monitoring."""

    def __init__(self, jax):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs


def run_steps(tr, steps, stats, tag, after_step=None):
    """``steps`` Trainer steps; logs loss, wall and compile seconds each."""
    from repro import compat
    losses = []
    with compat.set_mesh(tr.mesh):
        for t in range(steps):
            c0, t0 = stats.seconds, time.perf_counter()
            loss = tr.step_once(t)
            wall = time.perf_counter() - t0
            kind = "sync" if tr.averager.sync_due(t) else "group"
            log(f"{tag} step {t} ({kind}): loss {loss!r} wall_s {wall!r} "
                f"compile_s {stats.seconds - c0!r}")
            losses.append(loss)
            if after_step is not None:
                after_step(t, kind)
    check(all(math.isfinite(l) for l in losses),
          f"{tag}: non-finite loss in {losses}")
    return losses


def peak_bytes(jax):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def train_one_chip(jax, stats, steps=6):
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import Trainer

    cfg = get_config(ARCH)
    mesh = make_mesh((1, 1), ("data", "model"))
    tr = Trainer(cfg, mesh, averager="wagma", tau=3, optimizer="sgd",
                 learning_rate=0.05, momentum=0.9, seq_len=SEQ,
                 global_batch=BATCH_PER_CHIP, seed=0)
    n_params = sum(l.size // l.shape[0] for l in jax.tree.leaves(tr.params))
    log(f"train: {ARCH} {cfg.n_layers} layers d_model {cfg.d_model} vocab "
        f"{cfg.vocab} ({n_params} params), WAGMA tau 3, SGD momentum 0.9, "
        f"seq {SEQ} batch {BATCH_PER_CHIP}")
    before = jax.tree.map(jnp.copy, tr.params)
    run_steps(tr, steps, stats, "train")
    changed = sum(bool(jnp.any(a != b)) for a, b in
                  zip(jax.tree.leaves(before), jax.tree.leaves(tr.params)))
    n_leaves = len(jax.tree.leaves(before))
    log(f"train: {changed}/{n_leaves} param leaves changed; peak bytes "
        f"{peak_bytes(jax)}")
    check(changed > 0, "train: no parameter changed")
    return tr


def same_step_check(jax):
    """Smoke-width first-step loss: chip vs the host CPU backend."""
    from repro import compat
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import Trainer

    cfg = get_config(ARCH, smoke=True)

    def first_loss(device):
        mesh = make_mesh((1, 1), ("data", "model"), devices=[device])
        tr = Trainer(cfg, mesh, averager="wagma", tau=3, seq_len=128,
                     global_batch=BATCH_PER_CHIP, seed=0)
        with compat.set_mesh(mesh):
            return tr.step_once(0)

    chip = first_loss(jax.devices()[0])
    host = first_loss(jax.devices("cpu")[0])
    rel = abs(chip - host) / abs(host)
    log(f"same-step: smoke-width first loss chip {chip!r} host {host!r} "
        f"rel diff {rel!r} (limit {BF16_RTOL!r})")
    check(rel <= BF16_RTOL, "same-step: chip and host losses disagree")


def serve_one_chip(jax, tr, max_new=16, block_size=16):
    import numpy as np
    from repro import serve

    model, cfg = tr.model, tr.cfg
    params = serve.serving_weights_from_state(tr.state)
    lens = (16, 77, 160, 300)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in lens]
    blocks = [-(-(n + max_new) // block_size) for n in lens]
    sched = serve.ServeScheduler(
        model, params, n_blocks=1 + sum(blocks), block_size=block_size,
        max_blocks_per_req=max(blocks), max_batch=len(lens))
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        sched.submit(serve.Request(i, p, max_new))
    outs = sched.run()
    log(f"serve: {len(lens)} requests, prompt lengths {lens}, "
        f"{max_new} new tokens each, wall_s {time.perf_counter() - t0!r}, "
        f"decode steps {sched.n_decode_steps}")
    for i in range(len(lens)):
        check(len(outs[i]) == max_new,
              f"serve: request {i} returned {len(outs[i])} tokens")
    alone = []
    for i, p in enumerate(prompts):
        sched.submit(serve.Request(("alone", i), p, max_new))
        alone.append(sched.run()[("alone", i)])
    same = sum(outs[i] == alone[i] for i in range(len(lens)))
    log(f"serve: {same}/{len(lens)} requests match decoding each alone")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def replica_classes(jax, stacked):
    """Partition of the replica rows into classes of bit-identical rows."""
    import jax.numpy as jnp
    import numpy as np

    def eq(tree):
        bits = [jax.lax.bitcast_convert_type(
            a, jnp.dtype(f"uint{8 * a.dtype.itemsize}"))
            for a in jax.tree.leaves(tree)]
        n = bits[0].shape[0]
        return jnp.stack([jnp.stack([
            jnp.all(jnp.stack([jnp.all(b[i] == b[j]) for b in bits]))
            for j in range(n)]) for i in range(n)])

    same = np.asarray(jax.jit(eq)(stacked))
    classes = []
    for i in range(same.shape[0]):
        if not any(i in c for c in classes):
            classes.append(tuple(int(j) for j in np.nonzero(same[i])[0]))
    return classes


def four_chips(jax, stats, steps=4):
    from repro.configs import get_config
    from repro.core.group_allreduce import dp_axis_layout
    from repro.core.plan import Topology
    from repro.launch.mesh import make_mesh
    from repro.launch.train import Trainer

    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 chips, found {len(jax.devices())}")
    cfg = get_config(ARCH)
    batch = 4 * BATCH_PER_CHIP
    common = dict(optimizer="sgd", learning_rate=0.05, momentum=0.9,
                  seq_len=SEQ, global_batch=batch, seed=0)
    mesh = make_mesh((4, 1), ("data", "model"))

    # -- WAGMA, replicated policy ------------------------------------------
    tr = Trainer(cfg, mesh, averager="wagma", group_size=2, tau=3, **common)
    S = tr.averager.S

    def rows_after(t, kind):
        classes = replica_classes(jax, tr.params)
        log(f"wagma step {t} ({kind}): identical replica rows {classes}")
        want = [4] if kind == "sync" else [S] * (4 // S)
        check(sorted(len(c) for c in classes) == want,
              f"wagma step {t} ({kind}): rows {classes}, want class "
              f"sizes {want}")

    log(f"wagma: {ARCH} at published widths, data=4, S={S}, tau=3, seq "
        f"{SEQ}, batch {BATCH_PER_CHIP} per chip")
    wagma = run_steps(tr, steps, stats, "wagma", after_step=rows_after)
    group_t = next(t for t in range(steps) if not tr.averager.sync_due(t))
    hlo = tr.step_hlo(group_t)
    n_cp = hlo.count("collective-permute")
    n_kernel = hlo.count("tpu_custom_call")
    log(f"wagma group step HLO: {n_cp} collective-permute, {n_kernel} "
        f"tpu_custom_call; peak bytes {peak_bytes(jax)}")
    check(n_cp > 0, "wagma group step has no collective-permute")
    check(n_kernel > 0, "wagma group step has no native combine kernel")
    del tr

    # -- allreduce on the same data ----------------------------------------
    tr = Trainer(cfg, mesh, averager="allreduce", **common)

    def rows_equal(t, kind):
        classes = replica_classes(jax, tr.params)
        check(len(classes) == 1, f"allreduce step {t}: rows {classes}")

    allreduce = run_steps(tr, steps, stats, "allreduce",
                          after_step=rows_equal)
    log(f"wagma vs allreduce losses: {wagma} vs {allreduce}")
    del tr

    # -- WAGMA, layer-streamed FSDP on pod x data --------------------------
    mesh = make_mesh((2, 2, 1), ("pod", "data", "model"))
    names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape),
                                  ("pod", "data"))
    topo = Topology.hierarchical(names, sizes, dcn_axes=("pod",))
    tr = Trainer(cfg.variant(n_layers=FSDP_LAYERS), mesh, averager="wagma",
                 group_size=2, tau=3, sharding="fsdp", streamed=True,
                 topology=topo, **common)

    def pods_after(t, kind):
        classes = replica_classes(jax, tr.params)
        log(f"fsdp-streamed step {t} ({kind}): identical pod rows {classes}")
        check(len(classes) == 1, f"fsdp-streamed step {t}: pods {classes}")

    log(f"fsdp-streamed: {FSDP_LAYERS} of {cfg.n_layers} layers, pod=2 x "
        f"data=2, hierarchical topology, "
        f"{tr.plan().shard_layout.n_buckets} shard buckets")
    run_steps(tr, steps, stats, "fsdp-streamed", after_step=pods_after)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip butterfly and its "
                         "allreduce comparison")
    args = ap.parse_args()

    # the same-step check compares against the host CPU backend
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (default device is "
                 f"{dev.platform}); nothing was run")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    stats = CompileStats(jax)
    log(f"chip_smoke: {len(jax.devices())} x {dev.device_kind}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(jax, stats)
    else:
        tr = train_one_chip(jax, stats)
        same_step_check(jax)
        serve_one_chip(jax, tr)
    log(f"chip_smoke: wall_s {time.perf_counter() - t0!r}, backend compile_s "
        f"{stats.seconds!r}, persistent cache hits {stats.hits} misses "
        f"{stats.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
