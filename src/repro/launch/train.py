"""Production training driver.

Builds the mesh, model, optimiser, and averager; maintains the cache of
compiled step variants (one per butterfly phase offset + the tau-sync step);
streams synthetic data; logs metrics; checkpoints.

Usage (CPU demo on forced host devices is in examples/; with no mesh flags
the run is data parallel over every device present):

    python -m repro.launch.train --arch tinyllama-1.1b --averager wagma \
        --steps 500 [--data-axis 16 --model-axis 16 [--pod-axis 2]]
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import InputShape
from repro.core.baselines import make_averager
from repro.core.group_allreduce import dp_axis_layout
from repro.core.replica import REPLICATED, ShardingPolicy, consolidate_state
from repro.data import make_batch_fn
from repro.models.registry import build_model
from repro.optim import sgd, adamw, cosine_warmup
from repro.train import build_train_step, init_replica_state, dp_axes_of
from repro.checkpoint import save_replica_state
from repro import compat
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh


def resolve_sharding(sharding, dp_names, streamed: bool = False
                     ) -> ShardingPolicy:
    """CLI/ctor spelling -> ShardingPolicy.

    ``None``/``"replicated"`` -> replicated; ``"fsdp"`` shards over the
    minor (intra-pod) dp axis; ``streamed=True`` (or the ``"fsdp_streamed"``
    spelling) selects the layer-streamed state layout (DESIGN.md §11); a
    ready ShardingPolicy passes through.
    """
    if isinstance(sharding, ShardingPolicy):
        if streamed and not sharding.streamed:
            import dataclasses
            return dataclasses.replace(sharding, streamed=True)
        return sharding
    if sharding == "fsdp_streamed":
        sharding, streamed = "fsdp", True
    if sharding is None or sharding == "replicated":
        if streamed:
            raise ValueError("--streamed requires --sharding fsdp")
        return REPLICATED
    if sharding == "fsdp":
        return ShardingPolicy.fsdp_within_pod(dp_names[0], streamed=streamed)
    raise ValueError(f"unknown sharding {sharding!r}; options: "
                     f"replicated | fsdp | fsdp_streamed | "
                     f"ShardingPolicy(...)")


class Trainer:
    def __init__(self, cfg, mesh, *, averager="wagma", group_size=None,
                 tau=10, optimizer="sgd", learning_rate=0.1, momentum=0.9,
                 seq_len=512, global_batch=None, seed=0, microbatch=None,
                 imbalanced=False, topology=None, sharding=None,
                 streamed=False, init_state=None, fault_injector=None):
        self.cfg = cfg
        self.mesh = mesh
        self.model = build_model(cfg)
        dp = dp_axes_of(mesh)
        self.n_dp = int(np.prod([mesh.shape[a] for a in dp]))
        names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape), dp)
        self.sharding = resolve_sharding(sharding, names, streamed=streamed)
        kw = {}
        if averager == "wagma":
            kw = {"group_size": group_size, "tau": tau}
        elif averager == "local_sgd":
            kw = {"sync_period": tau}
        if topology is not None:
            # pod-aware (or custom) Topology: the averager compiles one
            # AveragingPlan per tree structure on it — per-link-class bucket
            # budgets, stage classification, wavefront schedule (DESIGN §9)
            kw["topology"] = topology
        kw["sharding"] = self.sharding
        self.averager = make_averager(averager, names, sizes, **kw)
        if optimizer == "sgd":
            self.opt = sgd(learning_rate, momentum=momentum)
        else:
            self.opt = adamw(learning_rate)
        self.shape = InputShape("custom", seq_len,
                                global_batch or 8 * self.n_dp, "train")
        self.batch_fn = make_batch_fn(cfg, self.shape, seed=seed,
                                      imbalanced=imbalanced)
        self.microbatch = microbatch
        self._steps = {}
        dp_spec = dp if len(dp) > 1 else dp[0]
        self._dp_spec = dp_spec
        with compat.set_mesh(mesh):
            if init_state is not None:
                # elastic handoff / warm start: seat a host-side
                # ReplicaState (already in this policy's layout, with the
                # right replica-row count for this mesh) instead of
                # initialising fresh weights
                self.state = self._put_state(init_state)
            else:
                self.state = init_replica_state(self.model, self.opt,
                                                self.averager, mesh,
                                                jax.random.PRNGKey(seed))
        self._batch_sharding = lambda v: NamedSharding(
            mesh, P(dp_spec, *([None] * (v.ndim - 1))))
        # core.faults.FaultInjector (or None): wall-clock fault runtime
        # for this process's worker identity, consulted before each step
        self.fault_injector = fault_injector
        # replica-steps whose optimiser update was skipped by the
        # non-finite gradient guard (train/train_step.py), accumulated
        # from the per-step `skipped_nonfinite` metric fraction
        self.skipped_nonfinite = 0.0
        self.last_metrics = {}
        # blocking device-to-host transfers step_once has made (_read)
        self.host_reads = 0

    def _put_state(self, state):
        """device_put a host ReplicaState with this run's shardings."""
        from repro.core.replica import ReplicaState, map_opt_state
        from repro.train import replica_state_specs
        specs = replica_state_specs(self.model, self.opt, self.averager,
                                    self.mesh)
        scalar = NamedSharding(self.mesh, P())
        put = lambda spec: (lambda t: jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a),
                                     NamedSharding(self.mesh, spec)), t))
        # the per-replica count vector shards over dim 0 only
        opt = map_opt_state(state.opt_state, put(specs.params),
                            put(P(specs.params[0])))
        return ReplicaState(put(specs.params)(state.params), opt,
                            jax.device_put(jnp.asarray(state.step), scalar),
                            jax.device_put(jnp.asarray(state.phase), scalar))

    @property
    def params(self):
        return self.state.params

    def plan(self):
        """The compiled AveragingPlan the train step executes."""
        from repro.train.train_step import _plan_of
        return _plan_of(self.model, self.averager)

    def variant(self, t: int) -> str:
        """The compiled step variant global step ``t`` runs: ``sync`` (the
        tau-sync step) or ``group:<phase>``."""
        if self.averager.sync_due(t):
            return "sync"
        return f"group:{self.averager.phase_for_step(t)}"

    def _step_fn(self, t: int):
        key = self.variant(t)
        if key not in self._steps:
            self._steps[key] = build_train_step(
                self.model, self.opt, self.averager, self.mesh,
                phase=self.averager.phase_for_step(t),
                sync=self.averager.sync_due(t), microbatch=self.microbatch)
        return self._steps[key]

    def _put_batch(self, t: int):
        per = self.shape.global_batch
        nb = self.batch_fn(t, 0, per)
        return {k: jax.device_put(jnp.asarray(v), self._batch_sharding(
            jnp.asarray(v))) for k, v in nb.items()}

    def _read(self, v) -> float:
        """``float`` of a device value: one blocking device-to-host
        transfer, counted in ``host_reads``."""
        self.host_reads += 1
        return float(v)

    def step_once(self, t: int) -> float:
        """Run global step ``t`` (data, variant dispatch, update); returns loss.

        ``t`` is the *global* step index — the butterfly phase and the
        tau-sync schedule key off it, so an elastic driver that rebuilds
        the Trainer mid-run keeps passing its own monotonic counter.
        Callers outside :meth:`run` wrap in ``compat.set_mesh(self.mesh)``.

        Three host spans name the step's parts in a profiler trace:
        ``trainer.put_batch``, ``trainer.dispatch`` (stats ``variant`` and
        ``step``) and ``trainer.read_metrics`` (stat ``host_reads``, the
        counter as the span opens).
        """
        if self.fault_injector is not None:
            self.fault_injector.before_step(t)
        with TraceAnnotation("trainer.put_batch"):
            batch = self._put_batch(t)
        step = self._step_fn(t)
        with TraceAnnotation("trainer.dispatch", variant=self.variant(t),
                             step=t):
            self.state, metrics = step(self.state, batch)
        with TraceAnnotation("trainer.read_metrics",
                             host_reads=self.host_reads):
            self.last_metrics = {k: self._read(v) for k, v in metrics.items()}
            self.skipped_nonfinite += \
                self.last_metrics.get("skipped_nonfinite", 0.0) * self.n_dp
            # the loss is on the host already: no transfer
            return float(metrics["loss"])

    def step_hlo(self, t: int) -> str:
        """Compiled HLO text of the step variant global step ``t`` runs."""
        return self._step_fn(t).lower(self.state,
                                      self._put_batch(t)).compile().as_text()

    def run(self, steps: int, log_every: int = 10, ckpt_dir=None,
            ckpt_every=0):
        history = []
        with compat.set_mesh(self.mesh):
            for t in range(steps):
                loss = self.step_once(t)
                history.append(loss)
                if log_every and (t % log_every == 0 or t == steps - 1):
                    skip = (f" skipped_nonfinite {self.skipped_nonfinite:.0f}"
                            if self.skipped_nonfinite else "")
                    print(f"step {t:5d} loss {loss:.4f}{skip}", flush=True)
                if ckpt_dir and ckpt_every and (t + 1) % ckpt_every == 0:
                    save_replica_state(
                        ckpt_dir, jax.device_get(self.state),
                        sharding=self.sharding,
                        metadata={"arch": self.cfg.name})
        return history

    def consolidated(self):
        plan = self.plan() if self.sharding.is_sharded else None
        return consolidate_state(jax.device_get(self.state), plan)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--averager", default="wagma")
    ap.add_argument("--group-size", type=int, default=None)
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--data-axis", type=int, default=None,
                    help="default: every device present over the "
                         "pod/model axes")
    ap.add_argument("--model-axis", type=int, default=None)
    ap.add_argument("--pod-axis", type=int, default=None,
                    help="build a (pod, data, model) mesh — required for "
                         "--sharding fsdp (the pod axis carries the "
                         "pod-to-pod averaging)")
    ap.add_argument("--pod-dcn", action="store_true",
                    help="hierarchical topology: the pod axis rides DCN "
                         "constants/budget, data rides ICI (DESIGN.md §9)")
    ap.add_argument("--sharding", default="replicated",
                    choices=["replicated", "fsdp"],
                    help="fsdp: shard params/opt over the intra-pod dp "
                         "axis; replicas inside a pod act as one logical "
                         "WAGMA worker (DESIGN.md §10)")
    ap.add_argument("--streamed", action="store_true",
                    help="with --sharding fsdp: layer-streamed execution — "
                         "gather layer span k+1 while span k computes, "
                         "backward re-gathers + early reduce-scatters "
                         "(DESIGN.md §11; needs a model with a per-layer "
                         "apply decomposition)")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--imbalanced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    model_axis = args.model_axis or 1
    pod_axis = args.pod_axis or 1
    # no --data-axis: data parallel over every device present
    data_axis = args.data_axis or jax.device_count() // (pod_axis * model_axis)
    if args.pod_axis:
        mesh = make_mesh((pod_axis, data_axis, model_axis),
                         ("pod", "data", "model"))
    else:
        mesh = make_mesh((data_axis, model_axis), ("data", "model"))

    cfg = get_config(args.arch, smoke=args.smoke)
    topology = None
    if args.pod_dcn:
        from repro.core.plan import Topology
        names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape),
                                      dp_axes_of(mesh))
        topology = Topology.hierarchical(names, sizes, dcn_axes=("pod",))
    tr = Trainer(cfg, mesh, averager=args.averager,
                 group_size=args.group_size, tau=args.tau,
                 optimizer=args.optimizer, learning_rate=args.lr,
                 seq_len=args.seq_len, global_batch=args.global_batch,
                 microbatch=args.microbatch, imbalanced=args.imbalanced,
                 topology=topology, sharding=args.sharding,
                 streamed=args.streamed)
    hist = tr.run(args.steps, ckpt_dir=args.ckpt_dir,
                  ckpt_every=50 if args.ckpt_dir else 0)
    print(f"final loss {hist[-1]:.4f} (start {hist[0]:.4f})")


if __name__ == "__main__":
    main()
