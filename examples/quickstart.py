"""Quickstart: WAGMA-SGD on 8 (forced host) devices in ~a minute on CPU.

Trains the reduced tinyllama config with wait-avoiding group model averaging
(2 pods x 2-4 workers, S=2, tau=5) on a **pod-aware hierarchical topology**
and compares the loss curve against Allreduce-SGD.

This is the intended surface of the averaging subsystem (DESIGN.md §9): map
the dp mesh axes onto link classes with a frozen ``Topology``, and let the
averager compile the collective once into an ``AveragingPlan`` — per-stage
ICI/DCN classification, one bucket budget per link class, wavefront
schedule.

    PYTHONPATH=src python examples/quickstart.py
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

from repro.configs import get_config
from repro.core.group_allreduce import dp_axis_layout
from repro.core.plan import Topology
from repro.launch.mesh import make_mesh
from repro.launch.train import Trainer


def main():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("tinyllama-1.1b", smoke=True)

    # The topology is the compilation input: the 'data' axis rides intra-pod
    # ICI, the 'pod' axis rides inter-pod DCN — low butterfly bits classify
    # as ICI, high bits as DCN, each with its own bucket budget.
    names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape),
                                  ("pod", "data"))
    topology = Topology.hierarchical(names, sizes, dcn_axes=("pod",))
    print(f"topology: {topology.describe()}")

    print("== WAGMA-SGD (S=2, tau=5, pod-aware plan) ==")
    wagma = Trainer(cfg, mesh, averager="wagma", group_size=2, tau=5,
                    learning_rate=0.3, seq_len=64, global_batch=16,
                    topology=topology)
    # the plan the train step executes, compiled once per tree structure
    local = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         wagma.params)
    print(wagma.averager.plan_for(local).describe())
    h1 = wagma.run(steps=30, log_every=10)

    print("== Allreduce-SGD baseline ==")
    sync = Trainer(cfg, mesh, averager="allreduce", learning_rate=0.3,
                   seq_len=64, global_batch=16, topology=topology)
    h2 = sync.run(steps=30, log_every=10)

    print(f"\nWAGMA     first->last loss: {h1[0]:.3f} -> {h1[-1]:.3f}")
    print(f"Allreduce first->last loss: {h2[0]:.3f} -> {h2[-1]:.3f}")
    assert h1[-1] < h1[0] and h2[-1] < h2[0]
    print("both optimisers converge; WAGMA averages only within groups "
          "per step (global consensus every tau) — see DESIGN.md")


if __name__ == "__main__":
    main()
