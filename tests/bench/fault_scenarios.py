"""Drive whole runs of a tiny cell with the train step broken underneath.

    python fault_scenarios.py <workload> <fault> [<fault> ...]

Faults: ``sound`` (nothing broken), ``unchanged`` (the step returns its
state unchanged), ``half_batch`` (each worker's step sees the first half
of its rows, the mean taken over them), ``no_exchange`` (the averager's
exchange between chips left out: each replica keeps what it made).
Everything but the look for a chip runs as in ``bench/run.py``, with the
cell's own limits.  Prints one JSON line per fault: its ``correct`` and
its compared numbers.
"""

import copy
import json
import sys
import time

import _paths  # noqa: F401
from tiny import tiny_cell


def plant(fault: str, originals: dict):
    """Break the program for ``fault``, after undoing any earlier break."""
    import jax
    import jax.numpy as jnp
    import repro.launch.train as train_mod

    train_mod.build_train_step = real = originals["build_train_step"]

    def factory(model, opt, averager, mesh, **kw):
        if fault == "no_exchange":
            lone = copy.copy(averager)
            lone.comm = lambda tree, phase: tree
            lone.sync = lambda tree: tree
            return real(model, opt, lone, mesh, **kw)
        step = real(model, opt, averager, mesh, **kw)
        workers = mesh.shape["data"]

        def unchanged(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        def half_batch(state, batch):
            def half(v):
                per = v.shape[0] // workers
                return v.reshape((workers, per) + v.shape[1:])[
                    :, :per // 2].reshape((-1,) + v.shape[1:])
            return step(state, {k: half(v) for k, v in batch.items()})

        return {"unchanged": unchanged, "half_batch": half_batch}[fault]

    if fault != "sound":
        train_mod.build_train_step = factory


def main(workload, faults):
    import repro.launch.train as train_mod
    from bench import correct, harness
    originals = {"build_train_step": train_mod.build_train_step}
    cell = tiny_cell(workload)
    limits = correct.load_limits(workload)
    for fault in faults:
        plant(fault, originals)
        res = harness.run(workload, 2**31 + 99, 0.2, False,
                              time.perf_counter(), require_accelerator=False,
                              cell=cell, limits=limits)
        print(json.dumps({"fault": fault, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
