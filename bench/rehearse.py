#!/usr/bin/env python3
"""Compile a cell's step variants for a described TPU, with no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <name>

Builds the train step the cell's window runs (every butterfly phase and
the tau-sync) at the configuration's real widths and the traffic's real
shapes, for one described v5e chip or a described ``v5e:2x2``, and prints
per variant the compiler's memory analysis and the HLO's
``collective-permute``, ``all-reduce`` and ``tpu_custom_call`` counts.
What the compiler refuses here costs no chip time; nothing here is a time.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def rehearse(workload: str) -> list:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from bench import harness
    from repro import compat
    from repro.core.baselines import make_averager
    from repro.core.group_allreduce import dp_axis_layout
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.optim import sgd
    from repro.train import (batch_shardings, build_train_step, dp_axes_of,
                             init_replica_state)

    jax.config.update("jax_enable_compilation_cache", False)
    ops._default_interpret = lambda: False        # Mosaic, as on the chip
    r = harness.resolve(harness.load_manifest(), workload)
    chips, spec, mix = r["cell"]["chips"], r["spec"], r["traffic"]
    train = spec["train"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_mesh((chips, 1), ("data", "model"),
                     devices=topo.devices[:chips])
    names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape),
                                  dp_axes_of(mesh))
    model = build_model(harness.program_config(spec))
    av = make_averager("wagma", names, sizes, group_size=train["group_size"],
                       tau=train["tau"])
    opt = sgd(train["learning_rate"], momentum=train["momentum"])
    key = jax.random.PRNGKey(0)
    rows = mix["batch_per_worker"] * chips
    shapes = {"tokens": (rows, mix["seq_len"]), "labels": (rows, mix["seq_len"])}
    if mix["source_len"]:
        shapes["src"] = (rows, mix["source_len"])
    out = []
    with compat.set_mesh(mesh):
        state = init_replica_state(model, opt, av, mesh, key, abstract=True)
        sds = {k: jax.ShapeDtypeStruct(v, jnp.int32) for k, v in shapes.items()}
        sh = batch_shardings(mesh, sds)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
                 for k, v in sds.items()}
        variants = [("sync", 0, True)] + [(f"group{p}", p, False)
                                          for p in range(av.n_phases)]
        for tag, phase, sync in variants:
            t = time.perf_counter()
            step = build_train_step(model, opt, av, mesh, phase=phase,
                                    sync=sync)
            compiled = step.lower(state, batch).compile()
            hlo = compiled.as_text()
            ma = compiled.memory_analysis()
            rec = {"workload": workload, "variant": tag,
                   "compile_s_cpu_host": time.perf_counter() - t,
                   "argument_bytes": ma.argument_size_in_bytes,
                   "output_bytes": ma.output_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes,
                   "alias_bytes": ma.alias_size_in_bytes,
                   "collective-permute": hlo.count("collective-permute-start(")
                   + hlo.count("collective-permute("),
                   "all-reduce": hlo.count("all-reduce-start(")
                   + hlo.count("all-reduce("),
                   "tpu_custom_call": hlo.count('custom_call_target="tpu_custom_call"')}
            print(json.dumps(rec), flush=True)
            out.append(rec)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    rehearse(ap.parse_args().workload)
