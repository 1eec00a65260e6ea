"""Calibrate per-mesh-axis link constants (ROADMAP: measured alpha/beta/gamma).

The overlapped cost model (DESIGN.md §8) and the per-link-class
``LinkClass`` defaults (``plan.ICI``/``plan.DCN``) run on assumed
constants.  This scaffold microbenches the real backend:

* **alpha** — per-collective launch latency: wall time of a lane-sized
  ``ppermute`` ring shift on each mesh axis (latency-dominated);
* **beta**  — inverse wire bandwidth: the marginal time per byte between a
  small and a large ``ppermute`` payload on the same axis;
* **ag_alpha/ag_beta** — all-gather latency/bandwidth per axis (the FSDP
  gather path): ``with_measured`` takes the slower of the ppermute and
  all-gather rates per class, so a backend whose gathers are slower than
  its ring permutes prices the streamed-engine gather model honestly;
* **gamma** — combine throughput: the fused ``(acc + recv) * scale``
  kernel's seconds per payload byte on this backend's memory system.

Results land in ``LINK_CONSTANTS.json`` (``--out``):

    {"backend": ..., "mesh": {...}, "axes": {axis: {alpha, beta, gamma,
     ag_alpha, ag_beta, ...}}}

which ``plan.Topology.with_measured(path)`` loads back into a topology's
link classes (each class takes the slowest measurement among its axes).
On the forced-host-device CPU mesh the numbers measure XLA's CPU
emulation, not real wire — useful as a smoke of the scaffold (scripts/ci.sh
runs ``--smoke``) and as the recording template for a real TPU/GPU pod,
where this script is the calibration the ROADMAP item asks for.

Usage:
    python benchmarks/calibrate_links.py [--mesh-shape 2,4] [--iters 20]
        [--big-mb 4] [--out LINK_CONSTANTS.json] [--smoke]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.plan import DEFAULT_LINK_CONSTANTS_PATH
from repro.launch.mesh import make_mesh

# One canonical tracked location (repo root) shared with
# Topology.with_measured's default — there is no second copy to drift.
OUT_JSON = DEFAULT_LINK_CONSTANTS_PATH
SMALL_ELEMS = 128                      # one lane: latency-dominated
_WARMUP = 3


def _time(fn, x, iters: int) -> float:
    out = jax.block_until_ready(fn(x))          # compile
    for _ in range(_WARMUP):
        out = jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(x))
    del out
    return (time.perf_counter() - t0) / iters


def _ring(axis: str, n: int):
    perm = [(i, (i + 1) % n) for i in range(n)]
    return lambda buf: jax.lax.ppermute(buf, axis, perm)


def bench_axis(mesh, axis: str, *, big_elems: int, iters: int) -> dict:
    """Microbench one mesh axis: ppermute + all-gather latency/bandwidth."""
    n = mesh.shape[axis]

    def collective_fn(body):
        return jax.jit(compat.shard_map(
            body, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            axis_names=set(mesh.axis_names)))

    def stacked(elems):
        return jnp.zeros((n, elems), jnp.float32)

    ring = _ring(axis, n)
    t_pp_small = _time(collective_fn(ring), stacked(SMALL_ELEMS), iters)
    t_pp_big = _time(collective_fn(ring), stacked(big_elems), iters)
    big_bytes = big_elems * 4
    small_bytes = SMALL_ELEMS * 4
    beta = max(t_pp_big - t_pp_small, 1e-12) / max(big_bytes - small_bytes, 1)

    def ag_body(b):
        # consume every gathered row (sum) so XLA cannot elide the gather,
        # and keep the output per-device-sized so the timing excludes any
        # host-side materialisation
        return jax.lax.all_gather(b, axis, tiled=True).sum(
            axis=0, keepdims=True)

    ag_fn = collective_fn(ag_body)
    t_ag_small = _time(ag_fn, stacked(SMALL_ELEMS), iters)
    t_ag_big = _time(ag_fn, stacked(big_elems), iters)
    # all-gather moves (n-1)/n of the gathered buffer per device
    ag_wire = big_bytes * n * (n - 1) / n
    ag_beta = max(t_ag_big - t_ag_small, 1e-12) / max(ag_wire, 1)

    return {
        "alpha": t_pp_small,
        "beta": beta,
        "ppermute_small_s": t_pp_small,
        "ppermute_big_s": t_pp_big,
        "ag_alpha": t_ag_small,
        "ag_beta": ag_beta,
        "axis_size": n,
        "payload_big_bytes": big_bytes,
    }


def bench_gamma(*, big_elems: int, iters: int) -> float:
    """Combine throughput: fused (acc + recv) * scale seconds per byte."""
    from repro.core.plan import _stage_combine
    acc = jnp.zeros((big_elems,), jnp.float32)
    f = jax.jit(lambda a: _stage_combine(a, a, 0.5, False))
    t = _time(f, acc, iters)
    return t / (big_elems * 4)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh-shape", default="2,4",
                    help="comma ints: 'pod,data' (2) or 'pod,data,model' "
                         "(3); product must divide the device count")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--big-mb", type=float, default=4.0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny payload + few iters (CI scaffold smoke)")
    ap.add_argument("--out", default=OUT_JSON)
    args = ap.parse_args()
    if args.smoke:
        args.iters = min(args.iters, 5)
        args.big_mb = min(args.big_mb, 1.0)

    dims = tuple(int(x) for x in args.mesh_shape.split(","))
    axes = ("pod", "data", "model")[:len(dims)] if len(dims) != 2 \
        else ("pod", "data")
    mesh = make_mesh(dims, axes)
    big_elems = int(args.big_mb * 2**20 / 4)

    report = {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "mesh": {a: int(mesh.shape[a]) for a in mesh.axis_names},
        "iters": args.iters,
        "note": ("microbenched collective constants; on a forced-host CPU "
                 "mesh these measure XLA's emulation, not real links — "
                 "re-run on a TPU/GPU pod for production constants"),
        "axes": {},
    }
    gamma = bench_gamma(big_elems=big_elems, iters=args.iters)
    with compat.set_mesh(mesh):
        for axis in mesh.axis_names:
            if mesh.shape[axis] < 2 or axis == "model":
                continue
            print(f"benching axis {axis!r} (size {mesh.shape[axis]})...",
                  flush=True)
            ent = bench_axis(mesh, axis, big_elems=big_elems,
                             iters=args.iters)
            ent["gamma"] = gamma
            report["axes"][axis] = ent
            print(f"  alpha {ent['alpha']:.3e}s  beta {ent['beta']:.3e}s/B "
                  f"ag_beta {ent['ag_beta']:.3e}s/B gamma {gamma:.3e}s/B",
                  flush=True)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")

    # round-trip through the Topology loader as a self-check
    from repro.core.plan import Topology
    names = tuple(a for a in mesh.axis_names if a in report["axes"])
    if names:
        topo = Topology.hierarchical(
            names, tuple(mesh.shape[a] for a in names),
            dcn_axes=("pod",)).with_measured(args.out)
        print("with_measured ->", topo.describe())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
