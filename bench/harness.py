"""The benchmark harness: one cell, one run.

Everything that belongs to a cell is found by name in ``BENCHMARK.json``:
its configuration (``configs/<config>.json``, with the reference family it
names under ``reference/``), its traffic mix (``traffic/<traffic>.json``),
its limits (``limits/<workload>.json``) and each metric it reports
(``metrics/<metric>.py``).  Adding a cell adds files; nothing here changes.

A run:

1. set-up: the benchmark's weights made on the chip from the seed, the
   program's ``Trainer`` built around them, the traffic's batches made,
   put on the chips and installed through ``Trainer.batch_fn``, then the
   first ``correct.CHECKED_STEPS`` steps through ``Trainer.step_once``.  These
   start at global step ``first_checked_step`` (tau - 2: a group step,
   the tau-sync and a group step), compile every step variant the window
   uses and are the steps the reference follows;
2. the window: ``step_once`` for ``--seconds`` seconds (with ``--trace 1``,
   one whole tau period under the profiler instead), the global step
   continuing;
3. after the window: peak memory, the program freed, then the reference.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

from bench import correct as CK
from bench import yardstick
from bench.traffic import generator

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoAccelerator(RuntimeError):
    pass


# -- the manifest ------------------------------------------------------------

def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(manifest: dict, workload: str) -> dict:
    """The cell ``workload`` with its configuration, traffic and metrics."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        spec = json.load(f)

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    traffic = generator.load(cell["traffic"])
    if traffic["workers"] != cell["chips"]:
        raise ValueError(f"{workload}: traffic {cell['traffic']!r} has "
                         f"{traffic['workers']} workers, the cell "
                         f"{cell['chips']} chips (one worker per chip)")
    return {
        "cell": cell,
        "spec": spec,
        "traffic": traffic,
        "end_to_end": [m for m in manifest["end_to_end"] if listed(m)],
        "per_layer": [m for m in manifest["per_layer"] if listed(m)],
    }


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family_module(spec: dict):
    return importlib.import_module(f"bench.reference.{spec['reference']}")


# -- jax set-up ---------------------------------------------------------------

def enable_compile_cache(jax) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` where set, else a fixed directory in
    the checkout; every program is cached, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileStats:
    """Backend compiles and persistent-cache hits, from jax.monitoring."""

    def __init__(self, jax):
        self.compiles, self.hits = 0, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def count(self) -> int:
        return self.compiles + self.hits


def chips_for(jax, chips: int, require_accelerator: bool):
    devices = jax.devices()
    if require_accelerator:
        if devices[0].platform == "cpu":
            raise NoAccelerator("JAX finds no accelerator, only the CPU")
        if len(devices) < chips:
            raise NoAccelerator(f"the cell asks for {chips} chips, JAX finds "
                                f"{len(devices)}")
        yardstick.peaks(devices[0].device_kind)
    return devices[:chips]


def program_config(spec: dict):
    from repro.configs import get_config
    prog = spec["program"]
    return get_config(prog["preset"]).variant(**prog["fields"])


def make_weights(jax, family, spec, mesh, chips: int, seed: int):
    """The benchmark's weights, stacked over the replicas, on the chips, in
    one jitted call from the seed."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench.reference import common as C
    shapes = family.shapes(spec)
    key = C.seed_key(seed)

    def make(key):
        tree = C.init_tree(shapes, key)
        return jax.tree.map(
            lambda a: jax.numpy.broadcast_to(a[None], (chips,) + a.shape),
            tree)

    return jax.jit(make, out_shardings=NamedSharding(mesh, P("data")))(key)


def first_checked_step(train: dict) -> int:
    """Global step of the first checked step: the checked steps are a
    group step, the tau-sync and a group step."""
    return train["tau"] - 2


def build(jax, r: dict, seed: int, devices):
    """Trainer around the benchmark's weights, with the traffic installed."""
    from repro.core.replica import ReplicaState
    from repro.launch.mesh import make_mesh
    from repro.launch.train import Trainer
    from repro.optim import sgd

    cell, spec, mix, train = r["cell"], r["spec"], r["traffic"], \
        r["spec"]["train"]
    chips = cell["chips"]
    if train["averager"] != "wagma" or train["optimizer"] != "sgd":
        raise ValueError("the reference follows WAGMA with SGD momentum only")
    mesh = make_mesh((chips, 1), ("data", "model"), devices=devices)
    family = family_module(spec)
    params = make_weights(jax, family, spec, mesh, chips, seed)
    opt = sgd(train["learning_rate"], momentum=train["momentum"])
    opt_state = jax.jit(jax.vmap(opt.init))(params)
    tr = Trainer(program_config(spec), mesh, averager="wagma",
                 group_size=train["group_size"], tau=train["tau"],
                 optimizer="sgd", learning_rate=train["learning_rate"],
                 momentum=train["momentum"], seq_len=mix["seq_len"],
                 global_batch=mix["batch_per_worker"] * chips, seed=seed,
                 init_state=ReplicaState.create(params, opt_state))
    vocab = spec.get("vocab_size")
    data = generator.batches(mix, vocab, seed)
    # on the chips once, in the program's batch sharding, as a prefetching
    # input pipeline holds them: no host-to-device copy in a step
    staged = [{k: jax.device_put(v, tr._batch_sharding(v))
               for k, v in b.items()} for b in data]
    t0 = first_checked_step(train)

    def batch_fn(t, worker, bsz):
        with jax.profiler.TraceAnnotation("bench.batch"):
            return staged[(t - t0) % len(staged)]

    tr.batch_fn = batch_fn
    return tr, data, t0


def variant(tr, t: int) -> tuple:
    """The compiled step variant the program runs at global step ``t``."""
    av = tr.averager
    return ("sync",) if av.sync_due(t) else ("group", av.phase_for_step(t))


def warm_rest(tr, t0: int) -> int:
    """Steps past the checked ones until every variant has run on a state
    that a step made (a variant's first call on such a state still takes
    JAX's slow dispatch path once); returns the window's first step."""
    need = {("sync",)} | {("group", p) for p in range(tr.averager.n_phases)}
    t = t0 + CK.CHECKED_STEPS
    seen = {variant(tr, u) for u in range(t0 + 1, t)}
    while need - seen:
        tr.step_once(t)
        seen.add(variant(tr, t))
        t += 1
    return t


def program_readings(jax, r, tr, t0: int) -> tuple:
    """The checked steps through the window's own call, and the program's
    readings from its state: each step's loss, the first gradient's norms
    (SGD's momentum after one step from zero), and a host copy of the
    parameters after the checked steps, which ``change_readings`` turns
    into the change's norms once the program is freed, so that no copy of
    the weights sits beside the program's state on the chip.
    Returns (readings, seconds spent reading the state)."""
    losses, read_s = [], 0.0
    for i in range(CK.CHECKED_STEPS):
        losses.append(tr.step_once(t0 + i))
        if i == 0:
            r0 = time.perf_counter()
            grad_norms = CK.host(CK.leaf_norms(tr.state.opt_state.momentum))
            read_s += time.perf_counter() - r0
    r0 = time.perf_counter()
    after = jax.device_get(tr.state.params)
    read_s += time.perf_counter() - r0
    return {"losses": losses, "grad_norms": grad_norms,
            "params_after": after}, read_s


def change_readings(jax, r, mesh, prog: dict, seed: int) -> dict:
    """``prog`` with the parameters' change over the checked steps in
    place of its host copy of the parameters: per leaf and replica, the
    norm of that copy minus the initial weights, made anew from the seed
    by the call that made them for the program."""
    init = make_weights(jax, family_module(r["spec"]), r["spec"], mesh,
                        r["cell"]["chips"], seed)
    after = jax.device_put(prog["params_after"], jax.tree.map(
        lambda a: a.sharding, init))
    out = {k: v for k, v in prog.items() if k != "params_after"}
    out["change_norms"] = CK.host(CK.change_norms(after, init))
    return out


def reference_for(jax, r, data, t0, seed, mode="reference", fault=None):
    """The reference's readings of the checked steps, on the first device."""
    from bench.reference import common as C
    family = family_module(r["spec"])
    params0 = jax.jit(lambda k: C.init_tree(family.shapes(r["spec"]), k))(
        C.seed_key(seed))
    return CK.reference_readings(
        family, r["spec"], r["spec"]["train"], params0,
        data[:CK.CHECKED_STEPS], r["cell"]["chips"], t0, mode=mode,
        fault=fault)


def peak_bytes(devices) -> int:
    """The peak of bytes in use over the process's life, on the fullest
    chip."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))


def half_rates(ends: list, tokens_per_step: float) -> tuple:
    """From the seconds since the window opened at which each step ended:
    tokens per second over the steps that ended in the first half of the
    window, and over the rest."""
    n1 = int(np.searchsorted(ends, ends[-1] / 2, side="right"))
    if n1 == 0 or n1 == len(ends):
        return ()
    return (tokens_per_step * n1 / ends[n1 - 1],
            tokens_per_step * (len(ends) - n1) / (ends[-1] - ends[n1 - 1]))


# -- the run -------------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_accelerator: bool = True, cell: dict | None = None,
        limits: dict | None = None) -> dict:
    """One run of ``workload``; ``cell`` (a ``resolve`` result) and
    ``limits`` replace what the manifest names, for tests at small sizes."""
    r = cell or resolve(load_manifest(), workload)
    cell = r["cell"]
    chips = cell["chips"]
    limits = limits if limits is not None else CK.load_limits(workload)
    import jax
    cache = enable_compile_cache(jax)
    stats = CompileStats(jax)
    devices = chips_for(jax, chips, require_accelerator)
    kind = devices[0].device_kind
    log(f"cell {workload}: {chips} x {kind} ({devices[0].platform}), "
        f"seed {seed}, compile cache {cache}")

    from repro import compat
    tr, data, t0 = build(jax, r, seed, devices)
    n_params = sum(int(np.prod(l.shape[1:]))
                   for l in jax.tree.leaves(tr.params))
    print(f"{r['spec']['name']}: {n_params} parameters", flush=True)
    mix = r["traffic"]
    with compat.set_mesh(tr.mesh):
        prog, read_s = program_readings(jax, r, tr, t0)
        log(f"checked steps: losses {prog['losses']!r}")
        t = warm_rest(tr, t0)
        peak_setup = peak_bytes(devices)
        c0 = stats.count()
        # set-up's objects out of the collector's reach, and no collection
        # pause inside the window
        gc.collect()
        gc.freeze()
        gc.disable()
        w_start = time.perf_counter()
        setup_s = w_start - t_start - read_s
        times, ends, failed = [], [], 0
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # no per-call Python events
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            while True:
                s = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.step"):
                    loss = tr.step_once(t)
                e = time.perf_counter()
                times.append(e - s)
                ends.append(e - w_start)
                if not math.isfinite(loss) or \
                        tr.last_metrics.get("skipped_nonfinite", 0.0) > 0:
                    failed += 1
                t += 1
                if trace:
                    if len(times) >= r["spec"]["train"]["tau"]:  # one period
                        break
                elif e - w_start >= seconds:
                    break
        finally:
            gc.enable()
            gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
        window_s = e - w_start
        in_window = stats.count() - c0
    log(f"window: {len(times)} steps in {window_s!r} s, "
        f"compiles in window {in_window}")
    log("window halves: tokens/s/chip " + " ".join(
        repr(x) for x in half_rates(ends, generator.tokens_per_step(mix)
                                    / chips)))
    peak = peak_bytes(devices)
    log(f"memory peak: {peak_setup} B after set-up, {peak} B after the "
        f"window")
    mesh = tr.mesh
    del tr
    gc.collect()
    prog = change_readings(jax, r, mesh, prog, seed)

    ctx = {
        "setup_s": setup_s, "step_s": times, "window_s": window_s,
        "chips": chips, "tokens_per_step": generator.tokens_per_step(mix),
        "flops_per_step": yardstick.train_flops_per_step(r["spec"], mix),
        "steps": len(times), "device_kind": kind,
    }
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak}
    out = {}
    if trace:
        from bench import trace as TR
        red = TR.reduce(TR.load(TRACE_DIR), [d.id for d in devices])
        ctx["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = red["breakdown"]
        metrics = r["per_layer"]
    else:
        metrics = r["end_to_end"]
    values = {}
    for m in metrics:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    ref = reference_for(jax, r, data, t0, seed)
    numbers = CK.gaps(prog, ref)
    ok = CK.verdict(numbers, limits) and failed == 0
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    result = {"correct": bool(ok), "attempted": len(times), "failed": failed,
              "metrics": values, "device": device, **out,
              "compiles_in_window": in_window, "checks": checks}
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start)
    except NoAccelerator as e:
        log(f"error: {e}")
        return 3
    print(f"compiles in window: {result['compiles_in_window']}", flush=True)
    for k, c in result["checks"].items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
