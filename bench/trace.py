"""From a profiler trace to device busy, idle and per-class time.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps what the
per-layer metrics need, in a plain form (``{"devices": {id: [[name,
start_ns, dur_ns, kind], ...]}, "host": [[name, start_ns, dur_ns],
...]}``, ``kind`` from ``op_kind``) that tests can build by hand or read
from a recorded file.  Device ops are the TPU planes' "XLA Ops" line;
host spans are those of the thread that ran the ``bench.step`` spans.  ``reduce`` turns that into the numbers:

* the window: from the start of the first ``bench.step`` host span to the
  end of the last;
* per device, busy time: the union of its operations' intervals inside
  the window;
* per device and class, the union of the intervals of the operations of
  that class: ``collective`` (all-reduce, all-gather, reduce-scatter,
  collective-permute, all-to-all, send/recv), ``combine`` (the averaging
  combine kernel) and ``compute`` (everything else, loop containers
  included: a collective inside a loop would count as hidden);
* the breakdown: the operations that took most device time, and the
  longest idle gaps named by the innermost host span around their middle.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|send|recv)(-start|-done)?\(")
CONTAINER = re.compile(r"\s(while|conditional|call)\(")
# The averaging combine is a Mosaic custom call; the trace names it after
# the jitted wrapper in kernels/ops.py (``_combine_jit``,
# ``_combine_multi_jit``), not after the kernel function.
COMBINE = re.compile(r"^_combine")
STEP_SPAN = "bench.step"
OPS_LINE = "XLA Ops"
TOP = 10


def op_kind(hlo_text: str) -> tuple:
    """(short name, kind) of a device op from its HLO text, where kind is
    ``combine``, ``collective``, ``container`` (a while, conditional or
    call whose body's ops appear as ops of their own) or ``compute``."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%")
    if COMBINE.match(name) and "tpu_custom_call" in hlo_text:
        return name, "combine"
    if COLLECTIVE.search(hlo_text):
        return name, "collective"
    if CONTAINER.search(hlo_text):
        return name, "container"
    return name, "compute"


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir``, in the plain form."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, kind = op_kind(ev.name)
                    ops.append([name, int(ev.start_ns), int(ev.duration_ns),
                                kind])
            devices[m.group(1)] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                       for ev in line.events]
                if any(e[0] == STEP_SPAN for e in evs):
                    host.extend(evs)
    return {"devices": devices, "host": host}


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window(host) -> tuple:
    steps = [(s, s + d) for n, s, d in host if n == STEP_SPAN]
    if not steps:
        raise ValueError(f"no {STEP_SPAN!r} span in the trace")
    return min(s for s, _ in steps), max(e for _, e in steps), len(steps)


def host_label(host, t: int) -> str:
    """The innermost host span around time ``t``."""
    best = None
    for n, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (n, d)
    return best[0] if best else "(no host span)"


def reduce(tr: dict, device_ids=None) -> dict:
    """Window, busy and per-class time per device, and breakdown."""
    lo, hi, n_steps = window(tr["host"])
    ids = [str(i) for i in device_ids] if device_ids is not None \
        else sorted(tr["devices"])
    per, op_time, gaps = {}, {}, []
    for did in ids:
        ops = tr["devices"].get(did, [])
        by_cat = {"compute": [], "collective": [], "combine": []}
        for name, start, dur, kind in ops:
            iv = [start, start + dur]
            by_cat["compute" if kind == "container" else kind].append(iv)
            cut = clip([iv], lo, hi)
            if cut and kind != "container":
                op_time[name] = op_time.get(name, 0) + total(cut)
        busy = clip(union(iv for v in by_cat.values() for iv in v), lo, hi)
        cls = {k: clip(union(v), lo, hi) for k, v in by_cat.items()}
        per[did] = {
            "busy_ns": total(busy),
            "compute_ns": total(cls["compute"]),
            "collective_ns": total(cls["collective"]),
            "combine_ns": total(cls["combine"]),
        }
        if did == ids[0]:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, host_label(tr["host"], (s + e) // 2)))
    n = len(ids)
    mean = lambda k: sum(p[k] for p in per.values()) / n / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) / 1e9,
        "steps": n_steps,
        "busy_s": mean("busy_ns"),
        "compute_s": mean("compute_ns"),
        "collective_s": mean("collective_ns"),
        "combine_s": mean("combine_ns"),
        "per_device": per,
        "breakdown": {
            "device_ops": [[name, t / n / 1e9] for name, t in top_ops],
            "idle_gaps": [[label, t / 1e9] for t, label in gaps[:TOP]],
        },
    }
