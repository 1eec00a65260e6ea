"""From a profiler trace to device time by the program's scopes, and device
idle time inside the program's host spans.

The program names its parts in the one trace the benchmark already reads:
``jax.named_scope`` puts ``forward``, ``attention``, ``optimizer``,
``average`` and ``sync`` into each device op's ``op_name`` (JAX names the
backward ``transpose(jvp(forward))`` and the remat recompute
``.../rematted_computation/...``), and ``Trainer.step_once`` writes the
host spans ``trainer.put_batch``, ``trainer.dispatch`` and
``trainer.read_metrics`` (stat ``host_reads``: the program's counter of
blocking device-to-host reads as the span opens) on the stepping thread.

``load`` keeps what ``bench/trace.py``'s plain form keeps, and more: each
device op as ``[name, start_ns, dur_ns, kind, op_name]`` and each host span
as ``[name, start_ns, dur_ns, stats]``.  A TPU op's ``op_name`` is the
``tf_op`` stat of its event's metadata (``""`` where it has none), which
``ProfileData`` does not show: ``op_names`` reads it from the ``.xplane.pb``
with a schema of the few XSpace fields it needs, and matches it to the
events by their HLO text (an op of the same text in two programs takes
one of their names).  ``reduce`` adds, per device and averaged over the
devices, in seconds over the window (``trace.window``):

* ``scope_s``: the union of the intervals of the leaf ops (loop containers
  left out) of each scope, clipped to the window.  Each op falls in one of
  ``forward`` (a path segment ``forward``, under any transform but
  ``transpose``), ``backward`` (``transpose(jvp(forward))``, the
  recompute included), ``optimizer``, ``average`` (``average`` or
  ``sync``) and ``unscoped``; besides, ``recompute``
  (``rematted_computation``, a part of backward) and ``attention`` (an
  ``attention`` segment, in forward, backward and recompute alike).  Where
  an op's ``op_name`` joins several names with ``;`` (a fusion of ops of
  several origins), the first name decides.  An op with no ``op_name`` (a
  copy, slice or broadcast the compiler added, or the wait for one) takes
  that of the leaf op before it on its device: it serves the computation
  it runs amid;
* ``leaf_s``: the union of every leaf op's interval, clipped to the window;
* ``scope_ops``: how many ops of the window carry each scope;
* ``idle_s``: device idle time in the window (the complement of the union
  of every op's interval, as ``trace.reduce`` has it), and ``idle_in_s``:
  that idle time inside the union of the intervals of ``READ_SPANS``
  (``read``) and of ``LAUNCH_SPANS`` (``launch``);
* ``host_reads``: the ``host_reads`` stat of each ``trainer.read_metrics``
  span in the window, in order;
* ``clock_shift_s``: how far each device's times were moved first.  The
  profiler puts the device planes on the host's clock only to within about
  a millisecond, and a step's device work then seems to start before the
  host began to dispatch it.  ``load`` keeps each device's program runs
  (``modules``, the "XLA Modules" line), and ``reduce`` moves the device's
  times later by the least amount that starts no run before its
  ``trainer.dispatch`` span, pairing the k-th run with the k-th span where
  their counts agree.  A device that already reads no earlier than its
  dispatch is left as it is: the shift corrects the sign of the skew, not
  its size.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from bench import trace as T

# the stat of a TPU op's event metadata that holds its ``op_name``
OP_NAME_STAT = "tf_op"
PHASES = ("forward", "backward", "optimizer", "average", "unscoped")
SCOPES = PHASES + ("recompute", "attention")
READ_SPANS = ("trainer.read_metrics",)
DISPATCH_SPAN = "trainer.dispatch"
MODULES_LINE = "XLA Modules"
LAUNCH_SPANS = ("trainer.put_batch", DISPATCH_SPAN)


def _segment(*names):
    """A path segment that is one of ``names``, bare or under JAX's
    transform wrappers (``jvp(forward)``, ``transpose(jvp(forward))``)."""
    return re.compile(r"(?:\w+\()*(?:{})\)*".format(
        "|".join(map(re.escape, names))))


_FORWARD, _ATTENTION = _segment("forward"), _segment("attention")
_OPTIMIZER, _AVERAGE = _segment("optimizer"), _segment("average", "sync")


def scopes_of(op_name: str) -> tuple:
    """The scopes of a device op from its ``op_name``: its phase (one of
    ``PHASES``), then ``recompute`` and ``attention`` where they apply."""
    segs = op_name.split(";", 1)[0].split("/")
    has = lambda rx: any(rx.fullmatch(s) for s in segs)
    fwd = [s for s in segs if _FORWARD.fullmatch(s)]
    if fwd:
        phase = "backward" if any("transpose(" in s for s in fwd) \
            else "forward"
    elif has(_OPTIMIZER):
        phase = "optimizer"
    elif has(_AVERAGE):
        phase = "average"
    else:
        phase = "unscoped"
    out = (phase,)
    if phase == "backward" and "rematted_computation" in segs:
        out += ("recompute",)
    if has(_ATTENTION):
        out += ("attention",)
    return out


@functools.lru_cache(maxsize=1)
def _xspace():
    """The message class of an XSpace (tsl/profiler/protobuf/xplane.proto)
    cut to the fields that name each op: the planes' names and their event
    and stat metadata."""
    from google.protobuf import descriptor_pb2, message_factory
    F = descriptor_pb2.FieldDescriptorProto
    OPT, REP = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    I64, U64, STR = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING
    fdp = descriptor_pb2.FileDescriptorProto(name="xspace_names.proto",
                                             package="xs")

    def message(name, *fields, map_entry=False):
        """A message of ``fields`` (name, number, label, type); a type
        given as a string is a message of this file."""
        m = fdp.message_type.add(name=name)
        m.options.map_entry = map_entry
        for fname, number, label, ftype in fields:
            f = m.field.add(name=fname, number=number, label=label)
            if isinstance(ftype, str):
                f.type, f.type_name = F.TYPE_MESSAGE, ".xs." + ftype
            else:
                f.type = ftype

    message("XStat", ("metadata_id", 1, OPT, I64), ("str_value", 5, OPT, STR),
            ("ref_value", 7, OPT, U64))
    message("XEventMetadata", ("name", 2, OPT, STR),
            ("stats", 5, REP, "XStat"))
    message("XStatMetadata", ("name", 2, OPT, STR))
    message("EventMetadataEntry", ("key", 1, OPT, I64),
            ("value", 2, OPT, "XEventMetadata"), map_entry=True)
    message("StatMetadataEntry", ("key", 1, OPT, I64),
            ("value", 2, OPT, "XStatMetadata"), map_entry=True)
    message("XPlane", ("name", 2, OPT, STR),
            ("event_metadata", 4, REP, "EventMetadataEntry"),
            ("stat_metadata", 5, REP, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, REP, "XPlane"))
    return message_factory.GetMessages([fdp])["xs.XSpace"]


def op_names(path: str) -> dict:
    """``{TPU plane name: {op event name: op_name}}`` from the ``tf_op``
    stat of each op's event metadata in the ``.xplane.pb`` at ``path``."""
    with open(path, "rb") as f:
        space = _xspace().FromString(f.read())
    out = {}
    for plane in space.planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        stat = {k: v.name for k, v in plane.stat_metadata.items()}
        names = out[plane.name] = {}
        for md in plane.event_metadata.values():
            for st in md.stats:
                if stat.get(st.metadata_id) == OP_NAME_STAT:
                    names[md.name] = st.str_value or stat.get(st.ref_value,
                                                              "")
    return out


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir``, in this module's
    plain form (device ops with their ``op_name``, host spans with their
    stats, each device's program runs as ``[start_ns, dur_ns]``)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    named = op_names(paths[-1])
    devices, host, modules = {}, [], {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            names, kinds, ops = named.get(plane.name, {}), {}, []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[m.group(1)] = [
                        [int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
                if line.name != T.OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.name not in kinds:
                        kinds[ev.name] = T.op_kind(ev.name)
                    name, kind = kinds[ev.name]
                    ops.append([name, int(ev.start_ns), int(ev.duration_ns),
                                kind, names.get(ev.name, "")])
            devices[m.group(1)] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = list(line.events)
                if any(ev.name == T.STEP_SPAN for ev in evs):
                    host.extend([ev.name, int(ev.start_ns),
                                 int(ev.duration_ns), dict(ev.stats)]
                                for ev in evs)
    return {"devices": devices, "host": host, "modules": modules}


def clock_shift(host, runs) -> int:
    """Nanoseconds to add to a device's times so that none of its program
    runs starts before the ``trainer.dispatch`` span that launched it: the
    k-th run pairs with the k-th span; 0 where their counts differ."""
    spans = sorted(s for n, s, _, _ in host if n == DISPATCH_SPAN)
    starts = sorted(s for s, _ in runs)
    if not spans or len(spans) != len(starts):
        return 0
    return max(0, max(a - b for a, b in zip(spans, starts)))


def intersect(a, b) -> list:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce(tr: dict, device_ids=None) -> dict:
    """Per-scope device time, idle time inside the program's host spans and
    the read counter's stamps, over the window."""
    host = tr["host"]
    lo, hi, _ = T.window([h[:3] for h in host])
    ids = [str(i) for i in device_ids] if device_ids is not None \
        else sorted(tr["devices"])
    spans = lambda names: T.clip(T.union(
        [s, s + d] for n, s, d, _ in host if n in names), lo, hi)
    program = {"read": spans(READ_SPANS), "launch": spans(LAUNCH_SPANS)}
    sums = {k: 0 for k in SCOPES + ("leaf",)}
    idle_in = {k: 0 for k in program}
    idle_total = 0
    counts = {k: 0 for k in SCOPES}
    of = functools.lru_cache(maxsize=None)(scopes_of)
    shift = 0
    for did in ids:
        dt = clock_shift(host, tr.get("modules", {}).get(did, []))
        shift += dt
        by_scope = {k: [] for k in SCOPES}
        leaf, busy, last = [], [], ""
        for _, start, dur, kind, op_name in sorted(
                tr["devices"].get(did, []), key=lambda o: o[1]):
            start += dt
            iv = [start, start + dur]
            busy.append(iv)
            if kind == "container":
                continue
            leaf.append(iv)
            last = op_name or last
            in_window = start < hi and start + dur > lo
            for k in of(last):
                by_scope[k].append(iv)
                counts[k] += in_window
        for k, ivs in by_scope.items():
            sums[k] += T.total(T.clip(T.union(ivs), lo, hi))
        sums["leaf"] += T.total(T.clip(T.union(leaf), lo, hi))
        busy = T.clip(T.union(busy), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]
        idle_total += T.total(idle)
        for k, ivs in program.items():
            idle_in[k] += T.total(intersect(idle, ivs))
    n = max(len(ids), 1)
    reads = [st["host_reads"] for name, s, _, st in sorted(
        host, key=lambda h: h[1])
        if name in READ_SPANS and lo <= s < hi and "host_reads" in st]
    return {
        "scope_s": {k: sums[k] / n / 1e9 for k in SCOPES},
        "leaf_s": sums["leaf"] / n / 1e9,
        "scope_ops": counts,
        "idle_s": idle_total / n / 1e9,
        "idle_in_s": {k: v / n / 1e9 for k, v in idle_in.items()},
        "span_s": {k: T.total(v) / 1e9 for k, v in program.items()},
        "host_reads": reads,
        "clock_shift_s": shift / n / 1e9,
    }


def readings(ctx: dict) -> dict:
    """``reduce`` of the traced run's trace, for the metric readers: made
    once from the harness's trace directory and kept in ``ctx``, whose
    reduced trace (``ctx["trace"]``) names the devices."""
    if "scopes" not in ctx:
        from bench.harness import TRACE_DIR
        ctx["scopes"] = reduce(load(TRACE_DIR),
                               list(ctx["trace"]["per_device"]))
    return ctx["scopes"]


def scope_ms(ctx: dict, scope: str):
    """Device milliseconds per step in ``scope``; None where no op of the
    window carries it (a program without the scope)."""
    r = readings(ctx)
    if not r["scope_ops"][scope]:
        return None
    return 1e3 * r["scope_s"][scope] / ctx["trace"]["steps"]


def idle_ms(ctx: dict, spans: str):
    """Device idle milliseconds per step inside the ``read`` or ``launch``
    spans; None where the trace has no such span."""
    r = readings(ctx)
    if not r["span_s"][spans]:
        return None
    return 1e3 * r["idle_in_s"][spans] / ctx["trace"]["steps"]
