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
one of their names).

Each op falls in scopes by ``scopes_of``: one phase of ``forward`` (a path
segment ``forward``, under any transform but ``transpose``), ``backward``
(``transpose(jvp(forward))``, the recompute included), ``optimizer``,
``average`` (``average`` or ``sync``) and ``unscoped``; besides,
``recompute`` (``rematted_computation``, a part of backward) and
``attention`` (an ``attention`` segment, in forward, backward and recompute
alike); and every other named-scope segment of its ``op_name``
(``jax.named_scope``'s names and the functions of nested jits, bare of
JAX's transform wrappers).  Where an op's ``op_name`` joins several names
with ``;`` (a fusion of ops of several origins), the first name decides.
An op with no ``op_name`` (a copy, slice or broadcast the compiler added,
or the wait for one) takes that of the leaf op before it on its device: it
serves the computation it runs amid.

``reduce`` gives, per device and averaged over the devices, in seconds
over the window (``trace.window``):

* ``leaf_s``: the union of every leaf op's interval (loop containers left
  out), clipped to the window;
* ``idle_s``: device idle time in the window (the complement of the union
  of every op's interval, as ``trace.reduce`` has it), and ``idle_in_s``:
  that idle time inside the union of the intervals of ``READ_SPANS``
  (``read``) and of ``LAUNCH_SPANS`` (``launch``);
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

The metric readers' side reduces a scope on demand and keeps it in
``ctx``: ``by_name`` and ``scope_ms`` (the union of the intervals of the
leaf ops that carry any one name), ``calls_per_step`` (a kernel's calls by
its op name's pattern) and ``roofline_share`` (a kernel's roofline time
over its scope's device time).  A counter that the program stamps on a
host span as a stat reads through ``stat_rise_per_step``.  A metric on a
new scope, kernel or counter is then a metric file alone.
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
_BARE = re.compile(r"(?:\w+\()*(.*?)\)*")


def scopes_of(op_name: str) -> tuple:
    """The scopes of a device op from its ``op_name``: its phase (one of
    ``PHASES``), then ``recompute`` and ``attention`` where they apply,
    then every other named-scope segment of its path (each segment but the
    last, which names the op's primitive), bare of transform wrappers; a
    segment named like one of ``SCOPES`` keeps that scope's meaning."""
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
    for seg in segs[:-1]:
        bare = _BARE.fullmatch(seg).group(1)
        if bare and bare not in out and bare not in SCOPES:
            out += (bare,)
    return out


_scopes = functools.lru_cache(maxsize=None)(scopes_of)


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


def device_ops(tr: dict, did: str, shift_ns: int = 0):
    """Device ``did``'s ops in time order, moved later by ``shift_ns``:
    ``(name, [start, end], kind, op_name)``, where a leaf op with no
    ``op_name`` takes that of the leaf op before it."""
    last = ""
    for name, start, dur, kind, op_name in sorted(
            tr["devices"].get(did, []), key=lambda o: o[1]):
        start += shift_ns
        if kind != "container":
            last = op_name or last
        yield name, [start, start + dur], kind, last


def stamps(host, lo: int, hi: int, span: str, stat: str) -> list:
    """The ``stat`` of each ``span`` host span that starts in the window
    and carries it, in time order."""
    return [st[stat] for name, s, _, st in sorted(host, key=lambda h: h[1])
            if name == span and lo <= s < hi and stat in st]


def reduce(tr: dict, device_ids=None) -> dict:
    """Leaf and idle device time, and idle time inside the program's host
    spans, over the window; with the window and the devices' clock shifts,
    for the reductions made on demand."""
    host = tr["host"]
    lo, hi, _ = T.window([h[:3] for h in host])
    ids = [str(i) for i in device_ids] if device_ids is not None \
        else sorted(tr["devices"])
    spans = lambda names: T.clip(T.union(
        [s, s + d] for n, s, d, _ in host if n in names), lo, hi)
    program = {"read": spans(READ_SPANS), "launch": spans(LAUNCH_SPANS)}
    idle_in = {k: 0 for k in program}
    leaf_total = idle_total = 0
    shifts = {}
    for did in ids:
        dt = shifts[did] = clock_shift(host, tr.get("modules", {}).get(did, []))
        leaf, busy = [], []
        for _, iv, kind, _ in device_ops(tr, did, dt):
            busy.append(iv)
            if kind != "container":
                leaf.append(iv)
        leaf_total += T.total(T.clip(T.union(leaf), lo, hi))
        busy = T.clip(T.union(busy), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]
        idle_total += T.total(idle)
        for k, ivs in program.items():
            idle_in[k] += T.total(intersect(idle, ivs))
    n = max(len(ids), 1)
    return {
        "leaf_s": leaf_total / n / 1e9,
        "idle_s": idle_total / n / 1e9,
        "idle_in_s": {k: v / n / 1e9 for k, v in idle_in.items()},
        "span_s": {k: T.total(v) / 1e9 for k, v in program.items()},
        "clock_shift_s": sum(shifts.values()) / n / 1e9,
        "window_ns": [lo, hi],
        "shift_ns": shifts,
    }


# -- the metric readers' side --------------------------------------------------

def trace_of(ctx: dict) -> dict:
    """The traced run's trace in this module's plain form, loaded once from
    the harness's trace directory and kept in ``ctx``."""
    if "scope_trace" not in ctx:
        from bench.harness import TRACE_DIR
        ctx["scope_trace"] = load(TRACE_DIR)
    return ctx["scope_trace"]


def readings(ctx: dict) -> dict:
    """``reduce`` of the traced run's trace, for the metric readers: made
    once and kept in ``ctx``, whose reduced trace (``ctx["trace"]``) names
    the devices."""
    if "scopes" not in ctx:
        ctx["scopes"] = reduce(trace_of(ctx),
                               list(ctx["trace"]["per_device"]))
    return ctx["scopes"]


def by_name(ctx: dict, name: str) -> dict:
    """Device seconds of the leaf ops that carry scope ``name``, any name
    ``scopes_of`` reports (the union of their intervals, clipped to the
    window, averaged over the devices), and how many ops of the window
    carry it; reduced on the first call for ``name`` and kept in ``ctx``."""
    red = readings(ctx)
    cache = red.setdefault("by_name", {})
    if name not in cache:
        tr = trace_of(ctx)
        lo, hi = red["window_ns"]
        total = ops = 0
        for did, dt in red["shift_ns"].items():
            mine = []
            for _, iv, kind, op_name in device_ops(tr, did, dt):
                if kind != "container" and name in _scopes(op_name):
                    mine.append(iv)
                    ops += iv[0] < hi and iv[1] > lo
            total += T.total(T.clip(T.union(mine), lo, hi))
        n = max(len(red["shift_ns"]), 1)
        cache[name] = {"s": total / n / 1e9, "ops": ops}
    return cache[name]


def scope_ms(ctx: dict, scope: str):
    """Device milliseconds per step in ``scope``; None where no op of the
    window carries it (a program without the scope)."""
    got = by_name(ctx, scope)
    if not got["ops"]:
        return None
    return 1e3 * got["s"] / ctx["trace"]["steps"]


def calls_per_step(ctx: dict, pattern: str,
                   scope: str | None = None) -> float:
    """Leaf ops per step, averaged over the devices, whose name (the HLO
    instruction's, such as a kernel's ``splash_mqa_fwd_residuals.19``)
    matches the regular expression ``pattern`` from its start, which start
    in the window and, where ``scope`` is given, carry that scope."""
    rx = re.compile(pattern)
    red, tr = readings(ctx), trace_of(ctx)
    lo, hi = red["window_ns"]
    n = sum(kind != "container" and lo <= iv[0] < hi and bool(rx.match(name))
            and (scope is None or scope in _scopes(op_name))
            for did, dt in red["shift_ns"].items()
            for name, iv, kind, op_name in device_ops(tr, did, dt))
    return n / max(len(red["shift_ns"]), 1) / ctx["trace"]["steps"]


def stat_rise_per_step(ctx: dict, span: str, stat: str):
    """A counter the program stamps as the stat ``stat`` of each ``span``
    host span: its rise from the window's first such span to its last,
    over the steps between them; None with fewer than two stamps."""
    lo, hi = readings(ctx)["window_ns"]
    got = stamps(trace_of(ctx)["host"], lo, hi, span, stat)
    if len(got) < 2:
        return None
    return (got[-1] - got[0]) / (len(got) - 1)


def roofline_share(ctx: dict, scope: str, roofline_s_per_step: float):
    """Percent of the device time per step of the ops under ``scope`` that
    the roofline time ``roofline_s_per_step`` (``yardstick.roofline_s`` of
    the kernels' work) takes; None where no op of the window carries the
    scope or the kernels read no work."""
    ms = scope_ms(ctx, scope)
    if not ms or not roofline_s_per_step:
        return None
    return 100.0 * 1e3 * roofline_s_per_step / ms


def idle_ms(ctx: dict, spans: str):
    """Device idle milliseconds per step inside the ``read`` or ``launch``
    spans; None where the trace has no such span."""
    r = readings(ctx)
    if not r["span_s"][spans]:
        return None
    return 1e3 * r["idle_in_s"][spans] / ctx["trace"]["steps"]
