"""Topology-aware compiled averaging plans (DESIGN.md §9).

The paper's butterfly is topology-aware by construction: low XOR bits ride
intra-pod links (ICI), high bits ride inter-pod links (DCN).  Before this
module, that structure was implicit — every entry point took ~10 threaded
kwargs (``offset/P/S/axis_names/axis_sizes/average_dtype/fused/bucket_bytes/
use_pallas/overlap/tau``) with ONE bucket budget and ONE set of alpha/beta
constants for all links.  This module makes the collective a compiled
artifact instead:

    topology = Topology.hierarchical(names, sizes, dcn_axes=("pod",))
    plan     = compile_plan(topology, params, AveragingConfig(group_size=S))
    ...inside shard_map (manual over the dp axes)...
    new      = plan.average(params, phase)      # wait-avoiding group step
    new      = plan.sync(params)                # tau-periodic global step

``compile_plan`` runs once per (topology, config, tree structure) — cached —
and precomputes everything the kwargs used to re-derive per call:

* **stage classification** — which butterfly bit of which phase offset rides
  which mesh axis, hence which :class:`LinkClass` (Layered-SGD's split of
  the averaging hierarchy along the physical interconnect);
* **per-link-class bucket budgets** — ``choose_class_bucket_bytes`` sweeps
  the per-class alpha-beta-gamma pipeline model (MG-WFBP: bucket-merge
  decisions against per-link cost constants, not a global 32 MiB default),
  so ICI stages get their own budget and DCN stages theirs;
* **per-class bucket layouts** and the wavefront schedule each stage run
  executes under (core/overlap.py).

Execution walks the offset's stages as maximal **runs** of equal link class:
the tree is cast to the accumulation dtype once, packed into the run's
class layout, butterflied in wavefront order, and repacked only at class
boundaries.  Per element the arithmetic is unchanged — ``log2(S)`` adds in
stage order, then one scale — so the plan path stays bit-identical to the
per-leaf reference and the stacked simulator under fp32 accumulation, for
any topology (pinned by tests/test_plan.py on every phase offset).

Migration note: the ``group_allreduce.group_average(...)`` kwarg shims
completed their deprecation cycle and are now hard errors; construct a
:class:`Topology` and hold the plan.

Sharded replicas (DESIGN.md §10): ``compile_plan(..., sharding=
ShardingPolicy.fsdp_within_pod(axis))`` compiles the FSDP-within-pod
realisation — the state is the plan's shard-aligned bucket buffers, the
butterfly runs pod-to-pod on each device's shard slice, and
``shard_tree``/``unshard_tree``/``grad_shards`` provide the intra-pod
gather/scatter collectives the train step composes around it.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bucketing, grouping
from repro.core import overlap as pipeline
from repro.core import streaming
from repro.core.replica import REPLICATED, ShardingPolicy


# ---------------------------------------------------------------------------
# Link classes and topologies
# ---------------------------------------------------------------------------

# Default network constants (Piz Daint-scale Aries; the single-class legacy
# model).  group_allreduce re-exports these names for its cost-model API.
DEFAULT_ALPHA = 20e-6          # seconds per collective launch
DEFAULT_BETA = 1.0 / 10e9      # seconds per wire byte
# Combine throughput: 2 reads + 1 write at P100-scale HBM (~700 GB/s) —
# seconds per *payload* byte per stage.  gamma << beta is why the combine
# can hide entirely behind the wire once the schedule overlaps them.
DEFAULT_GAMMA = 3.0 / 700e9


@dataclass(frozen=True)
class LinkClass:
    """One class of physical link with its own cost constants.

    ``alpha``  seconds per collective launch on this link class;
    ``beta``   seconds per wire byte (inverse bandwidth);
    ``gamma``  combine seconds per payload byte (HBM-side, link-independent
               in principle but kept per class so calibration can differ);
    ``bucket_bytes`` pins this class's bucket budget; ``None`` lets
    :func:`choose_class_bucket_bytes` pick the modeled argmin.
    """
    name: str
    alpha: float = DEFAULT_ALPHA
    beta: float = DEFAULT_BETA
    gamma: float = DEFAULT_GAMMA
    bucket_bytes: Optional[int] = None


# The flat single-class default reproduces the legacy (pre-plan) constants.
DEFAULT_LINK = LinkClass("link")
# Hierarchical defaults: intra-pod ICI (fast, cheap launches) vs inter-pod
# DCN (slow, expensive launches).  Replace with measured constants
# (ROADMAP: calibration) via LinkClass(...) when a real pod is available.
ICI = LinkClass("ici", alpha=1e-6, beta=1.0 / 100e9)
DCN = LinkClass("dcn", alpha=50e-6, beta=1.0 / 10e9)

# The one canonical location of the calibrated link constants.  Every loader
# (``Topology.with_measured`` with no path, ``benchmarks/calibrate_links.py``'s
# default ``--out``, the serving KV-transfer cost model) resolves through this
# constant so there is exactly one tracked file to regenerate.
DEFAULT_LINK_CONSTANTS_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "LINK_CONSTANTS.json")


@dataclass(frozen=True)
class Topology:
    """Frozen map from dp mesh axes (minor-to-major) to link classes.

    ``axis_names``/``axis_sizes`` follow ``group_allreduce.dp_axis_layout``
    order: minor-to-major, so global dp-rank bit b lives on the axis whose
    cumulative log2 size spans b (``grouping.split_bit_over_axes``).
    ``axis_class[i]`` indexes ``link_classes`` for axis i.
    """
    axis_names: Tuple[str, ...]
    axis_sizes: Tuple[int, ...]
    link_classes: Tuple[LinkClass, ...]
    axis_class: Tuple[int, ...]

    def __post_init__(self):
        if not (len(self.axis_names) == len(self.axis_sizes)
                == len(self.axis_class)):
            raise ValueError("axis_names/axis_sizes/axis_class length mismatch")
        for s in self.axis_sizes:
            grouping.ilog2(s)          # powers of two only
        for c in self.axis_class:
            if not 0 <= c < len(self.link_classes):
                raise ValueError(f"axis_class index {c} out of range")

    @classmethod
    def flat(cls, axis_names: Sequence[str], axis_sizes: Sequence[int],
             link: LinkClass = DEFAULT_LINK) -> "Topology":
        """Single link class for every axis — the legacy behaviour."""
        names = tuple(axis_names)
        return cls(names, tuple(int(s) for s in axis_sizes), (link,),
                   (0,) * len(names))

    @classmethod
    def hierarchical(cls, axis_names: Sequence[str],
                     axis_sizes: Sequence[int], *,
                     dcn_axes: Sequence[str] = ("pod",),
                     ici: LinkClass = ICI,
                     dcn: LinkClass = DCN) -> "Topology":
        """Axes named in ``dcn_axes`` ride DCN; all others ride ICI."""
        names = tuple(axis_names)
        classes = tuple(1 if a in dcn_axes else 0 for a in names)
        if 1 not in classes:
            return cls.flat(names, axis_sizes, link=ici)
        return cls(names, tuple(int(s) for s in axis_sizes), (ici, dcn),
                   classes)

    @property
    def P(self) -> int:
        p = 1
        for s in self.axis_sizes:
            p *= s
        return p

    def class_of_bit(self, bit: int) -> int:
        ax, _ = grouping.split_bit_over_axes(bit, self.axis_sizes)
        return self.axis_class[ax]

    def link_of_bit(self, bit: int) -> LinkClass:
        return self.link_classes[self.class_of_bit(bit)]

    def axis_of_bit(self, bit: int) -> str:
        ax, _ = grouping.split_bit_over_axes(bit, self.axis_sizes)
        return self.axis_names[ax]

    def bottleneck(self) -> LinkClass:
        """The slowest-wire class — what a global collective is bound by."""
        return max(self.link_classes, key=lambda l: l.beta)

    def drop_axis(self, name: str) -> "Topology":
        """This topology minus one dp axis (the FSDP shard axis).

        The remaining axes keep their minor-to-major order and their link
        classes; the result is the *effective* (pod-level) replica space a
        sharded plan butterflies over.
        """
        if name not in self.axis_names:
            raise ValueError(f"axis {name!r} not in {self.axis_names}")
        keep = [i for i, a in enumerate(self.axis_names) if a != name]
        if not keep:
            raise ValueError("cannot drop the only dp axis")
        return Topology(tuple(self.axis_names[i] for i in keep),
                        tuple(self.axis_sizes[i] for i in keep),
                        self.link_classes,
                        tuple(self.axis_class[i] for i in keep))

    def classes_in_use(self) -> Tuple[int, ...]:
        return tuple(sorted(set(self.axis_class)))

    def with_measured(self, path: Optional[str] = None) -> "Topology":
        """This topology with calibrated link constants loaded from disk.

        ``path`` defaults to :data:`DEFAULT_LINK_CONSTANTS_PATH` (the one
        tracked ``LINK_CONSTANTS.json`` at the repo root); it is a file
        written by
        ``benchmarks/calibrate_links.py`` (ROADMAP: measured alpha/beta/
        gamma constants): per mesh axis, the microbenched collective launch
        latency, inverse wire bandwidth, and combine throughput.  Each link
        class takes the *slowest* measurement among its axes (conservative
        — the class cost model prices the class's worst link).  A class's
        alpha/beta price BOTH the butterfly ppermutes and the FSDP
        all-gather/reduce-scatter path (``modeled_fsdp_step_seconds``), so
        when the file also carries ``ag_alpha``/``ag_beta`` the class takes
        the slower of the ppermute and all-gather measurements.  Classes
        with no measured axis keep their assumed defaults, and pinned
        ``bucket_bytes`` survive.
        """
        import json
        with open(path or DEFAULT_LINK_CONSTANTS_PATH) as f:
            data = json.load(f)
        axes = data.get("axes", {})
        new_classes = []
        for ci, link in enumerate(self.link_classes):
            ms = [axes[a] for a, c in zip(self.axis_names, self.axis_class)
                  if c == ci and a in axes]
            if not ms:
                new_classes.append(link)
                continue
            new_classes.append(LinkClass(
                link.name + "@measured",
                alpha=max(max(float(m["alpha"]),
                              float(m.get("ag_alpha", 0.0))) for m in ms),
                beta=max(max(float(m["beta"]),
                             float(m.get("ag_beta", 0.0))) for m in ms),
                gamma=max(float(m.get("gamma", link.gamma)) for m in ms),
                bucket_bytes=link.bucket_bytes))
        return Topology(self.axis_names, self.axis_sizes,
                        tuple(new_classes), self.axis_class)

    def describe(self) -> str:
        parts = []
        for i, link in enumerate(self.link_classes):
            axes = [f"{n}={s}" for n, s, c in
                    zip(self.axis_names, self.axis_sizes, self.axis_class)
                    if c == i]
            parts.append(f"{link.name}({', '.join(axes)}; "
                         f"a={link.alpha:.1e} b={link.beta:.1e})")
        return " | ".join(parts)


def butterfly_exchange(x: jax.Array, bit: int, axis_names: Sequence[str],
                       axis_sizes: Sequence[int]) -> jax.Array:
    """One butterfly stage: return the XOR-partner's value for global dp bit."""
    ax, local_bit = grouping.split_bit_over_axes(bit, axis_sizes)
    n = axis_sizes[ax]
    perm = [(i, i ^ (1 << local_bit)) for i in range(n)]
    return jax.lax.ppermute(x, axis_names[ax], perm)


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AveragingConfig:
    """Everything about the averaging math that is not the topology.

    ``bucket_bytes`` is a *global override*: when set, every link class uses
    it verbatim (the legacy single-budget behaviour).  ``None`` lets each
    class pick its own modeled-optimal budget.  Exposed to legacy callers as
    ``wagma.WagmaConfig`` (same class, aliased).
    """
    group_size: Optional[int] = None      # None -> sqrt(P) rounded to pow2
    tau: int = 10                         # global sync period (paper §V-B)
    average_dtype: Optional[str] = "float32"   # accumulation dtype
    dynamic_groups: bool = True           # False -> fixed groups (ablation 2)
    fused: bool = True                    # bucketed flat-buffer path
    bucket_bytes: Optional[int] = None    # global budget override
    use_pallas: Optional[bool] = None     # None -> Pallas combine when fused
    overlap: bool = True                  # wavefront bucket pipeline (§8)


# ---------------------------------------------------------------------------
# Per-class cost model + budget choice
# ---------------------------------------------------------------------------

def class_stage_seconds(payload_bytes: float, link: LinkClass,
                        n_buckets: int, *, overlap: bool = True) -> float:
    """Modeled seconds for ONE butterfly stage on ``link`` with B buckets."""
    wire = payload_bytes * link.beta
    combine = payload_bytes * link.gamma
    if overlap:
        return pipeline.overlapped_stage_seconds(wire, combine, n_buckets,
                                                 link.alpha)
    return max(n_buckets, 1) * link.alpha + wire + combine


@lru_cache(maxsize=None)
def choose_class_bucket_bytes(
        payload_bytes: int, link: LinkClass, *, overlap: bool = True,
        candidates: Tuple[int, ...] = bucketing.BUCKET_BYTES_CANDIDATES
        ) -> int:
    """Bucket budget minimising THIS link class's modeled stage time.

    The per-class replacement for the global ``bucketing.choose_bucket_bytes``
    sweep: a cheap-launch high-bandwidth class (ICI) favours small buckets
    (pipelining granularity), an expensive-launch class (DCN) favours big
    ones (alpha amortisation) — MG-WFBP's merge criterion, per link.  The
    stage count multiplies every candidate equally, so the argmin is
    per-stage.  Cached: the sweep re-runs only for new (payload, link) pairs,
    not per phase-offset trace.
    """
    if link.bucket_bytes is not None:
        return link.bucket_bytes
    payload = max(int(payload_bytes), 1)
    best, best_t = None, None
    for cand in candidates:
        n_buckets = max(1, -(-payload // cand))
        t = class_stage_seconds(payload, link, n_buckets, overlap=overlap)
        if best_t is None or t < best_t:
            best, best_t = cand, t
    return best


def link_transfer_seconds(payload_bytes: float, link: LinkClass, *,
                          message_bytes: Optional[int] = None) -> float:
    """Modeled seconds to move ``payload_bytes`` point-to-point on ``link``.

    The serving KV-transfer path (serve/kv_transfer.py) is not a collective:
    a prefill pod streams one request's KV blocks to a decode pod, so the
    cost is the plain alpha-beta line — one launch per message plus wire
    time — with the payload packed into ``message_bytes``-sized messages.
    ``message_bytes=None`` picks this link's modeled-optimal budget via
    :func:`choose_class_bucket_bytes` (non-overlapped: a unidirectional
    send has no combine to hide behind the wire), which is exactly how the
    bucketing layer packs the blocks in practice.
    """
    payload = max(int(payload_bytes), 0)
    if payload == 0:
        return 0.0
    if message_bytes is None:
        message_bytes = choose_class_bucket_bytes(payload, link,
                                                  overlap=False)
    n_messages = max(1, -(-payload // int(message_bytes)))
    return n_messages * link.alpha + payload * link.beta


def ring_sync_seconds(payload_bytes: float, P: int, link: LinkClass,
                      n_buckets: int) -> float:
    """Classic alpha-beta ring allreduce on the bottleneck link class."""
    wire = 2.0 * payload_bytes * (P - 1) / max(P, 1)
    stages = 2 * (P - 1)
    return stages * max(n_buckets, 1) * link.alpha + wire * link.beta


def stage_class_counts(topology: Topology, S: int, offset: int
                       ) -> Dict[int, int]:
    """How many butterfly stages of this offset ride each link class."""
    counts: Dict[int, int] = {}
    for bit in grouping.mask_bits_for_offset(topology.P, S, offset):
        c = topology.class_of_bit(bit)
        counts[c] = counts.get(c, 0) + 1
    return counts


def modeled_wagma_step_seconds(payload_bytes: int, topology: Topology,
                               S: int, *, tau: int = 10,
                               overlap: bool = True,
                               bucket_bytes: Optional[int] = None) -> dict:
    """Tau-amortised hierarchical step model with per-class budgets.

    Group term: mean over the distinct phase offsets of the sum over that
    offset's stages of the stage's class cost (per-class budget, alpha,
    beta, gamma — ``class_stage_seconds``).  Sync term: ring allreduce on
    the bottleneck class.  ``bucket_bytes`` forces one global budget on
    every class (the legacy behaviour the per-class sweep is gated
    against in ``bench_group_average.py --check``).
    """
    P = topology.P
    payload = max(int(payload_bytes), 1)
    per_class = {}
    for ci in topology.classes_in_use():
        link = topology.link_classes[ci]
        budget = bucket_bytes if bucket_bytes is not None else \
            choose_class_bucket_bytes(payload, link, overlap=overlap)
        n_buckets = max(1, -(-payload // budget))
        per_class[ci] = {
            "link": link.name,
            "bucket_bytes": budget,
            "n_buckets": n_buckets,
            "stage_s": class_stage_seconds(payload, link, n_buckets,
                                           overlap=overlap),
            "alpha": link.alpha, "beta": link.beta, "gamma": link.gamma,
        }
    offsets = grouping.distinct_offsets(P, S)
    group_times = []
    for off in offsets:
        t = 0.0
        for ci, n in stage_class_counts(topology, S, off).items():
            t += n * per_class[ci]["stage_s"]
        group_times.append(t)
    group_s = float(np.mean(group_times)) if group_times else 0.0
    bn = topology.bottleneck()
    sync_budget = bucket_bytes if bucket_bytes is not None \
        else bucketing.DEFAULT_BUCKET_BYTES
    sync_s = ring_sync_seconds(payload, P, bn,
                               max(1, -(-payload // sync_budget)))
    step_s = ((tau - 1) * group_s + sync_s) / max(tau, 1)
    return {
        "payload_bytes": payload, "P": P, "S": S, "tau": tau,
        "overlap": overlap,
        "group_s": group_s, "sync_s": sync_s, "step_s": step_s,
        "per_class": {v["link"]: {k: v[k] for k in
                                  ("bucket_bytes", "n_buckets", "stage_s",
                                   "alpha", "beta", "gamma")}
                      for v in per_class.values()},
    }


def modeled_fsdp_step_seconds(payload_bytes: int, topology: Topology,
                              S: int, *, shard_axis: str, tau: int = 10,
                              overlap: bool = True,
                              bucket_bytes: Optional[int] = None) -> dict:
    """Tau-amortised step model for FSDP-within-pod sharded replicas.

    Group term: the pod-to-pod butterfly moves only each device's shard
    slice, so every stage's wire/combine payload is ``payload / pod_size``
    (launch count per stage is unchanged — one ppermute per bucket).
    Gather/scatter term: every step additionally pays the per-bucket
    parameter all-gather (fwd/bwd) and gradient reduce-scatter on the
    shard (ICI) link class — ``(k-1)/k x payload`` wire each way.  Sync
    term: bottleneck-class ring on the shard slice.
    """
    ax = topology.axis_names.index(shard_axis)
    k = topology.axis_sizes[ax]
    shard_link = topology.link_classes[topology.axis_class[ax]]
    eff = topology.drop_axis(shard_axis)
    payload = max(int(payload_bytes), 1)
    slice_payload = payload / k

    per_class = {}
    for ci in eff.classes_in_use():
        link = topology.link_classes[ci]
        budget = bucket_bytes if bucket_bytes is not None else \
            choose_class_bucket_bytes(payload, link, overlap=overlap)
        n_buckets = max(1, -(-payload // budget))
        per_class[ci] = {
            "link": link.name, "bucket_bytes": budget,
            "n_buckets": n_buckets,
            "stage_s": class_stage_seconds(slice_payload, link, n_buckets,
                                           overlap=overlap),
        }
    group_times = []
    for off in grouping.distinct_offsets(eff.P, S):
        t = 0.0
        for bit in grouping.mask_bits_for_offset(eff.P, S, off):
            t += per_class[eff.class_of_bit(bit)]["stage_s"]
        group_times.append(t)
    group_s = float(np.mean(group_times)) if group_times else 0.0

    # the implemented step gathers per shard-layout bucket, and the shard
    # layout is sized at the butterfly (bottleneck-of-effective) class's
    # budget (AveragingPlan.shard_bucket_bytes) — price the AG/RS alpha
    # term at the same launch count the compiled step actually executes
    butterfly_link = max((topology.link_classes[ci]
                          for ci in eff.classes_in_use()),
                         key=lambda l: l.beta)
    ag_budget = bucket_bytes if bucket_bytes is not None else \
        choose_class_bucket_bytes(payload, butterfly_link, overlap=overlap)
    n_ag_buckets = max(1, -(-payload // ag_budget))
    gs_wire = payload * (k - 1) / k * shard_link.beta
    gather_scatter_s = 2 * (n_ag_buckets * shard_link.alpha + gs_wire)

    bn = eff.bottleneck()
    sync_budget = bucket_bytes if bucket_bytes is not None \
        else bucketing.DEFAULT_BUCKET_BYTES
    sync_s = ring_sync_seconds(slice_payload, eff.P, bn,
                               max(1, -(-payload // sync_budget)))
    step_s = ((tau - 1) * group_s + sync_s) / max(tau, 1) + gather_scatter_s
    return {
        "payload_bytes": payload, "P": topology.P, "P_eff": eff.P,
        "pod_size": k, "S": S, "tau": tau, "overlap": overlap,
        "shard_axis": shard_axis, "shard_link": shard_link.name,
        "group_s": group_s, "sync_s": sync_s,
        "gather_scatter_s": gather_scatter_s, "step_s": step_s,
        "per_class": {v["link"]: {kk: v[kk] for kk in
                                  ("bucket_bytes", "n_buckets", "stage_s")}
                      for v in per_class.values()},
    }


def modeled_streamed_fsdp_step_seconds(
        payload_bytes: int, topology: Topology, S: int, *, shard_axis: str,
        n_spans: int, span_fwd_compute_s: float, tau: int = 10,
        overlap: bool = True, bucket_bytes: Optional[int] = None) -> dict:
    """Step model for the layer-streamed FSDP engine (DESIGN.md §11).

    The gather-all step pays ``sum(gather) + compute + sum(scatter)``
    serially and pins the full gathered tree; the streamed step pays
    ``max(compute, gather)`` per layer span plus pipeline fill/drain, and
    holds at most ~two gathered spans.  Backward re-gathers (span-level
    remat) double the gather wire — the model charges them, and the win
    survives whenever span compute covers span gather.  The averaging
    (butterfly + tau-sync) term is identical to
    :func:`modeled_fsdp_step_seconds`.
    """
    base = modeled_fsdp_step_seconds(
        payload_bytes, topology, S, shard_axis=shard_axis, tau=tau,
        overlap=overlap, bucket_bytes=bucket_bytes)
    ax = topology.axis_names.index(shard_axis)
    k = topology.axis_sizes[ax]
    shard_link = topology.link_classes[topology.axis_class[ax]]
    payload = max(int(payload_bytes), 1)
    n = max(int(n_spans), 1)
    span_payload = payload / n
    # spans bucket at the shard layout's budget (the butterfly class's)
    eff = topology.drop_axis(shard_axis)
    butterfly_link = max((topology.link_classes[ci]
                          for ci in eff.classes_in_use()),
                         key=lambda l: l.beta)
    ag_budget = bucket_bytes if bucket_bytes is not None else \
        choose_class_bucket_bytes(payload, butterfly_link, overlap=overlap)
    span_buckets = max(1, -(-int(span_payload) // ag_budget))
    span_wire = span_payload * (k - 1) / k * shard_link.beta
    ag_span = span_buckets * shard_link.alpha + span_wire   # one span gather
    rs_span = ag_span                                       # mirror scatter
    fwd_c = float(span_fwd_compute_s)
    bwd_c = 2.0 * fwd_c

    # gather-all execution: every gather lands before the first flop
    exec_gather_all = n * (ag_span + fwd_c + bwd_c + rs_span)
    # streamed: fill with the first gather, then max(compute, comm) per
    # span; the backward overlaps re-gather + scatter with the 2x compute
    exec_streamed = (ag_span + n * max(fwd_c, ag_span)
                     + n * max(bwd_c, ag_span + rs_span) + rs_span)
    averaging_s = base["step_s"] - base["gather_scatter_s"]
    step_s = averaging_s + exec_streamed
    gather_all_step_s = averaging_s + exec_gather_all
    return {
        "payload_bytes": payload, "P": topology.P, "pod_size": k,
        "S": S, "tau": tau, "n_spans": n,
        "span_payload_bytes": span_payload,
        "span_buckets": span_buckets,
        "span_gather_s": ag_span, "span_fwd_compute_s": fwd_c,
        "exec_streamed_s": exec_streamed,
        "exec_gather_all_s": exec_gather_all,
        "averaging_s": averaging_s,
        "step_s": step_s, "gather_all_step_s": gather_all_step_s,
        "streamed_win": gather_all_step_s / max(step_s, 1e-30),
        # peak transient gathered bytes: full tree vs ~2 spans in flight
        # (clamped — the engine's liveness peak can never exceed the tree,
        # and for n_spans <= 2 "two spans" IS the whole tree)
        "peak_gathered_bytes_full": float(payload),
        "peak_gathered_bytes_streamed": min(2.0 * span_payload,
                                            float(payload)),
    }


# ---------------------------------------------------------------------------
# Combine kernels (moved from group_allreduce)
# ---------------------------------------------------------------------------

def _stage_combine(acc, recv, scale: float, use_pallas: bool):
    """(acc + recv) * scale — fused Pallas kernel or plain jnp."""
    return _combine_many([acc], [recv], scale, use_pallas)[0]


def _manual_over_auto_axes(kernel, accs, recvs):
    """Call ``kernel(accs, recvs) -> outs`` manual over every mesh axis.

    A Mosaic kernel cannot be partitioned by GSPMD, so it must sit where
    every mesh axis is Manual.  Inside the train step's shard_map (manual
    over the dp axes) the ``model`` axis is still Auto — even at size 1 —
    so the call is wrapped in a nested shard_map over the remaining Auto
    axes.  The combine is elementwise: a flat buffer that divides evenly
    is split over those axes (each tensor-parallel rank combines its
    slice), anything else is combined whole on every rank.
    """
    from jax.sharding import AxisType, PartitionSpec as P
    from repro import compat
    mesh = compat.get_abstract_mesh()
    auto = () if mesh.empty else tuple(
        a for a, t in zip(mesh.axis_names, mesh.axis_types)
        if t != AxisType.Manual)
    if not auto:
        return kernel(accs, recvs)
    n = math.prod(mesh.shape[a] for a in auto)
    specs = [P(auto) if a.ndim == 1 and a.shape[0] % n == 0 else P()
             for a in accs]
    return compat.shard_map(kernel, mesh=mesh, in_specs=(specs, specs),
                            out_specs=specs,
                            axis_names=set(mesh.axis_names))(accs, recvs)


def _combine_many(accs, recvs, scale: float, use_pallas: bool):
    """Batch of independent (acc, recv) combines — one wavefront tick.

    The Pallas route groups the batch by dtype and feeds each group to ONE
    multi-bucket kernel launch (grid walks buckets x row-tiles); the jnp
    route does the same per-pair arithmetic.
    """
    if not use_pallas:
        return [(a + r) * jnp.asarray(scale, a.dtype)
                for a, r in zip(accs, recvs)]
    from repro.kernels import ops
    outs = [None] * len(accs)
    by_dtype = {}
    for i, a in enumerate(accs):
        by_dtype.setdefault(jnp.dtype(a.dtype), []).append(i)
    for idxs in by_dtype.values():
        res = _manual_over_auto_axes(
            lambda ws, rs: ops.group_average_combine_multi(ws, rs, scale),
            [accs[i] for i in idxs], [recvs[i] for i in idxs])
        for i, o in zip(idxs, res):
            outs[i] = o
    return outs


# ---------------------------------------------------------------------------
# The compiled plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StageRun:
    """A maximal run of consecutive butterfly stages on one link class."""
    class_index: int
    bits: Tuple[int, ...]


class AveragingPlan:
    """Compiled realisation of group + global averaging on one topology.

    Built by :func:`compile_plan`; holds the static schedule data (stage
    classification, per-class budgets/layouts, wavefront order) and exposes
    the execution entry points used inside shard_map:

        plan.average(tree, phase)     group butterfly for a phase index
        plan.sync(tree)               tau-periodic global allreduce mean
        plan.mix(tree, issue, combine, bits=...)
                                      single-round gossip/psum mixes
                                      (the baseline averagers)

    plus the stacked-simulator twins (``average_stacked``/``sync_stacked``)
    and analysis/accounting helpers (``describe``, ``expected_ppermutes``,
    ``per_class_expected``, ``modeled_step_seconds``).
    """

    def __init__(self, topology: Topology, cfg: AveragingConfig,
                 storage_struct, work_struct, payload_bytes: int,
                 sharding: ShardingPolicy = REPLICATED):
        self.topology = topology
        self.cfg = cfg
        self.sharding = sharding
        self.P = topology.P
        # Sharded plans butterfly over the *effective* (pod-level) replica
        # space: the shard axis's ranks share weights and act as ONE
        # logical WAGMA worker (DESIGN.md §10).
        if sharding.is_sharded:
            if sharding.shard_axis not in topology.axis_names:
                raise ValueError(
                    f"shard_axis {sharding.shard_axis!r} not a dp axis of "
                    f"{topology.axis_names}")
            self.shard_axis_index = topology.axis_names.index(
                sharding.shard_axis)
            self.shard_size = topology.axis_sizes[self.shard_axis_index]
            shard_link = topology.link_classes[
                topology.axis_class[self.shard_axis_index]]
            if len(topology.classes_in_use()) > 1 and \
                    shard_link.beta >= topology.bottleneck().beta:
                raise ValueError(
                    f"shard_axis {sharding.shard_axis!r} rides the "
                    f"bottleneck link class {shard_link.name!r}; FSDP "
                    "shards over an intra-pod (ICI) axis")
            self.eff_topology = topology.drop_axis(sharding.shard_axis)
        else:
            self.shard_axis_index = None
            self.shard_size = 1
            self.eff_topology = topology
        self.P_eff = self.eff_topology.P
        self.S = cfg.group_size or grouping.default_group_size(self.P_eff)
        if self.S > self.P_eff:
            raise ValueError(f"group size {self.S} exceeds replica world "
                             f"{self.P_eff}")
        self.avg_dtype = (None if cfg.average_dtype is None
                          else np.dtype(cfg.average_dtype))
        if cfg.dynamic_groups:
            self.offsets: Tuple[int, ...] = grouping.distinct_offsets(
                self.P_eff, self.S)
        else:
            self.offsets = (0,)
        self.storage_struct = storage_struct    # SDS tree, storage dtypes
        self.work_struct = work_struct          # SDS tree, accumulation dtype
        self.payload_bytes = payload_bytes      # bytes of the work tree
        self.storage_payload_bytes = bucketing.tree_payload_bytes(
            storage_struct)
        # per-class budgets, resolved once at compile time
        self.class_bucket_bytes: Dict[int, int] = {}
        for ci in topology.classes_in_use():
            link = topology.link_classes[ci]
            if cfg.bucket_bytes is not None:
                self.class_bucket_bytes[ci] = cfg.bucket_bytes
            else:
                self.class_bucket_bytes[ci] = choose_class_bucket_bytes(
                    payload_bytes, link, overlap=cfg.overlap)
        self.sync_bucket_bytes = (cfg.bucket_bytes
                                  or bucketing.DEFAULT_BUCKET_BYTES)
        self._runs: Dict[int, Tuple[StageRun, ...]] = {}
        self._shard_layout: Optional[bucketing.BucketLayout] = None
        # layer-streamed state layout (DESIGN.md §11): derive the ordered
        # leaf groups from the layered tree convention up front so a
        # non-layered tree fails at compile time, not first gather
        if sharding.is_sharded and sharding.streamed:
            self._stream_groups = streaming.layered_leaf_groups(
                storage_struct)
            self.n_stream_spans = len(storage_struct["layers"])
        else:
            self._stream_groups = None
            self.n_stream_spans = 0
        self._stream_sublayouts: Dict[int, bucketing.BucketLayout] = {}

    # -- static schedule ---------------------------------------------------
    @property
    def n_phases(self) -> int:
        return len(self.offsets)

    def runs_for_offset(self, offset: int) -> Tuple[StageRun, ...]:
        """The offset's stages as maximal runs of equal link class.

        Bits live in the *effective* replica rank space — identical to the
        full dp space for replicated plans; the pod-level space (shard
        axis dropped) for sharded plans.
        """
        cached = self._runs.get(offset)
        if cached is not None:
            return cached
        bits = grouping.mask_bits_for_offset(self.P_eff, self.S, offset)
        runs: List[StageRun] = []
        for bit in bits:
            ci = self.eff_topology.class_of_bit(bit)
            if runs and runs[-1].class_index == ci:
                runs[-1] = StageRun(ci, runs[-1].bits + (bit,))
            else:
                runs.append(StageRun(ci, (bit,)))
        self._runs[offset] = tuple(runs)
        return self._runs[offset]

    def class_layout(self, class_index: int) -> bucketing.BucketLayout:
        """The (cached) bucket layout the class's stages pack into."""
        return bucketing.layout_for(
            self.work_struct,
            max_bucket_bytes=self.class_bucket_bytes[class_index])

    # -- sharded-state layout (ShardingPolicy.fsdp_within_pod) -------------
    @property
    def shard_layout(self) -> bucketing.BucketLayout:
        """Storage-dtype bucket layout the sharded state persists in.

        Every bucket is padded to shard_size x 128 elements so each device
        owns an equal, lane-aligned contiguous slice.  One layout serves
        storage, the fwd/bwd all-gather, the grad reduce-scatter, and the
        pod-to-pod butterfly (class budgets degenerate to the butterfly
        link class's budget under sharding — the stage bits all ride the
        non-shard axes, so there is no intra-butterfly repack).
        """
        if not self.sharding.is_sharded:
            raise ValueError("shard_layout is only defined for sharded plans")
        if self._shard_layout is None:
            self._shard_layout = bucketing.layout_for(
                self.storage_struct,
                max_bucket_bytes=self.shard_bucket_bytes,
                align=self.shard_size,
                groups=self._stream_groups)
        return self._shard_layout

    @property
    def shard_bucket_bytes(self) -> int:
        """The sharded state's bucket budget: the butterfly link class's."""
        if self.cfg.bucket_bytes is not None:
            return self.cfg.bucket_bytes
        eff_classes = self.eff_topology.classes_in_use()
        link_ci = max(eff_classes,
                      key=lambda ci: self.topology.link_classes[ci].beta)
        return self.class_bucket_bytes[link_ci]

    def shard_struct(self) -> tuple:
        """ShapeDtypeStructs of one device's owned shard slices."""
        lay = self.shard_layout
        return tuple(
            jax.ShapeDtypeStruct((s // self.shard_size,), d)
            for s, d in zip(lay.bucket_sizes, lay.bucket_dtypes))

    def shard_tree(self, tree) -> tuple:
        """Full local tree -> this device's owned shard slices.

        Must run inside shard_map (manual over the dp axes): packs into the
        shard layout and takes the ``axis_index(shard_axis)``-th slice of
        every bucket.
        """
        idx = jax.lax.axis_index(self.sharding.shard_axis)
        out = []
        for buf in bucketing.pack(tree, self.shard_layout):
            n = buf.shape[0] // self.shard_size
            out.append(jax.lax.dynamic_slice(buf, (idx * n,), (n,))
                       if n else buf)
        return tuple(out)

    def unshard_tree(self, shards) -> object:
        """Owned shard slices -> the full local tree (all-gather on ICI).

        One tiled all-gather per bucket over the shard axis — the
        forward/backward parameter gather of the FSDP-within-pod step.
        """
        ax = self.sharding.shard_axis
        bufs = tuple(
            jax.lax.all_gather(b, ax, tiled=True) if b.size else
            jnp.zeros((0,), b.dtype) for b in shards)
        return bucketing.unpack(bufs, self.shard_layout)

    def grad_shards(self, grad_tree) -> tuple:
        """Full-tree gradients -> owned fp32 grad slices (pod mean).

        One tiled ``psum_scatter`` per bucket over the shard axis, scaled
        by 1/shard_size: pod members form one logical worker whose
        gradient is the mean over members, and each device keeps only the
        slice its optimiser shard needs.
        """
        ax = self.sharding.shard_axis
        inv = 1.0 / self.shard_size
        out = []
        for buf in bucketing.pack(grad_tree, self.shard_layout,
                                  dtype=jnp.float32):
            if buf.size:
                buf = jax.lax.psum_scatter(buf, ax, scatter_dimension=0,
                                           tiled=True) * inv
            out.append(buf)
        return tuple(out)

    # -- layer-streamed gather/scatter (DESIGN.md §11) ---------------------
    def _require_streamed(self):
        if self._stream_groups is None:
            raise ValueError(
                "stream_* needs a streamed plan: compile with "
                "ShardingPolicy.fsdp_within_pod(axis, streamed=True) over "
                "the layered param tree")

    def stream_bucket_indices(self, group: int) -> Tuple[int, ...]:
        """Global bucket indices holding one stream group's leaves."""
        self._require_streamed()
        return self.shard_layout.group_bucket_indices(group)

    def stream_group_template(self, group: int):
        """The group's sub-SDS-tree of the layered storage struct."""
        self._require_streamed()
        if group == streaming.STEM_GROUP:
            return self.storage_struct["stem"]
        if group == streaming.head_group(self.n_stream_spans):
            return self.storage_struct["head"]
        return self.storage_struct["layers"][group - 1]

    def stream_sublayout(self, group: int) -> bucketing.BucketLayout:
        """Pack/unpack layout of ONE group's buckets (a layout view).

        Because the grouped global layout restarts its greedy fill at every
        group boundary, laying out the group's sub-tree alone at the same
        budget/alignment reproduces exactly the global layout's slice for
        that group — asserted here once per group, then cached.
        """
        self._require_streamed()
        lay = self._stream_sublayouts.get(group)
        if lay is not None:
            return lay
        lay = bucketing.layout_for(
            self.stream_group_template(group),
            max_bucket_bytes=self.shard_bucket_bytes, align=self.shard_size)
        idxs = self.stream_bucket_indices(group)
        glob = self.shard_layout
        if (lay.n_buckets != len(idxs)
                or tuple(lay.bucket_sizes) != tuple(
                    glob.bucket_sizes[i] for i in idxs)
                or tuple(lay.bucket_dtypes) != tuple(
                    glob.bucket_dtypes[i] for i in idxs)):
            raise AssertionError(
                f"group {group} sublayout diverged from the global grouped "
                f"layout: {lay.describe()} vs global buckets {idxs}")
        self._stream_sublayouts[group] = lay
        return lay

    def stream_unshard(self, shards, group: int, *, barrier: bool = False):
        """One group's shard slices -> its full sub-tree (all-gather on ICI).

        ``barrier=True`` marks a backward *re*-gather, which must not CSE
        with the forward gather, or XLA keeps the forward buffers alive
        and the streamed memory bound silently degrades to gather-all.
        It gathers the buffers' bits as same-width unsigned integers: a
        different all-gather operand, bit-identical after the bitcast
        back.  (``lax.optimization_barrier`` does not fence it: XLA
        expands barriers before its last CSE pass.)
        """
        self._require_streamed()
        ax = self.sharding.shard_axis

        def gather(b):
            if not b.size:
                return jnp.zeros((0,), b.dtype)
            if not barrier:
                return jax.lax.all_gather(b, ax, tiled=True)
            bits = jnp.dtype(f"uint{8 * b.dtype.itemsize}")
            g = jax.lax.all_gather(jax.lax.bitcast_convert_type(b, bits),
                                   ax, tiled=True)
            return jax.lax.bitcast_convert_type(g, b.dtype)

        gathered = tuple(gather(shards[i])
                         for i in self.stream_bucket_indices(group))
        return bucketing.unpack(gathered, self.stream_sublayout(group))

    def stream_grad_shards(self, grad_subtree, group: int) -> tuple:
        """One group's full-tree grads -> owned fp32 pod-mean slices.

        The exact per-group mirror of :meth:`grad_shards`: cast-to-fp32
        pack into the group's buckets, tiled ``psum_scatter`` over the
        shard axis, scale by 1/shard_size — so streamed gradients are
        bit-identical to the gather-all path's.
        """
        self._require_streamed()
        ax = self.sharding.shard_axis
        inv = 1.0 / self.shard_size
        out = []
        for buf in bucketing.pack(grad_subtree, self.stream_sublayout(group),
                                  dtype=jnp.float32):
            if buf.size:
                buf = jax.lax.psum_scatter(buf, ax, scatter_dimension=0,
                                           tiled=True) * inv
            out.append(buf)
        return tuple(out)

    def stream_group_bytes(self) -> Dict[int, int]:
        """Gathered (padded storage) bytes per stream group."""
        self._require_streamed()
        lay = self.shard_layout
        return {g: lay.group_bytes(g) for g in sorted(set(lay.bucket_groups))}

    def stream_peak_gathered_bytes(self) -> int:
        """Peak gathered bytes of the streamed schedule (liveness walk)."""
        self._require_streamed()
        return streaming.max_in_flight_gathered_bytes(
            self.stream_group_bytes(), self.n_stream_spans)

    def full_gathered_bytes(self) -> int:
        """Transient bytes of a gather-all unshard (every padded bucket)."""
        lay = self.shard_layout
        return sum(s * d.itemsize
                   for s, d in zip(lay.bucket_sizes, lay.bucket_dtypes))

    # -- execution: the paper's group butterfly ----------------------------
    def average(self, tree, phase: int):
        """Wait-avoiding group model averaging for compiled phase ``phase``.

        Replicated plans take (and return) the local params pytree; sharded
        plans take the tuple of owned shard-slice buffers and butterfly
        them pod-to-pod directly (each device exchanges only its slice).
        """
        return self.average_offset(tree, self.offsets[phase])

    def _cast_shards(self, shards):
        if self.avg_dtype is None:
            return list(shards)
        return [b.astype(self.avg_dtype) if b.size else b for b in shards]

    def _uncast_shards(self, work, shards):
        return tuple(w.astype(b.dtype) for w, b in zip(work, shards))

    def _average_sharded(self, shards, offset: int):
        """Pod-to-pod butterfly on the shard-slice buffers.

        Per element the arithmetic is exactly the replicated reference's —
        log2(S) adds in stage order, then one scale — applied to each
        device's slice, so the sharded path stays bit-identical to the
        replicated plan and the stacked simulator (tests/test_replica.py).
        """
        bits = grouping.mask_bits_for_offset(self.P_eff, self.S, offset)
        inv_s = 1.0 / self.S
        exchange = lambda buf, bit: butterfly_exchange(
            buf, bit, self.eff_topology.axis_names,
            self.eff_topology.axis_sizes)
        pallas = True if self.cfg.use_pallas is None else self.cfg.use_pallas
        work = self._cast_shards(shards)
        if self.cfg.overlap:
            work = pipeline.overlapped_butterfly(
                work, bits, inv_s, exchange=exchange,
                combine_many=lambda a, r, s: _combine_many(a, r, s, pallas))
        else:
            out = []
            for buf in work:
                if not buf.size:
                    out.append(buf)
                    continue
                for i, bit in enumerate(bits):
                    recv = exchange(buf, bit)
                    s = inv_s if i == len(bits) - 1 else 1.0
                    buf = _stage_combine(buf, recv, s, pallas)
                out.append(buf)
            work = out
        return self._uncast_shards(work, shards)

    def average_offset(self, tree, offset: int):
        """Group averaging for an explicit phase offset."""
        if self.sharding.is_sharded:
            return self._average_sharded(tree, offset)
        bits = grouping.mask_bits_for_offset(self.P_eff, self.S, offset)
        inv_s = 1.0 / self.S
        exchange = lambda buf, bit: butterfly_exchange(
            buf, bit, self.topology.axis_names, self.topology.axis_sizes)

        if not self.cfg.fused:
            def avg_leaf(w):
                orig_dtype = w.dtype
                acc = w.astype(self.avg_dtype) if self.avg_dtype is not None \
                    else w
                for bit in bits:
                    acc = acc + exchange(acc, bit)
                acc = acc * jnp.asarray(inv_s, acc.dtype)
                return acc.astype(orig_dtype)

            return jax.tree.map(avg_leaf, tree)

        pallas = True if self.cfg.use_pallas is None else self.cfg.use_pallas
        runs = self.runs_for_offset(offset)
        # Cast once up front and keep the accumulation dtype across runs, so
        # multi-class butterflies stay bit-identical to the per-leaf
        # reference (no intermediate storage-dtype round trips).
        if self.avg_dtype is not None:
            work = jax.tree.map(lambda w: w.astype(self.avg_dtype), tree)
        else:
            work = tree
        for ri, run in enumerate(runs):
            scale = inv_s if ri == len(runs) - 1 else 1.0
            budget = self.class_bucket_bytes[run.class_index]
            if self.cfg.overlap:
                def mix_all(bufs, run=run, scale=scale):
                    return pipeline.overlapped_butterfly(
                        bufs, run.bits, scale, exchange=exchange,
                        combine_many=lambda a, r, s: _combine_many(
                            a, r, s, pallas))
                work = bucketing.tree_map_buckets(
                    mix_all, work, compute_dtype=None,
                    max_bucket_bytes=budget)
            else:
                def mix(acc, run=run, scale=scale):
                    for i, bit in enumerate(run.bits):
                        recv = exchange(acc, bit)
                        s = scale if i == len(run.bits) - 1 else 1.0
                        acc = _stage_combine(acc, recv, s, pallas)
                    return acc
                work = bucketing.tree_map_bucketed(
                    mix, work, compute_dtype=None, max_bucket_bytes=budget)
        if self.avg_dtype is None:
            return work
        return jax.tree.map(lambda w, o: w.astype(o.dtype), work, tree)

    # -- execution: tau-periodic global sync -------------------------------
    def sync(self, tree):
        """Synchronous allreduce mean over all replicas (Alg. 2 line 16).

        Sharded plans pmean the shard-slice buffers over the *effective*
        (pod) axes only — shard-axis neighbours hold different slices, not
        divergent copies, so they are never averaged.
        """
        if self.sharding.is_sharded:
            names = self.eff_topology.axis_names
            return tuple(
                jax.lax.pmean(b.astype(jnp.float32), names).astype(b.dtype)
                if b.size else b for b in tree)
        names = self.topology.axis_names
        if not self.cfg.fused:
            return jax.tree.map(
                lambda w: jax.lax.pmean(w.astype(jnp.float32),
                                        names).astype(w.dtype), tree)
        return bucketing.tree_map_bucketed(
            lambda buf: jax.lax.pmean(buf, names), tree,
            compute_dtype=jnp.float32,
            max_bucket_bytes=self.sync_bucket_bytes)

    # -- execution: single-round gossip/psum mixes (baseline averagers) ----
    def mix_bucket_bytes(self, bits: Tuple[int, ...] = ()) -> int:
        """Budget for a single-round mix touching the given dp-rank bits.

        The mix's collectives ride the classes of its bits (all classes for
        a global collective, ``bits=()``); the budget follows the slowest
        wire involved — the link the mix is bound by.
        """
        if self.cfg.bucket_bytes is not None:
            return self.cfg.bucket_bytes
        if bits:
            classes = {self.eff_topology.class_of_bit(b) for b in bits}
            link = max((self.topology.link_classes[c] for c in classes),
                       key=lambda l: l.beta)
        else:
            link = self.eff_topology.bottleneck()
        return choose_class_bucket_bytes(self.payload_bytes, link,
                                         overlap=self.cfg.overlap)

    def mix(self, tree, issue: Callable, combine: Callable, *,
            bits: Tuple[int, ...] = ()):
        """Apply a flat fp32 gossip/psum mix per bucket (fused) or per leaf.

        ``issue(buf) -> recv`` is the collective half (shape-polymorphic),
        ``combine(buf, recv) -> buf`` the local arithmetic; per leaf and per
        serial bucket the halves compose back into the original mix, so all
        granularities compute identical element math.  With ``overlap=True``
        every bucket's collectives are issued before any bucket's combine
        (core/overlap.py single-stage pipeline).

        Sharded plans run the mix directly on the shard-slice buffers
        (``bits`` are effective/pod-space bits; the issue half must ride
        the non-shard axes only — the averagers guarantee that).
        """
        mixfn = lambda buf: combine(buf, issue(buf))
        if self.sharding.is_sharded:
            work = [b.astype(jnp.float32) if b.size else b for b in tree]
            if self.cfg.overlap:
                out = pipeline.overlapped_mix(work, issue, combine)
            else:
                out = [mixfn(b) if b.size else b for b in work]
            return tuple(o.astype(b.dtype) for o, b in zip(out, tree))
        if not self.cfg.fused:
            return jax.tree.map(
                lambda w: mixfn(w.astype(jnp.float32)).astype(w.dtype), tree)
        budget = self.mix_bucket_bytes(tuple(bits))
        if not self.cfg.overlap:
            return bucketing.tree_map_bucketed(
                mixfn, tree, compute_dtype=jnp.float32,
                max_bucket_bytes=budget)
        return bucketing.tree_map_buckets(
            lambda bufs: pipeline.overlapped_mix(bufs, issue, combine),
            tree, compute_dtype=jnp.float32, max_bucket_bytes=budget)

    # -- stacked-simulator twins (single process, leading replica axis) ----
    def average_stacked(self, stacked_tree, *, t: int):
        """Simulator twin over the logical replica axis (P_eff rows)."""
        from repro.core import group_allreduce as ga
        return ga.group_average_stacked(stacked_tree, P=self.P_eff,
                                        S=self.S, t=t)

    def sync_stacked(self, stacked_tree):
        from repro.core import group_allreduce as ga
        return ga.global_average_stacked(stacked_tree, P=self.P_eff)

    # -- accounting / analysis ---------------------------------------------
    def n_leaves(self) -> int:
        return len(jax.tree_util.tree_leaves(self.work_struct))

    def butterfly_summary(self, offset: int = 0) -> List[dict]:
        """One dict per stage run: link class, bits, budget, launch count.

        Sharding never changes the launch count per stage — the sharded
        butterfly runs one ppermute per shard-layout bucket, not per
        (bucket x shard) — so under FSDP every class reports the shard
        layout's bucket count.
        """
        out = []
        for run in self.runs_for_offset(offset):
            link = self.topology.link_classes[run.class_index]
            if self.sharding.is_sharded:
                units = self.shard_layout.n_buckets
                budget = self.shard_bucket_bytes
            else:
                units = (self.class_layout(run.class_index).n_buckets
                         if self.cfg.fused else self.n_leaves())
                budget = self.class_bucket_bytes[run.class_index]
            out.append({
                "link": link.name,
                "bits": run.bits,
                "axes": tuple(self.eff_topology.axis_of_bit(b)
                              for b in run.bits),
                "stages": len(run.bits),
                "bucket_bytes": budget,
                "n_buckets": units,
                "ppermutes": len(run.bits) * units,
            })
        return out

    def per_class_expected(self, offset: int = 0) -> Dict[str, dict]:
        """Expected ppermute launches per link class at one phase offset."""
        agg: Dict[str, dict] = {}
        for run in self.butterfly_summary(offset):
            ent = agg.setdefault(run["link"], {
                "stages": 0, "ppermutes": 0,
                "bucket_bytes": run["bucket_bytes"],
                "n_buckets": run["n_buckets"],
                "axes": (),
            })
            ent["stages"] += run["stages"]
            ent["ppermutes"] += run["ppermutes"]
            ent["axes"] = tuple(dict.fromkeys(ent["axes"] + run["axes"]))
        return agg

    def expected_ppermutes(self, offset: int = 0) -> int:
        return sum(r["ppermutes"] for r in self.butterfly_summary(offset))

    def modeled_step_seconds(self, *, overlap: Optional[bool] = None) -> dict:
        """Per-class alpha-beta-gamma model of this plan's step time."""
        return modeled_wagma_step_seconds(
            self.payload_bytes, self.topology, self.S, tau=self.cfg.tau,
            overlap=self.cfg.overlap if overlap is None else overlap,
            bucket_bytes=self.cfg.bucket_bytes)

    def describe(self) -> str:
        """Human-readable plan summary (stages, classes, budgets)."""
        lines = [
            f"AveragingPlan P={self.P} S={self.S} tau={self.cfg.tau} "
            f"payload={self.payload_bytes / 2**20:.2f}MiB "
            f"avg_dtype={self.avg_dtype} fused={self.cfg.fused} "
            f"overlap={self.cfg.overlap}",
            f"  topology: {self.topology.describe()}",
            f"  sharding: {self.sharding.describe()}"
            + (f" -> {self.P_eff} logical replicas of "
               f"{self.shard_size} shards" if self.sharding.is_sharded
               else ""),
        ]
        if self.sharding.is_sharded:
            lines.append(
                f"  shard layout: budget "
                f"{self.shard_bucket_bytes / 2**20:.0f}MiB -> "
                f"{self.shard_layout.n_buckets} buckets x "
                f"{self.shard_size} slices")
            if self._stream_groups is not None:
                lay = self.shard_layout
                lines.append(
                    f"  layer map ({self.n_stream_spans} spans + stem/head):"
                    f" {lay.describe_groups()}")
                lines.append(
                    f"  streamed coverage: peak gathered "
                    f"{self.stream_peak_gathered_bytes() / 2**20:.2f}MiB "
                    f"of {self.full_gathered_bytes() / 2**20:.2f}MiB "
                    f"full-tree ({streaming.expected_stream_gathers(self)} "
                    f"gathers/step fwd+bwd)")
        else:
            for ci in self.topology.classes_in_use():
                link = self.topology.link_classes[ci]
                bb = self.class_bucket_bytes[ci]
                nb = self.class_layout(ci).n_buckets if self.cfg.fused else 0
                lines.append(f"  class {link.name}: budget "
                             f"{bb / 2**20:.0f}MiB -> {nb} buckets")
        for ph, off in enumerate(self.offsets):
            runs = ", ".join(
                f"{r['link']}[bits={list(r['bits'])} x{r['n_buckets']}buk]"
                for r in self.butterfly_summary(off))
            lines.append(f"  phase {ph} (offset {off}): {runs}")
        lines.append(f"  sync: pmean budget "
                     f"{self.sync_bucket_bytes / 2**20:.0f}MiB")
        stats = bucketing.layout_cache_stats()
        lines.append(f"  layout cache: {stats['hits']} hits / "
                     f"{stats['misses']} misses")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Compilation (cached on topology x config x tree structure)
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict[tuple, AveragingPlan] = {}
# Sharded plans are additionally indexed by the *shard-buffer* structure
# they produce, so averagers handed the sharded state (a tuple of slice
# buffers) inside the train step resolve back to the plan compiled from
# the full tree at init time.
_SHARD_STRUCT_CACHE: Dict[tuple, AveragingPlan] = {}


def clear_plan_cache() -> None:
    """Drop every compile-time cache this subsystem owns.

    The single delegating entry point: compiled plans (and the treedefs
    they retain), the shard-struct index, the per-class budget sweep, AND
    ``bucketing``'s layout cache + budget sweep — a long-lived process
    that recompiles after a topology change must be able to release all
    of it with one call (previously only the autouse test fixture
    cleared the layout cache, so production churn leaked layouts).
    """
    _PLAN_CACHE.clear()
    _SHARD_STRUCT_CACHE.clear()
    choose_class_bucket_bytes.cache_clear()
    bucketing.clear_layout_cache()


def evict_topology(topology: Topology) -> int:
    """Drop cached plans compiled for one topology; returns entries removed.

    Membership changes (core/elastic.py) retire topologies for good — the
    old world size never comes back under the same object — so the
    controller evicts their plans instead of nuking every cache the way
    :func:`clear_plan_cache` does.  Cache keys lead with the topology, so
    eviction is a key-prefix filter.
    """
    removed = 0
    for cache in (_PLAN_CACHE, _SHARD_STRUCT_CACHE):
        dead = [k for k in cache if k[0] == topology]
        for k in dead:
            del cache[k]
        removed += len(dead)
    return removed


def _structure_key(tree) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple((tuple(l.shape), np.dtype(l.dtype).str)
                           for l in leaves))


def _config_key(cfg: AveragingConfig) -> tuple:
    avg = None if cfg.average_dtype is None \
        else np.dtype(cfg.average_dtype).name
    return (cfg.group_size, cfg.tau, avg, cfg.dynamic_groups, cfg.fused,
            cfg.bucket_bytes, cfg.use_pallas, cfg.overlap)


def compile_plan(topology: Topology, tree_shapes,
                 config: AveragingConfig = AveragingConfig(),
                 sharding: ShardingPolicy = REPLICATED) -> AveragingPlan:
    """Compile the collective once for a tree structure on a topology.

    ``tree_shapes`` may be concrete arrays, tracers, or ShapeDtypeStructs —
    only structure/shapes/dtypes are read.  Cached on (topology, config,
    sharding, structure): repeated calls from every compiled phase variant
    return the same plan object, and only the first call derives
    budgets/layouts.

    ``sharding`` selects the replica-state realisation the plan executes
    (DESIGN.md §10): ``ShardingPolicy.fsdp_within_pod(axis)`` compiles the
    sharded-state plan — ``tree_shapes`` is still the FULL local tree; the
    plan derives the shard-aligned bucket layout, and subsequent
    ``compile_plan`` calls that pass the plan's own shard-buffer tuple
    (the state the train step actually holds) resolve to the same plan.
    """
    skey = (topology, _config_key(config), sharding)
    if sharding.is_sharded:
        plan = _SHARD_STRUCT_CACHE.get(skey + (_structure_key(tree_shapes),))
        if plan is not None:
            return plan
    key = skey + (_structure_key(tree_shapes),)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        return plan
    storage = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree_shapes)
    avg = None if config.average_dtype is None \
        else np.dtype(config.average_dtype)
    work = storage if avg is None else jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, avg), storage)
    payload = bucketing.tree_payload_bytes(work)
    plan = AveragingPlan(topology, config, storage, work, payload,
                         sharding=sharding)
    _PLAN_CACHE[key] = plan
    if sharding.is_sharded:
        # register BOTH shard-buffer structures the train step holds: the
        # storage-dtype param slices and the fp32 gradient slices
        # (grad_shards packs fp32 buffers of the same shapes), so
        # plan_for(grads) resolves here instead of silently compiling a
        # bogus plan that treats the slice tuple as a full model tree
        _SHARD_STRUCT_CACHE[
            skey + (_structure_key(plan.shard_struct()),)] = plan
        grad_struct = tuple(
            jax.ShapeDtypeStruct(s.shape, np.dtype(np.float32))
            for s in plan.shard_struct())
        _SHARD_STRUCT_CACHE.setdefault(
            skey + (_structure_key(grad_struct),), plan)
    return plan
