"""Layer-streamed FSDP execution engine (DESIGN.md §11).

The gather-all FSDP step (§10) materialises the **entire** gathered param
tree before the forward starts: the intra-pod all-gather sits serially in
front of fwd/bwd — exactly the wait WAGMA-SGD exists to avoid — and the
transient gathered buffer erases most of the ÷pod-size memory win.  This
module extends the §8 wavefront idea (issue the next unit's communication
before the current unit's arithmetic) from (bucket, stage) grids to the
**joint compute/comm schedule over layer spans**:

* the shard layout is **layer-aware** (``bucketing.build_layout(groups=...)``)
  over the model's *layered* param tree ``{"stem", "layers", "head"}``
  (``models/common.LayeredModel``): every bucket belongs to exactly one
  ordered group — stem = 0, span k = k+1, head = n+1 — so one span's
  parameters are a contiguous run of whole buckets;
* **forward**: span k+1's per-bucket all-gather is issued before span k's
  compute (double buffering on the ICI wire), and span k's gathered
  buffers die as soon as its compute is done — peak gathered memory is
  ~2 layer spans (+ stem/head), not the full tree;
* **backward**: spans are *re-gathered* in reverse order (span-level
  rematerialisation — the remat recompute and the FSDP backward gather are
  the same walk), each span's pod-mean fp32 gradient is reduce-scattered
  to its owner slices the moment its VJP completes (while span k-1's VJP
  runs), and the re-gathered buffers die with the span.

The engine composes per-span ``jax.vjp`` calls manually instead of
differentiating through the collectives, for two reasons: (a) the gradient
reduce-scatter must accumulate in fp32 regardless of the storage dtype
(``plan.stream_grad_shards`` packs the span's leaf cotangents to fp32
before the ``psum_scatter``, exactly like the gather-all path's
``grad_shards``), and (b) backward re-gathers must not be CSE'd with the
forward gathers (XLA would otherwise keep the forward buffer alive and
silently restore gather-all memory) — a re-gather moves the buffers' bits
as unsigned integers, an all-gather of a different operand that CSE cannot
merge with the forward one.  Because the per-span primal/VJP ops are the
same ops ``jax.value_and_grad(model.loss)`` runs on the gathered tree, the
streamed step is **bit-identical** to the gather-all step (pinned by
tests/test_streaming.py on every phase offset).

``stream_schedule`` is the declarative event order the engine realises;
``validate_stream_schedule`` pins its invariants (gather-before-compute,
span-k+1-prefetch, at most two span gathers in flight) and
``max_in_flight_gathered_bytes`` walks the schedule's liveness to bound
peak gathered memory — the dry-run smoke cross-checks the compiled HLO
against both.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

# Ordered stream groups of a layered tree: stem, spans 1..n, head.
STEM_GROUP = 0


def span_group(k: int) -> int:
    return k + 1


def head_group(n_spans: int) -> int:
    return n_spans + 1


def is_layered_tree(tree) -> bool:
    """Structural check for the ``{"stem", "layers", "head"}`` convention."""
    return (isinstance(tree, dict) and set(tree) == {"stem", "layers", "head"}
            and isinstance(tree["layers"], (tuple, list)))


def layered_leaf_groups(tree) -> Tuple[int, ...]:
    """Per-leaf ordered layer ids of a layered tree (canonical leaf order).

    This is the ``groups`` input of :func:`bucketing.build_layout`: stem
    leaves map to 0, span-k leaves to k+1, head leaves to n_spans+1.
    """
    if not is_layered_tree(tree):
        raise ValueError(
            "streamed sharding needs the layered param tree "
            '{"stem", "layers", "head"} (models/common.LayeredModel.split); '
            f"got a {type(tree).__name__} with "
            f"{sorted(tree) if isinstance(tree, dict) else '?'}")
    n_spans = len(tree["layers"])
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, _leaf in flat:
        top = getattr(path[0], "key", None)
        if top == "stem":
            out.append(STEM_GROUP)
        elif top == "head":
            out.append(head_group(n_spans))
        else:
            out.append(span_group(int(path[1].idx)))
    return tuple(out)


# ---------------------------------------------------------------------------
# The joint compute/comm schedule
# ---------------------------------------------------------------------------

GATHER = "gather"        # issue a group's per-bucket all-gathers
COMPUTE = "compute"      # forward-apply a group (stem or a span)
GRAD = "grad"            # run a group's VJP (head's includes the loss)
SCATTER = "scatter"      # reduce-scatter a group's pod-mean fp32 grads

Event = Tuple[str, int]


@lru_cache(maxsize=None)
def stream_schedule(n_spans: int) -> Tuple[Event, ...]:
    """Event order of one streamed fwd+bwd over groups 0..n_spans+1.

    Forward: gather(g+1) is emitted before compute(g) for every span, so
    the next span's wire time hides behind the current span's arithmetic;
    the head's gather hides behind the last span.  Backward: the head VJP
    (which produces the loss) runs first with span n's re-gather already
    in flight, then spans re-gather/VJP/scatter in reverse with span k-1's
    re-gather emitted before span k's VJP.  The stem is gathered once and
    stays live to the end (tied unembeddings read it in the head).
    """
    n = int(n_spans)
    head = head_group(n)
    ev: List[Event] = [(GATHER, STEM_GROUP), (COMPUTE, STEM_GROUP)]
    if n:
        ev.append((GATHER, span_group(0)))
    for k in range(n):
        # prefetch the next group's buckets before this span computes
        ev.append((GATHER, span_group(k + 1) if k + 1 < n else head))
        ev.append((COMPUTE, span_group(k)))
    if not n:
        ev.append((GATHER, head))
    # backward: span n's re-gather overlaps the head VJP
    if n:
        ev.append((GATHER, span_group(n - 1)))
    ev += [(GRAD, head), (SCATTER, head)]
    for k in range(n - 1, -1, -1):
        if k:
            ev.append((GATHER, span_group(k - 1)))     # prefetch re-gather
        ev += [(GRAD, span_group(k)), (SCATTER, span_group(k))]
    ev += [(GRAD, STEM_GROUP), (SCATTER, STEM_GROUP)]
    return tuple(ev)


def _liveness(events: Sequence[Event], n_spans: int):
    """Yield (event, live_groups_after) walking the schedule's liveness.

    A group's gathered buffers are live from its (re)gather until its
    consuming compute/VJP is done; the stem stays live until its own VJP
    (the head may read it for tied unembeddings).
    """
    live: set = set()
    for ph, g in events:
        if ph == GATHER:
            live.add(g)
        elif ph == COMPUTE and g != STEM_GROUP:
            live.discard(g)                    # fwd span dies after compute
        elif ph == GRAD:
            live.discard(g)                    # bwd group dies after its VJP
        yield (ph, g), frozenset(live)
    assert not live, live


def validate_stream_schedule(events: Sequence[Event], n_spans: int) -> None:
    """Assert the streamed-schedule invariants (pure, used by tests/CI)."""
    head = head_group(n_spans)
    pos: Dict[Event, List[int]] = {}
    for i, e in enumerate(events):
        pos.setdefault(e, []).append(i)
    # every span gathers twice (fwd + bwd re-gather), stem/head once
    for k in range(n_spans):
        assert len(pos[(GATHER, span_group(k))]) == 2, k
    assert len(pos[(GATHER, STEM_GROUP)]) == len(pos[(GATHER, head)]) == 1
    # gather precedes the consuming compute / VJP; scatter follows the VJP
    for k in range(n_spans):
        g = span_group(k)
        assert pos[(GATHER, g)][0] < pos[(COMPUTE, g)][0]
        assert pos[(GATHER, g)][1] < pos[(GRAD, g)][0]
        assert pos[(GRAD, g)][0] < pos[(SCATTER, g)][0]
    # the tentpole property: span k+1's wire is in flight before span k's
    # compute (fwd), span k-1's before span k's VJP (bwd)
    for k in range(n_spans - 1):
        assert pos[(GATHER, span_group(k + 1))][0] < \
            pos[(COMPUTE, span_group(k))][0], k
        assert pos[(GATHER, span_group(k))][1] < \
            pos[(GRAD, span_group(k + 1))][0], k
    # at most two *span* gathers in flight at any point (stem/head ride
    # along; the dry-run memory bound counts them separately)
    for _, live in _liveness(events, n_spans):
        spans_live = [g for g in live if 0 < g <= n_spans]
        assert len(spans_live) <= 2, (spans_live, n_spans)


def max_in_flight_gathered_bytes(group_bytes: Dict[int, int],
                                 n_spans: int) -> int:
    """Peak gathered bytes of the schedule (liveness walk, exact)."""
    peak = 0
    for _, live in _liveness(stream_schedule(n_spans), n_spans):
        peak = max(peak, sum(group_bytes.get(g, 0) for g in live))
    return peak


def expected_stream_gathers(plan) -> int:
    """All-gather launches of ONE streamed fwd+bwd (the HLO cross-check).

    Every group's buckets gather once in the forward; spans re-gather in
    the backward (stem and head stay live / are still live at their VJPs).
    Zero-size buckets never launch a collective.
    """
    lay = plan.shard_layout
    n_real = sum(1 for s in lay.bucket_sizes if s)
    n_span_real = sum(
        1 for s, g in zip(lay.bucket_sizes, lay.bucket_groups)
        if s and 0 < g <= plan.n_stream_spans)
    return n_real + n_span_real


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

def streamed_loss_and_grad_shards(plan, layered, shards, batch, *,
                                  remat: bool = True):
    """One streamed fwd+bwd inside shard_map (manual over the dp axes).

    ``plan``     a streamed-policy :class:`~repro.core.plan.AveragingPlan`
                 compiled over the layered param tree;
    ``layered``  the model's :class:`~repro.models.common.LayeredModel`;
    ``shards``   this device's owned shard-slice buffers (full tuple);
    ``batch``    the local batch;
    ``remat``    must equal the gather-all reference's remat flag — remat
                 changes the fused gradient reductions XLA emits (not the
                 math), and bit-exactness vs the gather-all step is the
                 contract.

    Returns ``(loss, metrics, grad_shards)`` where ``grad_shards`` is the
    fp32 pod-mean gradient slice tuple in global bucket order — the same
    object ``plan.grad_shards(jax.grad(model.loss))`` produces on the
    gather-all path, computed without ever materialising the full gathered
    tree: the engine walks :func:`stream_schedule`, composing per-span
    ``jax.vjp`` calls across the saved span-boundary activations.
    """
    n = layered.n_spans
    head = head_group(n)
    if plan.n_stream_spans != n:
        raise ValueError(f"plan has {plan.n_stream_spans} spans, "
                         f"model decomposes into {n}")

    def fwd(fn, *args, **kw):
        # the model under the ``forward`` scope: the VJPs below then name
        # their backward ``transpose(jvp(forward))``, as ``jax.grad`` does
        # on the gather-all path
        with jax.named_scope("forward"):
            return fn(*args, **kw)

    gathered: Dict[int, object] = {}
    regathered: set = set()
    boundary: Dict[int, object] = {}      # span group -> its input carry
    pending: Dict[int, object] = {}       # group -> grads awaiting scatter
    grad_list = [None] * plan.shard_layout.n_buckets
    stem_tree = carry = aux = None
    d_carry = d_stem_head = loss = metrics = None

    for ph, g in stream_schedule(n):
        if ph == GATHER:
            gathered[g] = plan.stream_unshard(shards, g,
                                              barrier=g in regathered)
            regathered.add(g)
        elif ph == COMPUTE:
            if g == STEM_GROUP:
                stem_tree = gathered[STEM_GROUP]   # live until its own VJP
                carry, aux = fwd(layered.stem, stem_tree, batch)
            else:
                boundary[g] = carry
                # forward primal only — no residuals are kept (the backward
                # re-gathers and re-runs the span inside its VJP), so the
                # remat flag is irrelevant here
                carry = fwd(layered.span, g - 1, gathered.pop(g), carry,
                            aux, remat=False)
        elif ph == GRAD:
            if g == head:
                loss, vjp_fn, metrics = jax.vjp(
                    lambda h, s, c: fwd(layered.head_loss, h, s, c, aux,
                                        batch),
                    gathered.pop(head), stem_tree, carry, has_aux=True)
                d_head, d_stem_head, d_carry = vjp_fn(
                    jnp.ones((), loss.dtype))
                pending[head] = d_head
            elif g == STEM_GROUP:
                _, vjp_fn = jax.vjp(
                    lambda s: fwd(layered.stem, s, batch)[0], stem_tree)
                (d_stem,) = vjp_fn(d_carry)
                # tied unembeddings contribute through the head too; for
                # untied models the head cotangent is zeros and the add is
                # a bitwise no-op
                pending[STEM_GROUP] = jax.tree.map(
                    jnp.add, d_stem, d_stem_head)
            else:
                _, vjp_fn = jax.vjp(
                    lambda p, c: fwd(layered.span, g - 1, p, c, aux,
                                     remat=remat),
                    gathered.pop(g), boundary.pop(g))
                pending[g], d_carry = vjp_fn(d_carry)
        else:  # SCATTER: fp32 pod-mean reduce-scatter, bucket order
            for bi, buf in zip(plan.stream_bucket_indices(g),
                               plan.stream_grad_shards(pending.pop(g), g)):
                grad_list[bi] = buf

    assert all(b is not None for b in grad_list)
    return loss, metrics, tuple(grad_list)
