"""Distributed WAGMA-SGD train step.

Topology: ``shard_map`` (via ``repro.compat``) *manual* over the
data-parallel mesh axes (``pod``, ``data``) — local gradients, local
optimiser step, then the averager's collective (group butterfly / global
psum / gossip) — and *auto* (GSPMD) over the ``model`` axis for
tensor/expert parallelism inside each replica.

The averager's collective runs through a **compiled AveragingPlan**
(core/plan.py, DESIGN.md §9): the averager's frozen ``Topology`` (mesh axes
→ link classes with their own alpha/beta/gamma constants) is compiled once
per tree structure into a plan that owns the per-stage link classification
(which butterfly bits ride ICI vs DCN), per-link-class bucket layouts and
modeled-optimal budgets, and the wavefront schedule; inside the manual
region the step simply calls ``plan.average(tree, phase)`` /
``plan.sync(tree)``.  The execution realisation is unchanged from §7/§8:
dtype-homogeneous flat buckets (one ppermute per bucket per stage), fused
Pallas combine with fp32 accumulation, overlapped wavefront emission order
(bucket k+1's ppermute before bucket k's combine, same-tick combines in one
multi-bucket Pallas launch) — but every stage run now packs at *its link
class's* budget, and hierarchical (pod-aware) topologies repack only at
class boundaries.  Per-leaf (``fused=False``) and serial-bucketed
(``overlap=False``) behaviour remain available as plan configs and are
differentially tested to match bit-for-bit.

**Replica state (DESIGN.md §10).**  The step operates on a
:class:`~repro.core.replica.ReplicaState` — params + optimiser state +
averager step/phase bookkeeping — whose layout the averager's
:class:`~repro.core.replica.ShardingPolicy` dictates:

* ``replicated`` — model averaging needs divergent per-replica weights, so
  params and optimiser state carry a leading dp-replica axis of size P_dp,
  sharded over (pod, data): global arrays are (P_dp, ...) and each replica
  sees its own slice (squeezed inside the manual region).  Per-device
  memory equals classic replicated data parallelism (the §2 tension).
* ``fsdp_within_pod(shard_axis)`` — replicas inside a pod share weights and
  shard them over the intra-pod (ICI) axis: the state holds
  (P_pods, bucket) flat shard buckets, the step all-gathers params per
  bucket on ICI for fwd/bwd (inside the microbatch body, so the gathered
  tree is a per-microbatch transient and the fp32 grad accumulator is
  shard-sized), reduce-scatters the pod-mean gradient back, updates only
  the owned shard, and the averager butterflies pod-to-pod on the slices
  directly.  Per-device param+opt memory ÷ pod size.
* ``fsdp_within_pod(shard_axis, streamed=True)`` — same sharding, but the
  buckets are laid out layer-aware over the model's layered tree and the
  step runs the **layer-streamed engine** (core/streaming.py, DESIGN.md
  §11): span k+1's gather is in flight while span k computes, the
  backward re-gathers spans and reduce-scatters each span's grads as its
  VJP completes — peak gathered memory ~2 layer spans, bit-identical to
  the gather-all step.

**Compiled-phase-variant dispatch.** XLA collectives need static
permutations, so the group pattern of iteration t is static per compiled
variant: the host loop (launch/train.py ``Trainer._step_fn``) calls
``averager.phase_for_step(t)`` / ``sync_due(t)`` and dispatches to one of
``averager.n_phases + 1`` cached jitted step functions (+1 = the tau-sync
step).  Every variant shares the same bucket layout cache.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.group_allreduce import dp_axis_layout
from repro.core.replica import ReplicaState, map_opt_state
from repro.models import common as cm


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _dp_spec(mesh):
    dp = dp_axes_of(mesh)
    return dp if len(dp) > 1 else dp[0]


def stacked_init(model, mesh, key, abstract: bool = False):
    """Per-replica-divergent params: leading dp axis of size P_dp.

    abstract=True returns ShapeDtypeStructs with shardings (for dry-run).
    """
    dp = dp_axes_of(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    shapes = jax.eval_shape(model.init, key)
    model_specs = cm.tree_specs(shapes)
    dp_spec = _dp_spec(mesh)

    def full_spec(spec):
        return P(dp_spec, *spec)

    specs = jax.tree.map(full_spec, model_specs,
                         is_leaf=lambda x: isinstance(x, P))
    if abstract:
        tree = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                (n_dp,) + s.shape, s.dtype,
                sharding=NamedSharding(mesh, sp)),
            shapes, specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        return tree, specs

    params0 = model.init(key)

    def rep(a, sp):
        out = jnp.broadcast_to(a[None], (n_dp,) + a.shape)
        return jax.device_put(out, NamedSharding(mesh, sp))

    return jax.tree.map(rep, params0, specs), specs


@functools.lru_cache(maxsize=32)
def _model_shapes(model):
    """Abstract full param tree (key-independent shapes).

    Cached per model object: every step-variant build and spec derivation
    re-asks for the same shapes, and eval_shape re-traces ``model.init``
    each time otherwise.  The key is made inside the trace: nothing may
    run eagerly under a mesh of described (unattached) TPU devices.
    """
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


@functools.lru_cache(maxsize=32)
def _layered_shapes(model):
    """Abstract *layered* param tree ``{"stem", "layers", "head"}``.

    The tree the streamed-policy plan compiles over (its layer-aware shard
    layout needs per-leaf layer ids — DESIGN.md §11).
    """
    if model.layered is None:
        raise ValueError(
            f"--sharding fsdp --streamed needs a per-layer apply "
            f"decomposition, but the {model.cfg.family!r} family does not "
            "expose one (models/registry.ModelAPI.layered)")
    return jax.eval_shape(model.layered.split, _model_shapes(model))


def _plan_of(model, averager):
    """The averager's compiled plan for this model's state tree.

    Streamed FSDP plans compile over the layered tree (layer-aware shard
    layout); everything else over the canonical full tree.
    """
    if averager.sharding.is_sharded and averager.sharding.streamed:
        return averager.plan_for(_layered_shapes(model))
    return averager.plan_for(_model_shapes(model))


def _eff_dim0_spec(mesh, averager):
    """Dim-0 spec for (P_eff, ...) stacked FSDP state arrays.

    Mesh-order (major-to-minor) effective dp axes, so the C-order index of
    dim 0 equals the minor-to-major effective replica rank — the same
    convention the replicated (P_dp, ...) stacking and the stacked
    simulator use.
    """
    shard_axis = averager.sharding.shard_axis
    eff = tuple(a for a in dp_axes_of(mesh) if a != shard_axis)
    return eff if len(eff) != 1 else eff[0]


def replica_state_specs(model, optimizer, averager, mesh):
    """PartitionSpec pytree for a :class:`ReplicaState` (shard_map in/out).

    Replicated: every params/opt leaf shards dim 0 (the replica axis) over
    all dp axes.  FSDP: the (P_pods, bucket) buffers shard dim 0 over the
    effective (pod) axes and dim 1 over the shard axis; the per-replica
    optimiser ``count`` shards dim 0 only.
    """
    dp_spec = _dp_spec(mesh)
    if not averager.sharding.is_sharded:
        lead = P(dp_spec)
        return ReplicaState(lead, lead, P(), P())
    eff0 = _eff_dim0_spec(mesh, averager)
    buf = P(eff0, averager.sharding.shard_axis)
    plan = _plan_of(model, averager)
    opt_shapes = jax.eval_shape(optimizer.init, plan.shard_struct())
    opt_specs = map_opt_state(opt_shapes, lambda _: buf, lambda _: P(eff0))
    return ReplicaState(buf, opt_specs, P(), P())


def _scalar_sds(mesh):
    return jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))


def init_replica_state(model, optimizer, averager, mesh, key,
                       abstract: bool = False) -> ReplicaState:
    """Build the global :class:`ReplicaState` the train step operates on.

    Replicated policy: (P_dp, ...)-stacked divergent params (``stacked_init``)
    + vmapped optimiser state.  FSDP policy: the compiled plan's
    shard-aligned bucket buffers, stacked (P_pods, bucket) and sharded over
    (effective axes, shard axis).  ``abstract=True`` returns
    ShapeDtypeStructs with shardings (dry-run compilation).
    """
    from repro.core import bucketing

    is_sds = lambda x: isinstance(x, jax.ShapeDtypeStruct)

    if not averager.sharding.is_sharded:
        if abstract:
            params, pspecs = stacked_init(model, mesh, key, abstract=True)
            opt_shapes = jax.eval_shape(
                lambda p: jax.vmap(optimizer.init)(p), params)
            _, opt_sh = train_shardings(mesh, pspecs, opt_shapes, params)
            opt_sds = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                opt_shapes, opt_sh, is_leaf=is_sds)
            return ReplicaState(params, opt_sds, _scalar_sds(mesh),
                                _scalar_sds(mesh))
        params, _ = stacked_init(model, mesh, key)
        opt_state = jax.jit(lambda p: jax.vmap(optimizer.init)(p))(params)
        return ReplicaState.create(params, opt_state)

    plan = _plan_of(model, averager)
    specs = replica_state_specs(model, optimizer, averager, mesh)
    n_eff = plan.P_eff
    lay = plan.shard_layout
    buf_sharding = NamedSharding(mesh, specs.params)
    if abstract:
        bufs = tuple(
            jax.ShapeDtypeStruct((n_eff, size), dt, sharding=buf_sharding)
            for size, dt in zip(lay.bucket_sizes, lay.bucket_dtypes))
    else:
        init_tree = model.init(key)
        if averager.sharding.streamed:
            init_tree = model.layered.split(init_tree)
        packed = bucketing.pack(init_tree, lay)
        bufs = tuple(
            jax.device_put(jnp.broadcast_to(b[None], (n_eff,) + b.shape),
                           buf_sharding)
            for b in packed)
    opt_shapes = jax.eval_shape(lambda p: jax.vmap(optimizer.init)(p), bufs)
    if abstract:
        count_sharding = NamedSharding(mesh,
                                       P(_eff_dim0_spec(mesh, averager)))
        opt = map_opt_state(
            opt_shapes,
            lambda sub: jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=buf_sharding),
                sub, is_leaf=is_sds),
            lambda c: jax.ShapeDtypeStruct(c.shape, c.dtype,
                                           sharding=count_sharding))
        return ReplicaState(bufs, opt, _scalar_sds(mesh), _scalar_sds(mesh))
    opt = jax.jit(lambda p: jax.vmap(optimizer.init)(p))(bufs)
    return ReplicaState.create(bufs, opt)


def tree_all_finite(tree):
    """Traced scalar bool: every leaf of ``tree`` is NaN/Inf-free."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.asarray(True)
    return functools.reduce(jnp.logical_and,
                            (jnp.isfinite(l).all() for l in leaves))


def guarded_update(optimizer, grads, opt_state, params, *, finite=None):
    """Optimiser update with the non-finite gradient guard (DESIGN.md §13).

    When ``grads`` contain a NaN/Inf, the whole update is skipped —
    params and optimiser state pass through **bit-exact** — so a
    diverging or corrupted replica contributes its last good weights to
    the group average instead of poisoning it.  When grads are finite
    the result is bit-exact ``optimizer.update`` (``where(True, new,
    old)``), so differential tests see no change.  Pass ``finite`` to
    override the local check (the fsdp step pmin-reduces it over the
    shard axis first, so every shard of a pod agrees).  Returns
    ``(new_params, new_opt_state, skipped)``.
    """
    if finite is None:
        finite = tree_all_finite(grads)
    new_params, new_opt = optimizer.update(grads, opt_state, params)
    keep = lambda new, old: jnp.where(finite, new, old)
    new_params = jax.tree.map(keep, new_params, params)
    new_opt = jax.tree.map(keep, new_opt, opt_state)
    return new_params, new_opt, jnp.logical_not(finite)


def build_train_step(model, optimizer, averager, mesh, *, phase: int,
                     sync: bool, microbatch: Optional[int] = None,
                     remat: bool = True):
    """Returns jitted step(state: ReplicaState, batch) -> (state, metrics)."""
    from repro.core import streaming

    dp = dp_axes_of(mesh)
    dp_spec = _dp_spec(mesh)
    sharded = averager.sharding.is_sharded
    streamed = sharded and averager.sharding.streamed
    plan = _plan_of(model, averager) if sharded else None
    layered = model.layered if streamed else None

    def _accumulate_microbatches(one, batch, g0):
        """Scan ``one(mb) -> (grads, metrics, loss)`` over microbatches.

        Shared by all three grad paths: fp32 accumulation into ``g0``
        (zeros shaped like the grads — a full-tree pytree for replicated,
        the shard-slice tuple for fsdp), mean loss metrics.  ``one`` runs
        entirely inside the scan body, so any gather it performs is a
        body-local transient, never pinned across the scan.
        """
        b_local = jax.tree.leaves(batch)[0].shape[0]
        if b_local % microbatch or b_local < microbatch:
            raise ValueError(
                f"microbatch={microbatch} must divide the per-replica "
                f"batch {b_local}")
        mbs = jax.tree.map(
            lambda a: a.reshape((microbatch, a.shape[0] // microbatch)
                                + a.shape[1:]), batch)

        def acc_body(carry, mb):
            g_acc, l_acc = carry
            g, metrics, loss = one(mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + loss), metrics

        (grads, _), metrics_all = jax.lax.scan(
            acc_body, (g0, jnp.zeros((), jnp.float32)), mbs)
        grads = jax.tree.map(lambda g: g / microbatch, grads)
        metrics = jax.tree.map(lambda m: m.mean(), metrics_all)
        return grads, metrics

    def _shard_g0():
        return tuple(jnp.zeros(s.shape, jnp.float32)
                     for s in plan.shard_struct())

    def grads_and_metrics(params, batch):
        def loss_fn(p, mb):
            with jax.named_scope("forward"):
                loss, metrics = model.loss(p, mb, remat=remat)
            return loss, metrics

        def one(mb):
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            return g, metrics, loss

        if microbatch and microbatch > 1:
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            return _accumulate_microbatches(one, batch, g0)
        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, metrics

    def sharded_grads_and_metrics(shards, batch):
        """Gather-all FSDP grads -> fp32 pod-mean shard slices.

        The gather and the reduce-scatter both live INSIDE the microbatch
        body: the gathered tree is a body-local transient (freed after each
        microbatch's bwd, never pinned across the scan) and the fp32
        accumulator is shard-sized, not full-tree-sized.
        """
        def loss_fn(p, mb):
            with jax.named_scope("forward"):
                return model.loss(p, mb, remat=remat)

        def one(mb):
            full = plan.unshard_tree(shards)
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(full, mb)
            return plan.grad_shards(g), metrics, loss

        if microbatch and microbatch > 1:
            return _accumulate_microbatches(one, batch, _shard_g0())
        grads, metrics, _ = one(batch)
        return grads, metrics

    def streamed_grads_and_metrics(shards, batch):
        """Layer-streamed FSDP grads (core/streaming.py, DESIGN.md §11).

        Gather span k+1 while span k computes; backward re-gathers spans
        (span-level remat) and reduce-scatters each span's pod-mean fp32
        gradient the moment its VJP completes.  Bit-identical to
        ``sharded_grads_and_metrics`` — same per-span primal/VJP ops, same
        fp32 pack -> psum_scatter -> 1/pod scaling.
        """
        def one(mb):
            loss, metrics, gs = streaming.streamed_loss_and_grad_shards(
                plan, layered, shards, mb, remat=remat)
            return gs, metrics, loss

        if microbatch and microbatch > 1:
            return _accumulate_microbatches(one, batch, _shard_g0())
        grads, metrics, _ = one(batch)
        return grads, metrics

    def exchange(tree):
        """The averager's collective, under the ``sync`` or ``average``
        scope that the trace's per-scope device time reads."""
        if sync:
            with jax.named_scope("sync"):
                return averager.sync(tree)
        with jax.named_scope("average"):
            return averager.comm(tree, phase)

    def replica_fn(params, opt_state, batch):
        if streamed:
            grads, metrics = streamed_grads_and_metrics(params, batch)
        elif sharded:
            grads, metrics = sharded_grads_and_metrics(params, batch)
        else:
            grads, metrics = grads_and_metrics(params, batch)

        if averager.grad_comm:
            grads = exchange(grads)
        # non-finite guard on the (pod-mean, for fsdp; group/global-mean,
        # for grad_comm averagers) gradients: a poisoned replica skips its
        # update and keeps averaging in its last good weights
        with jax.named_scope("optimizer"):
            finite = tree_all_finite(grads)
            if sharded:
                # psum-scattered pod-mean shards can carry the NaN on one
                # slice only; every shard of the pod must agree to skip
                finite = jax.lax.pmin(finite.astype(jnp.int32),
                                      averager.sharding.shard_axis) > 0
            new_params, new_opt, skipped = guarded_update(
                optimizer, grads, opt_state, params, finite=finite)
        if not averager.grad_comm:
            new_params = exchange(new_params)
        metrics = dict(metrics)
        metrics["skipped_nonfinite"] = skipped
        metrics = {k: jax.lax.pmean(v.astype(jnp.float32), dp)
                   for k, v in metrics.items()}
        return new_params, new_opt, metrics

    squeeze = lambda t: jax.tree.map(lambda a: a[0], t)
    expand = lambda t: jax.tree.map(lambda a: a[None], t)

    def step(state, batch):
        p, o, m = replica_fn(squeeze(state.params), squeeze(state.opt_state),
                             batch)
        # the optimiser's elementwise update fuses into this write of the
        # new state, and a fusion takes the op_name of its last op
        with jax.named_scope("optimizer"):
            new_state = ReplicaState(
                expand(p), expand(o), state.step + 1,
                jnp.asarray(-1 if sync else phase, jnp.int32))
        return new_state, m

    # The module takes this name (``jit_group_step``, ``jit_sync_step``),
    # which tells the variants apart in a trace.  The persistent
    # compilation cache keys a module by its name and its ops with their
    # metadata stripped: a step cached under the same name before its
    # scopes moved comes back with the old ``op_name``s.
    step.__name__ = "sync_step" if sync else "group_step"

    state_specs = replica_state_specs(model, optimizer, averager, mesh)
    sm = compat.shard_map(
        step, mesh=mesh,
        in_specs=(state_specs, P(dp_spec)),
        out_specs=(state_specs, P()),
        axis_names=set(dp), check_vma=False,
    )
    return jax.jit(sm, donate_argnums=(0,))


def train_shardings(mesh, param_specs, opt_state_shapes, params_shapes):
    """NamedSharding trees for (params, opt_state) given the param specs.

    Momentum/mu/nu leaves have the same (stacked) shapes as params and take
    the matching param spec; scalar counts take P(dp).
    """
    dp_spec = _dp_spec(mesh)
    spec_by_shape = {}
    for sp, sh in zip(
            jax.tree.leaves(param_specs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.leaves(params_shapes,
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))):
        spec_by_shape.setdefault(tuple(sh.shape), sp)

    def opt_spec(leaf):
        sp = spec_by_shape.get(tuple(leaf.shape))
        if sp is None:
            sp = P(*([dp_spec] + [None] * (len(leaf.shape) - 1))) \
                if len(leaf.shape) >= 1 else P()
        return sp

    opt_specs = jax.tree.map(opt_spec, opt_state_shapes,
                             is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    to_ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    return to_ns(param_specs), to_ns(opt_specs)


def batch_shardings(mesh, batch_shapes):
    """Batch arrays shard axis 0 (global batch) over the dp axes."""
    dp_spec = _dp_spec(mesh)

    def spec(leaf):
        return NamedSharding(mesh, P(dp_spec, *([None] * (len(leaf.shape) - 1))))

    return jax.tree.map(spec, batch_shapes,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
