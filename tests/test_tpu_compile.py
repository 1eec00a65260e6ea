"""Native TPU compiles of the main path, for a described v5e:2x2.

The TPU compiler ships with jaxlib and compiles for a topology that is
described but not attached, so these tests need no chip.  They check what
interpret mode cannot: that the Mosaic combine kernel lowers at real bucket
sizes, and that the four-chip WAGMA train step compiles with the kernel
inside it (a Mosaic kernel cannot be partitioned, so it must sit where every
mesh axis is manual).  Interpret mode is steered off in each test.

The topology is described inside a module fixture: only one process at a
time may load the TPU library, so nothing here touches it at import.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# The ICI class's modeled bucket budget (plan.choose_class_bucket_bytes on a
# 256 MiB payload): the largest bucket the butterfly hands the kernel on ICI.
ICI_BUCKET_BYTES = 8 * 2**20
# Ragged multi-bucket batch: a full bucket, a lane-unaligned one, a tiny one.
RAGGED_FRACTIONS = (1.0, 0.37, 0.0001)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture
def native(monkeypatch):
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("multi", [False, True], ids=["single", "ragged"])
def test_combine_kernel_compiles_natively(topo, native, dtype, multi):
    one = SingleDeviceSharding(topo.devices[0])
    n = ICI_BUCKET_BYTES // jnp.dtype(dtype).itemsize
    sizes = [max(1, int(n * f)) + (7 if f < 1 else 0)
             for f in RAGGED_FRACTIONS] if multi else [n]
    bufs = [jax.ShapeDtypeStruct((s,), dtype, sharding=one) for s in sizes]
    if multi:
        fn = jax.jit(lambda ws, rs: ops.group_average_combine_multi(
            ws, rs, 0.5))
    else:
        fn = jax.jit(lambda ws, rs: [ops.group_average_combine(
            ws[0], rs[0], 0.5)])
    compiled = fn.lower(bufs, bufs).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    assert [o.shape for o in jax.tree.leaves(
        compiled.out_info)] == [(s,) for s in sizes]


@pytest.mark.parametrize("policy", ["replicated", "fsdp_streamed"])
def test_four_chip_wagma_step_compiles_natively(topo, native, policy):
    from repro import compat
    from repro.configs import get_config
    from repro.core.baselines import make_averager
    from repro.core.group_allreduce import dp_axis_layout
    from repro.core.plan import Topology
    from repro.launch.mesh import make_mesh
    from repro.launch.train import resolve_sharding
    from repro.models.registry import build_model
    from repro.optim import sgd
    from repro.train import (batch_shardings, build_train_step, dp_axes_of,
                             init_replica_state)

    if policy == "replicated":
        mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    else:
        mesh = make_mesh((2, 2, 1), ("pod", "data", "model"),
                         devices=topo.devices)
    names, sizes = dp_axis_layout(mesh.axis_names, dict(mesh.shape),
                                  dp_axes_of(mesh))
    kw = {}
    if policy == "fsdp_streamed":
        kw = dict(topology=Topology.hierarchical(names, sizes,
                                                 dcn_axes=("pod",)),
                  sharding=resolve_sharding("fsdp", names, streamed=True))
    model = build_model(get_config("qwen3-0.6b", smoke=True))
    av = make_averager("wagma", names, sizes, group_size=2, tau=3, **kw)
    opt = sgd(0.1, momentum=0.9)
    key = jax.random.PRNGKey(0)      # made on the host, outside the mesh
    with compat.set_mesh(mesh):
        state = init_replica_state(model, opt, av, mesh, key, abstract=True)
        shapes = {k: jax.ShapeDtypeStruct((8, 64), jnp.int32)
                  for k in ("tokens", "labels")}
        shardings = batch_shardings(mesh, shapes)
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                         sharding=shardings[k])
                 for k, v in shapes.items()}
        step = build_train_step(model, opt, av, mesh, phase=0, sync=False)
        hlo = step.lower(state, batch).compile().as_text()
    assert "collective-permute" in hlo
    assert "tpu_custom_call" in hlo


def _score_shaped_updates(hlo: str, block_q: int, block_k: int) -> list:
    """``dynamic-update-slice`` ops whose result ends in a score block."""
    import re
    tail = re.compile(rf"\[[0-9,]*{block_q},{block_k}\]")
    return [ln for ln in hlo.splitlines()
            if "dynamic-update-slice(" in ln and tail.search(ln.split("=")[1])]


def _qwen3_layer(b: int = 1, s: int = 2048):
    """One qwen3-0.6b attention layer at real widths (16/8 heads, hd 128)
    and a fresh function for its gradients under ``jax.remat``: a new
    function object each call, so each route is traced anew."""
    from repro.configs import get_config
    from repro.models import transformer as tfm
    cfg = get_config("qwen3-0.6b").variant(head_dim=128, n_layers=1)

    def make_grads():
        def grads(p, x, cot):
            pos = jnp.broadcast_to(jnp.arange(s), (b, s))
            layer = jax.remat(lambda p, x: tfm.attn_layer(cfg, p, x, pos,
                                                          None))
            return jax.grad(lambda p, x: jnp.sum(
                layer(p, x).astype(jnp.float32) * cot), argnums=(0, 1))(p, x)
        return grads

    def init(key):
        kp, kx, kc = jax.random.split(key, 3)
        x = jax.random.normal(kx, (b, s, cfg.d_model), jnp.float32)
        return (tfm.init_layer(cfg, kp, jnp.bfloat16), x.astype(jnp.bfloat16),
                jax.random.normal(kc, (b, s, cfg.d_model), jnp.float32))
    return cfg, make_grads, init


def test_qwen3_attention_layer_compiles_through_the_kernel(topo, native,
                                                            monkeypatch):
    """Routed as on a TPU, the layer's gradients compile for a described v5e
    with the splash kernel in them and no score-shaped buffer stacked for
    the backward; routed to the blocked path they stack such buffers, which
    keeps the check honest."""
    from repro.models import common as cm
    cfg, make_grads, init = _qwen3_layer()
    one = SingleDeviceSharding(topo.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    rule = cm.attention_path

    def compile_as(backend):
        monkeypatch.setattr(cm, "attention_path", lambda *a, **kw: rule(
            *a, **{**kw, "backend": backend}))
        return jax.jit(make_grads()).lower(*args).compile().as_text()

    before = cm.ATTENTION_PATHS["kernel"]
    hlo = compile_as("tpu")
    assert cm.ATTENTION_PATHS["kernel"] > before
    assert "tpu_custom_call" in hlo
    assert _score_shaped_updates(hlo, cfg.attn_block_q, cfg.attn_block_k) == []
    hlo = compile_as("cpu")
    assert "tpu_custom_call" not in hlo
    assert _score_shaped_updates(hlo, cfg.attn_block_q, cfg.attn_block_k)


def test_qwen3_attention_layer_runs_the_kernel_on_tpu(monkeypatch):
    """On a TPU the layer's gradients run through the splash kernel, with no
    score-shaped buffer stacked for the backward, and match the blocked
    path's within bfloat16 tolerance."""
    if jax.default_backend() != "tpu":
        pytest.skip("runs on a TPU")
    import numpy as np
    from repro.models import common as cm
    cfg, make_grads, init = _qwen3_layer()
    args = init(jax.random.PRNGKey(0))

    def run():
        compiled = jax.jit(make_grads()).lower(*args).compile()
        return compiled(*args), compiled.as_text()

    before = cm.ATTENTION_PATHS["kernel"]
    got, hlo = run()
    assert cm.ATTENTION_PATHS["kernel"] > before
    assert "tpu_custom_call" in hlo
    assert _score_shaped_updates(hlo, cfg.attn_block_q, cfg.attn_block_k) == []

    monkeypatch.setattr(cm, "attention_path", lambda *a, **kw: "blocked")
    want, hlo = run()
    assert "tpu_custom_call" not in hlo
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        err = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30)
        assert err < 3e-2, err
