"""The train step names its parts for the profiler: scopes in the compiled
HLO's ``op_name`` metadata, host spans and a read counter in
``Trainer.step_once``; the scopes change no numerics."""

import contextlib
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro import compat
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.train import Trainer

ARCHS = ["qwen3-0.6b", "transformer-wmt"]
SCOPES = ["forward", "transpose(jvp(forward))", "rematted_computation",
          "attention", "optimizer"]


def _trainer(arch, seed=0):
    mesh = make_mesh((1, 1), ("data", "model"))
    return Trainer(get_config(arch, smoke=True), mesh, averager="wagma",
                   group_size=1, tau=3, learning_rate=0.3, seq_len=16,
                   global_batch=2, seed=seed)


def _op_names(hlo: str) -> list:
    return re.findall(r'op_name="([^"]*)"', hlo)


def _has(names, scope) -> bool:
    """Some op's path has ``scope`` as a segment, bare or under a
    transform (``jvp(forward)``)."""
    seg = re.compile(r"[/(]" + re.escape(scope) + r"[/)]")
    return any(seg.search(n) for n in names)


@pytest.mark.parametrize("arch", ARCHS)
def test_compiled_step_carries_the_scopes(arch):
    tr = _trainer(arch)
    with compat.set_mesh(tr.mesh):
        names = _op_names(tr.step_hlo(0))
    for scope in SCOPES:
        assert _has(names, scope), scope
    # the backward is the forward's transpose: no op of it sits outside
    assert not any("transpose(" in n and "forward" not in n for n in names)


def test_averaging_scopes_name_each_variant():
    tr = _trainer("qwen3-0.6b")
    with compat.set_mesh(tr.mesh):
        group, sync = tr.step_hlo(0), tr.step_hlo(2)
    assert (tr.variant(0), tr.variant(2)) == ("group:0", "sync")
    assert _has(_op_names(group), "average")
    assert not _has(_op_names(group), "sync")
    assert _has(_op_names(sync), "sync")


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(ev.name, dict(ev.stats)) for ev in line.events
                    if ev.name.startswith("trainer.")]
    return out


def test_step_once_writes_spans_and_counts_reads(tmp_path):
    tr = _trainer("qwen3-0.6b")
    with compat.set_mesh(tr.mesh):
        tr.step_once(0)
        assert tr.host_reads == 3
        jax.profiler.start_trace(str(tmp_path))
        try:
            for t in (1, 2):
                tr.step_once(t)
        finally:
            jax.profiler.stop_trace()
    assert tr.host_reads == 9
    spans = _host_spans(str(tmp_path))
    assert [n for n, _ in spans] == [
        "trainer.put_batch", "trainer.dispatch", "trainer.read_metrics"] * 2
    dispatch = [st for n, st in spans if n == "trainer.dispatch"]
    assert dispatch == [{"variant": "group:0", "step": 1},
                        {"variant": "sync", "step": 2}]
    reads = [st["host_reads"] for n, st in spans
             if n == "trainer.read_metrics"]
    assert reads == [3, 6]


def _two_steps(arch):
    tr = _trainer(arch, seed=3)
    with compat.set_mesh(tr.mesh):
        losses = [tr.step_once(t) for t in range(2)]
        hlo = tr.step_hlo(0)
    return losses, jax.device_get(tr.params), hlo


@pytest.mark.parametrize("arch", ARCHS)
def test_scopes_change_no_numerics(arch, monkeypatch):
    losses, params, hlo = _two_steps(arch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_losses, bare_params, bare_hlo = _two_steps(arch)
    assert _has(_op_names(hlo), "forward")
    assert not any("forward" in n or "optimizer" in n
                   for n in _op_names(bare_hlo))
    assert losses == bare_losses
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(bare_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
