"""Optimisers, data pipeline, checkpointing."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import consolidate, load_checkpoint, save_checkpoint
from repro.configs import SHAPES, get_config
from repro.data import SyntheticTask, make_batch_fn
from repro.optim import adamw, cosine_warmup, sgd


# -- optimisers --------------------------------------------------------------

def test_sgd_momentum_matches_reference():
    opt = sgd(0.1, momentum=0.9)
    p = {"w": jnp.asarray([1.0, -2.0])}
    st_ = opt.init(p)
    g = {"w": jnp.asarray([0.5, 0.5])}
    m = np.zeros(2)
    w = np.asarray([1.0, -2.0])
    for _ in range(5):
        p, st_ = opt.update(g, st_, p)
        m = 0.9 * m + np.asarray([0.5, 0.5])
        w = w - 0.1 * m
    np.testing.assert_allclose(np.asarray(p["w"]), w, rtol=1e-6)


@pytest.mark.parametrize("make", [lambda: sgd(0.05, momentum=0.9),
                                  lambda: adamw(0.05)])
def test_optimizers_minimise_quadratic(make):
    opt = make()
    p = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(8),
                          jnp.float32)}
    st_ = opt.init(p)
    for _ in range(200):
        g = jax.grad(lambda q: jnp.sum(jnp.square(q["w"])))(p)
        p, st_ = opt.update(g, st_, p)
    assert float(jnp.sum(jnp.square(p["w"]))) < 1e-3


def test_momentum_state_is_fp32_under_bf16_params():
    opt = sgd(0.1)
    p = {"w": jnp.zeros((4,), jnp.bfloat16)}
    st_ = opt.init(p)
    assert st_.momentum["w"].dtype == jnp.float32
    p2, _ = opt.update({"w": jnp.ones((4,), jnp.bfloat16)}, st_, p)
    assert p2["w"].dtype == jnp.bfloat16


def test_cosine_warmup_schedule():
    fn = cosine_warmup(1.0, warmup_steps=10, total_steps=100)
    assert float(fn(jnp.asarray(0))) < float(fn(jnp.asarray(9)))
    assert abs(float(fn(jnp.asarray(10))) - 1.0) < 0.12
    assert float(fn(jnp.asarray(99))) < 0.2


# -- data --------------------------------------------------------------------

def test_batches_deterministic_per_step_and_worker():
    t = SyntheticTask(vocab=128, seq_len=32, seed=7)
    a = t.batch(3, 1, 4)
    b = t.batch(3, 1, 4)
    c = t.batch(4, 1, 4)
    d = t.batch(3, 2, 4)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], d["tokens"])


def test_teacher_task_is_learnable():
    """labels follow perm[token] ~75% of the time — predictable structure."""
    t = SyntheticTask(vocab=64, seq_len=128, seed=0, order_mix=0.75)
    b = t.batch(0, 0, 16)
    pred = t.perm[b["tokens"]]
    acc = (pred == b["labels"]).mean()
    assert 0.6 < acc < 0.9


def test_imbalanced_lengths_distribution():
    t = SyntheticTask(vocab=64, seq_len=256, seed=0)
    b = t.imbalanced_batch(0, 0, 256)
    lens = b["lengths"]
    assert lens.min() >= 4 and lens.max() <= 256
    assert lens.std() / lens.mean() > 0.3      # genuinely imbalanced
    assert b["mask"].shape == b["tokens"].shape
    np.testing.assert_array_equal(b["mask"].sum(1), lens)


@settings(max_examples=10, deadline=None)
@given(step=st.integers(0, 100), worker=st.integers(0, 31))
def test_make_batch_fn_family_extras(step, worker):
    cfg = get_config("internvl2-2b", smoke=True)
    fn = make_batch_fn(cfg, SHAPES["train_4k"], seed=0)
    b = fn(step, worker, 2)
    assert b["patches"].shape == (2, cfg.n_patches, cfg.d_model)
    assert b["tokens"].shape[1] == SHAPES["train_4k"].seq_len - cfg.n_patches


# -- checkpoint --------------------------------------------------------------

def test_checkpoint_roundtrip_and_consolidate():
    tree = {
        "emb": jnp.asarray(np.random.default_rng(0).standard_normal((4, 8)),
                           jnp.bfloat16),
        "blocks": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
    }
    opt = {"m": jnp.ones((4, 8), jnp.float32)}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, tree, opt_state=opt, step=42,
                        metadata={"arch": "test"})
        restored, ropt, step = load_checkpoint(d, tree, opt)
        assert step == 42
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        np.testing.assert_array_equal(np.asarray(ropt["m"]), np.asarray(opt["m"]))

    stacked = {"w": jnp.stack([jnp.zeros((3,)), jnp.ones((3,)) * 2.0])}
    cons = consolidate(stacked)
    np.testing.assert_allclose(np.asarray(cons["w"]), [1.0, 1.0, 1.0])


# -- atomic checkpointing (DESIGN.md §13) ------------------------------------

def _tiny_ckpt():
    params = {"w": jnp.arange(6, dtype=jnp.float32),
              "b": {"x": jnp.ones((2, 3), jnp.bfloat16)}}
    opt = {"m": jnp.zeros((6,), jnp.float32)}
    return params, opt


def test_atomic_save_leaves_no_tmp_files():
    params, opt = _tiny_ckpt()
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, params, opt_state=opt, step=1)
        assert not [f for f in os.listdir(d) if f.endswith(".tmp")]
        assert sorted(os.listdir(d)) == ["manifest.json", "opt_state.npz",
                                         "params.npz"]


def test_corrupted_leaf_bytes_fail_the_checksum():
    from repro.checkpoint import ChecksumError

    params, opt = _tiny_ckpt()
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, params, opt_state=opt, step=1)
        # bit rot / torn write: data no longer matches the manifest crc32
        stored = dict(np.load(os.path.join(d, "params.npz")))
        stored["w"] = stored["w"] + 1
        np.savez(os.path.join(d, "params.npz"), **stored)
        with pytest.raises(ChecksumError, match="torn or corrupted"):
            load_checkpoint(d, params, opt)


def test_crash_before_manifest_commit_preserves_previous_checkpoint():
    """Kill the writer between the data rename and the manifest rename
    (the `core.faults.InjectedCrash` the chaos harness schedules): the
    directory then holds NEW data under the OLD manifest.  Loading must
    refuse the torn combination, and after the stale data is discarded
    the previous complete checkpoint is still intact — a crash mid-save
    never loads silently wrong state."""
    from repro.checkpoint import ChecksumError
    from repro.checkpoint import ckpt as ckpt_mod
    from repro.core.faults import InjectedCrash

    params, opt = _tiny_ckpt()
    newer = jax.tree.map(lambda a: a * 3 + 1, params)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, params, opt_state=opt, step=1)
        real_replace = ckpt_mod._replace

        def crash_on_manifest(src, dst):
            if dst.endswith("manifest.json"):
                raise InjectedCrash("killed between data and manifest rename")
            real_replace(src, dst)

        ckpt_mod._replace = crash_on_manifest
        try:
            with pytest.raises(InjectedCrash):
                save_checkpoint(d, newer, opt_state=opt, step=2)
        finally:
            ckpt_mod._replace = real_replace

        # torn: step-2 data under the step-1 manifest -> refused
        with pytest.raises(ChecksumError):
            load_checkpoint(d, params, opt)

        # a retried save commits atomically and wins
        save_checkpoint(d, newer, opt_state=opt, step=2)
        restored, _, step = load_checkpoint(d, params, opt)
        assert step == 2
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(newer["w"]))


def test_crash_before_any_rename_leaves_no_checkpoint_at_all():
    from repro.checkpoint import ckpt as ckpt_mod
    from repro.core.faults import InjectedCrash

    params, opt = _tiny_ckpt()
    with tempfile.TemporaryDirectory() as d:
        real_replace = ckpt_mod._replace
        ckpt_mod._replace = lambda s, t: (_ for _ in ()).throw(
            InjectedCrash("killed before the first rename"))
        try:
            with pytest.raises(InjectedCrash):
                save_checkpoint(d, params, opt_state=opt, step=1)
        finally:
            ckpt_mod._replace = real_replace
        # only a .tmp remains; a reader sees "no checkpoint", never garbage
        assert all(f.endswith(".tmp") for f in os.listdir(d))
        with pytest.raises(FileNotFoundError):
            load_checkpoint(d, params, opt)


def test_pre_checksum_checkpoints_still_load():
    """Manifests written before this PR carry no checksums; they load
    unverified rather than erroring (backward compatibility)."""
    import json

    params, opt = _tiny_ckpt()
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, params, opt_state=opt, step=7)
        mpath = os.path.join(d, "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        manifest.pop("checksums")
        manifest.pop("opt_checksums")
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        restored, ropt, step = load_checkpoint(d, params, opt)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(params["w"]))


def test_replica_state_checkpoint_is_checksum_verified_too():
    """`load_replica_state` routes through the same checksummed rebuild,
    so a torn replica-state save is refused as well."""
    from repro.checkpoint import (ChecksumError, load_replica_state,
                                  save_replica_state)
    from repro.core.replica import ReplicaState

    params, opt = _tiny_ckpt()
    state = ReplicaState.create(params, opt, step=3)
    with tempfile.TemporaryDirectory() as d:
        save_replica_state(d, state)
        back = load_replica_state(d, state)
        assert int(back.step) == 3
        np.testing.assert_array_equal(np.asarray(back.params["w"]),
                                      np.asarray(params["w"]))
        stored = dict(np.load(os.path.join(d, "params.npz")))
        stored["w"] = stored["w"] * 2
        np.savez(os.path.join(d, "params.npz"), **stored)
        with pytest.raises(ChecksumError):
            load_replica_state(d, state)
