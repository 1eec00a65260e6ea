"""Wait-avoidance / staleness simulator semantics (paper Alg. 2 lines 8-17)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import group_allreduce as ga
from repro.core import staleness


def _state(P, dim=6, seed=0):
    rng = np.random.default_rng(seed)
    W = {"w": jnp.asarray(rng.standard_normal((P, dim)), jnp.float32)}
    return staleness.init_state(W)


def _identity_update(W):
    return W


def test_no_stragglers_equals_group_average():
    P, S = 8, 4
    st_ = _state(P)
    ready = jnp.ones((P,), bool)
    out = staleness.wagma_sim_step(st_, _identity_update, P=P, S=S, tau=100,
                                   ready=ready, completes=ready, t=0)
    want = ga.group_average_stacked(st_.models, P=P, S=S, t=0)
    np.testing.assert_allclose(np.asarray(out.models["w"]),
                               np.asarray(want["w"]), rtol=1e-6)
    assert (np.asarray(out.age) == 0).all()


def test_sync_step_equalises_everything():
    P, S = 8, 4
    st_ = _state(P)
    ready = jnp.zeros((P,), bool)          # even with everyone late,
    out = staleness.wagma_sim_step(st_, _identity_update, P=P, S=S, tau=1,
                                   ready=ready, completes=ready, t=0)
    w = np.asarray(out.models["w"])
    np.testing.assert_allclose(w, np.broadcast_to(w.mean(0), w.shape),
                               rtol=1e-6)
    assert (np.asarray(out.age) == 0).all()


def test_straggler_contributes_stale_buffer():
    """A late worker's *buffer* (old model) enters the group sum, and the
    late worker merges per line 13: (Wsum + W')/(S+1)."""
    P, S = 4, 2
    st_ = _state(P, dim=1, seed=1)
    W0 = np.asarray(st_.models["w"]).copy()

    def upd(W):
        return jax.tree.map(lambda a: a + 1.0, W)

    ready = jnp.asarray([True, False, True, True])
    completes = jnp.ones((P,), bool)
    out = staleness.wagma_sim_step(st_, upd, P=P, S=S, tau=100,
                                   ready=ready, completes=completes, t=0)
    # groups at t=0 for P=4,S=2: {0,1},{2,3}
    w = np.asarray(out.models["w"])[:, 0]
    wp = W0[:, 0] + 1.0                     # everyone's W'
    wsum_01 = wp[0] + W0[1, 0]              # P1 contributed stale buffer
    assert np.isclose(w[0], wsum_01 / S)                       # line 11
    assert np.isclose(w[1], (wsum_01 + wp[1]) / (S + 1))       # line 13
    wsum_23 = wp[2] + wp[3]
    assert np.isclose(w[2], wsum_23 / S)
    assert np.isclose(w[3], wsum_23 / S)
    assert np.asarray(out.age)[1] == 1


def test_non_completing_worker_keeps_model_and_ages():
    P, S = 4, 2
    st_ = _state(P, dim=3, seed=2)
    W0 = np.asarray(st_.models["w"]).copy()

    def upd(W):
        return jax.tree.map(lambda a: a * 2.0, W)

    ready = jnp.asarray([True, False, True, True])
    completes = jnp.asarray([True, False, True, True])
    out = staleness.wagma_sim_step(st_, upd, P=P, S=S, tau=100,
                                   ready=ready, completes=completes, t=0)
    # stalled worker is mid-computation: model unchanged, buffer unchanged
    np.testing.assert_allclose(np.asarray(out.models["w"])[1], W0[1])
    np.testing.assert_allclose(np.asarray(out.buffers["w"])[1], W0[1])
    assert np.asarray(out.age)[1] == 1


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000), n_straggle=st.integers(0, 3),
       p_stall=st.floats(0.0, 0.9))
def test_staleness_bounded_by_tau(seed, n_straggle, p_stall):
    """Theory Assumption 3: tau-periodic sync bounds buffer age by tau."""
    P, S, tau = 8, 4, 5
    st_ = _state(P, dim=4, seed=seed)
    model = staleness.StragglerModel(P, n_stragglers=n_straggle,
                                     p_stall=p_stall, seed=seed)

    def upd(W):
        return jax.tree.map(lambda a: a + 0.1, W)

    max_age = 0
    for t in range(3 * tau):
        ready, completes = model.sample()
        st_ = staleness.wagma_sim_step(st_, upd, P=P, S=S, tau=tau,
                                       ready=ready, completes=completes, t=t)
        max_age = max(max_age, int(np.asarray(st_.age).max()))
        if (t + 1) % tau == 0:
            assert int(np.asarray(st_.age).max()) == 0
    assert max_age <= staleness.max_staleness_bound(tau)


def test_straggler_model_no_stragglers_edge():
    """n_stragglers=0 must degenerate to the fully-synchronous schedule."""
    model = staleness.StragglerModel(8, n_stragglers=0, p_stall=1.0, seed=4)
    for _ in range(5):
        ready, completes = model.sample()
        assert np.asarray(ready).all() and np.asarray(completes).all()


def test_straggler_model_p_stall_one_edge():
    """p_stall=1.0: every drawn straggler also fails to complete."""
    model = staleness.StragglerModel(8, n_stragglers=3, p_stall=1.0, seed=5)
    for _ in range(10):
        ready, completes = model.sample()
        r, c = np.asarray(ready), np.asarray(completes)
        assert (~r).sum() == 3
        np.testing.assert_array_equal(r, c), \
            "a stalled straggler must not count as completing"


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), tau=st.integers(2, 7),
       p_ready=st.floats(0.0, 1.0), p_complete=st.floats(0.0, 1.0))
def test_age_bounded_under_arbitrary_schedules(seed, tau, p_ready,
                                               p_complete):
    """The tau bound must hold for ANY ready/completes schedule, not just
    StragglerModel's (which draws a fixed straggler count per step): age
    resets at every sync and never exceeds max_staleness_bound(tau) in
    between, even when whole iterations have nobody ready."""
    P, S = 8, 4
    rng = np.random.default_rng(seed)
    st_ = _state(P, dim=3, seed=seed)

    def upd(W):
        return jax.tree.map(lambda a: a + 0.1, W)

    for t in range(3 * tau):
        ready = rng.random(P) < p_ready
        completes = np.logical_or(ready, rng.random(P) < p_complete)
        st_ = staleness.wagma_sim_step(st_, upd, P=P, S=S, tau=tau,
                                       ready=jnp.asarray(ready),
                                       completes=jnp.asarray(completes), t=t)
        ages = np.asarray(st_.age)
        assert ages.max() <= staleness.max_staleness_bound(tau), \
            (t, ages.tolist())
        if (t + 1) % tau == 0:
            assert ages.max() == 0, "sync must reset all staleness"


def test_mean_preserved_without_stragglers():
    P, S = 16, 4
    st_ = _state(P, dim=5, seed=3)
    mean0 = np.asarray(st_.models["w"]).mean(0)
    ready = jnp.ones((P,), bool)
    for t in range(7):
        st_ = staleness.wagma_sim_step(st_, _identity_update, P=P, S=S,
                                       tau=100, ready=ready, completes=ready,
                                       t=t)
    np.testing.assert_allclose(np.asarray(st_.models["w"]).mean(0), mean0,
                               rtol=1e-5, atol=1e-6)
