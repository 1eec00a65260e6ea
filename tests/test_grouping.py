"""Algorithm 1 (dynamic grouping): paper worked examples + properties."""

import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import grouping


def test_paper_example_p8_s4():
    # paper §III-B worked example
    assert grouping.groups_for_iteration(8, 4, 0) == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert grouping.groups_for_iteration(8, 4, 1) == ((0, 1, 4, 5), (2, 3, 6, 7))


def test_propagation_latency_matches_paper():
    # paper §V-B: P=64, S=8 -> log_S P = 2 iterations
    assert grouping.propagation_latency(64, 8) == 2
    # gossip-style pairwise: log2 P
    assert grouping.propagation_latency(64, 2) == 6


def test_default_group_size_sqrt_p():
    assert grouping.default_group_size(64) == 8
    assert grouping.default_group_size(256) == 16
    assert grouping.default_group_size(16) == 4


pw2 = st.sampled_from([2, 4, 8, 16, 32, 64, 128, 256])


@settings(max_examples=200, deadline=None)
@given(P=pw2, t=st.integers(0, 1000), data=st.data())
def test_partition_properties(P, t, data):
    ls_max = grouping.ilog2(P)
    S = 2 ** data.draw(st.integers(1, ls_max))
    groups = grouping.groups_for_iteration(P, S, t)
    # non-overlapping groups of exactly S covering range(P)
    flat = sorted(x for g in groups for x in g)
    assert flat == list(range(P))
    assert all(len(g) == S for g in groups)
    assert len(groups) == P // S


@settings(max_examples=100, deadline=None)
@given(P=pw2, t=st.integers(0, 200), data=st.data())
def test_averaging_matrix_doubly_stochastic(P, t, data):
    S = 2 ** data.draw(st.integers(1, grouping.ilog2(P)))
    A = np.asarray(grouping.averaging_matrix(P, S, t))
    np.testing.assert_allclose(A.sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(A.sum(1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(A, A.T)
    # idempotent within an iteration: averaging twice changes nothing
    np.testing.assert_allclose(A @ A, A, atol=1e-6)


@settings(max_examples=100, deadline=None)
@given(P=pw2, data=st.data())
def test_dynamic_groups_propagate_globally(P, data):
    """After propagation_latency(P,S) iterations, one worker's update has
    influenced every worker (the paper's log_S P claim)."""
    S = 2 ** data.draw(st.integers(1, grouping.ilog2(P)))
    t0 = data.draw(st.integers(0, 50))
    influence = np.eye(P, dtype=np.float64)
    lat = grouping.propagation_latency(P, S)
    for t in range(t0, t0 + lat):
        A = np.asarray(grouping.averaging_matrix(P, S, t), np.float64)
        influence = A @ influence
    assert (influence[0] > 0).all(), f"P={P} S={S} lat={lat}"


@settings(max_examples=50, deadline=None)
@given(P=pw2, data=st.data())
def test_fixed_groups_do_not_propagate(P, data):
    """Ablation 2 rationale: with *fixed* groups (offset pinned), influence
    never leaves the initial group."""
    if P < 4:
        return
    S = 2 ** data.draw(st.integers(1, grouping.ilog2(P) - 1))
    A = np.asarray(grouping.averaging_matrix(P, S, 0), np.float64)
    influence = np.eye(P)
    for _ in range(10):
        influence = A @ influence
    assert (influence[0] > 0).sum() == S


def test_mask_bits_distinct_and_rotating():
    P, S = 256, 16
    b0 = grouping.mask_bits(P, S, 0)
    b1 = grouping.mask_bits(P, S, 1)
    assert len(set(b0)) == len(b0) == grouping.ilog2(S)
    assert b0 != b1


def test_phase_offsets_cycle():
    offs = grouping.distinct_offsets(16, 4)
    assert grouping.n_phases(16, 4) == len(offs) == 2
    for t in range(20):
        assert grouping.phase_offset(16, 4, t) in offs


def test_split_bit_over_axes():
    # data=16 minor, pod=2 major
    assert grouping.split_bit_over_axes(0, [16, 2]) == (0, 0)
    assert grouping.split_bit_over_axes(3, [16, 2]) == (0, 3)
    assert grouping.split_bit_over_axes(4, [16, 2]) == (1, 0)
    with pytest.raises(ValueError):
        grouping.split_bit_over_axes(5, [16, 2])
