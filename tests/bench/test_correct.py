"""``correct`` comes out false for the control and for every fault a cell
can have, and true for the sound program, at a size a test run can hold.

The control is the reference put in the program's place, computed in
int8 (the precision below the configurations' bfloat16).  The faults are
planted in the program under whole runs of the harness
(``fault_scenarios.py``, one process per cell, all cells at once, on as
many CPU devices as the cell has chips).  Only a cell of several chips
can leave out the exchange between chips.
"""

import json
import os
import subprocess
import sys

import pytest

import _paths  # noqa: F401
from bench import correct, harness
from tiny import tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
FAULTS = {
    "qwen3-0.6b.train-2k.1chip": ("unchanged", "half_batch"),
    "transformer-wmt.train.1chip": ("unchanged", "half_batch"),
    "transformer-wmt.allreduce.4chip": ("unchanged", "half_batch",
                                        "no_exchange"),
}
CASES = [(w, f) for w, fs in FAULTS.items() for f in ("sound",) + fs]


@pytest.fixture(scope="module")
def scenario_results(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    procs = {}
    for workload, faults in FAULTS.items():
        chips = harness.resolve(harness.load_manifest(),
                                workload)["cell"]["chips"]
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=cache,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
        procs[workload] = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "fault_scenarios.py"),
             workload, "sound", *faults], cwd=HERE, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    out = {}
    for workload, p in procs.items():
        stdout, stderr = p.communicate(timeout=900)
        assert p.returncode == 0, stderr[-4000:]
        for line in stdout.splitlines():
            if line.startswith("{"):
                rec = json.loads(line)
                out[(workload, rec["fault"])] = rec
    return out


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f}" for w, f in CASES])
def test_fault_makes_correct_false(scenario_results, workload, fault):
    rec = scenario_results[(workload, fault)]
    assert rec["correct"] is (fault == "sound"), rec["checks"]


def _readings(workload, mode):
    import jax
    from bench.traffic import generator
    r = tiny_cell(workload)
    data = generator.batches(r["traffic"], r["spec"]["vocab_size"], 7)
    t0 = harness.first_checked_step(r["train"])
    return harness.reference_for(jax, r, data, t0, 7, mode=mode)


@pytest.mark.parametrize("workload", list(FAULTS))
def test_control_in_lower_precision_is_not_correct(workload):
    ref = _readings(workload, "reference")
    control = _readings(workload, "int8")
    numbers = correct.gaps(control, ref)
    assert not correct.verdict(numbers, correct.load_limits(workload)), \
        numbers
    assert correct.verdict(correct.gaps(ref, ref),
                           correct.load_limits(workload))


def test_the_schedule_follows_algorithm_1():
    # P = 4, S = 2: even steps pair {0,1},{2,3}; odd steps {0,2},{1,3}
    assert correct.group_size(4, None) == 2
    assert correct.group_size(1, None) == 1
    assert correct.group_size(16, None) == 4
    assert correct.exchange_bits(4, 2, 10) == (0,)
    assert correct.exchange_bits(4, 2, 11) == (1,)
    assert correct.exchange_bits(16, 4, 1) == (2, 3)
    assert correct.exchange_bits(1, 1, 5) == ()


def test_average_pairs_syncs_and_rounds_to_storage():
    import jax.numpy as jnp
    reps = [{"w": jnp.full((2,), float(r), jnp.bfloat16)} for r in range(4)]
    train = {"tau": 10, "group_size": None}
    grp = correct.average(reps, 10, train, jnp.bfloat16)
    assert [float(p["w"][0]) for p in grp] == [0.5, 0.5, 2.5, 2.5]
    grp = correct.average(reps, 11, train, jnp.bfloat16)
    assert [float(p["w"][0]) for p in grp] == [1.0, 2.0, 1.0, 2.0]
    syn = correct.average(reps, 9, train, jnp.bfloat16)
    assert [float(p["w"][0]) for p in syn] == [1.5] * 4
    assert syn[0]["w"].dtype == jnp.bfloat16


def test_gaps_take_the_worst_leaf_against_the_median():
    import numpy as np
    ref = {"losses": [2.0, 2.0], "grad_norms": {
        "a": np.array([1.0]), "b": np.array([1.0]), "c": np.array([1e-9])},
        "change_norms": {"a": np.array([1.0]), "b": np.array([2.0]),
                         "c": np.array([5.0])}}
    prog = {"losses": [2.0, 2.2], "grad_norms": {
        "a": np.array([1.1]), "b": np.array([1.0]), "c": np.array([0.5])},
        "change_norms": {"a": np.array([1.0]), "b": np.array([2.5]),
                         "c": np.array([0.0])}}
    g = correct.gaps(prog, ref)
    assert g["loss_gap"] == pytest.approx(0.1)
    # leaf c's reference gradient is nought: its gradient gap is read
    # against the median leaf's norm, and its change is not compared
    assert g["grad_gap"] == pytest.approx(0.5)
    assert g["change_gap"] == pytest.approx(0.25)


def _linear_family():
    """A one-leaf family: loss = mean(tokens * w), so each replica's
    gradient is the mean of its rows."""
    import types
    import jax
    import jax.numpy as jnp

    def loss_and_grad(spec, mm, params, batch):
        x = batch["tokens"].astype(jnp.float32)
        return jax.value_and_grad(
            lambda p: jnp.mean(x * p["w"][None]))(params)

    return types.SimpleNamespace(loss_and_grad=loss_and_grad)


@pytest.mark.parametrize("fault", [None, "no_exchange"])
def test_allreduce_applies_the_mean_gradient_on_every_replica(fault):
    import jax.numpy as jnp
    import numpy as np
    train = {"averager": "allreduce", "learning_rate": 0.5, "momentum": 0.9,
             "tau": 10}
    # two replicas of two rows each: replica 0's rows read 1, replica 1's 3
    batch = {"tokens": np.array([[1, 1], [1, 1], [3, 3], [3, 3]], np.int32)}
    out = correct.reference_readings(
        _linear_family(), {}, train, {"w": jnp.zeros((2,), jnp.float32)},
        [batch] * 2, 2, 0, fault=fault)
    g = np.array([1.0, 3.0]) / 2 * np.sqrt(2)      # each replica's norm
    if fault is None:
        # the mean of the two, the same on both; two momentum steps
        assert out["grad_norms"]["w"] == pytest.approx([2 / 2 * np.sqrt(2)] * 2)
        assert out["change_norms"]["w"] == pytest.approx(
            [0.5 * (1 + 1.9) * np.sqrt(2)] * 2)
    else:
        assert out["grad_norms"]["w"] == pytest.approx(g)
        assert out["change_norms"]["w"] == pytest.approx(0.5 * 2.9 * g)


def test_the_checked_steps_and_warm_up_follow_the_averager():
    import types
    assert harness.first_checked_step({"averager": "wagma", "tau": 10}) == 8
    assert harness.first_checked_step({"averager": "allreduce",
                                       "tau": 10}) == 0

    def trainer(n_phases, sync_every):
        ran = []
        av = types.SimpleNamespace(
            n_phases=n_phases, phase_for_step=lambda t: t % n_phases,
            sync_due=lambda t: bool(sync_every) and (t + 1) % sync_every == 0)
        return types.SimpleNamespace(averager=av, step_once=ran.append), ran

    # allreduce: one variant, run on a step-made state by the checked steps
    tr, ran = trainer(1, 0)
    assert harness.warm_rest(tr, 0, 10) == 3 and ran == []
    # WAGMA over two phases from tau - 2: group 0, sync, group 0 checked;
    # group 1 comes next
    tr, ran = trainer(2, 10)
    assert harness.warm_rest(tr, 8, 10) == 12 and ran == [11]
