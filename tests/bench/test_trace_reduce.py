"""The reduction from trace events to per-layer numbers, on event lists
with known answers."""

import pytest

import _paths  # noqa: F401
from bench import trace as T

MS = 1_000_000   # ns


def op(name, start_ms, dur_ms, kind="compute"):
    return [name, int(start_ms * MS), int(dur_ms * MS), kind]


def host_steps(*spans):
    return [["bench.step", int(s * MS), int(d * MS)] for s, d in spans]


def test_union_clip_and_total():
    assert T.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert T.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]
    assert T.total([[0, 3], [5, 8]]) == 6


@pytest.mark.parametrize("text,name,want", [
    ("%fusion.12 = bf16[3072,512]{1,0} fusion(bf16[32768,512]{1,0} "
     "%bitcast.1770), kind=kLoop, calls=%fused_computation.3",
     "fusion.12", "compute"),
    ("%all-reduce.7 = (f32[]{:T(128)}, f32[]{:T(128)}) all-reduce(%multiply"
     ".37, %convert.3), channel_id=1", "all-reduce.7", "collective"),
    ("%collective-permute-start.9 = (f32[16777216]{0}, f32[16777216]{0}, "
     "u32[]{:S(2)}) collective-permute-start(%fusion.1)",
     "collective-permute-start.9", "collective"),
    ("%collective-permute-done.9 = f32[16777216]{0} collective-permute-done("
     "%collective-permute-start.9)", "collective-permute-done.9",
     "collective"),
    ("%all-gather-start = (f32[8]{0}) all-gather-start(%p)",
     "all-gather-start", "collective"),
    ("%_combine_multi_jit.10 = f32[37888,128]{1,0} custom-call(%custom-call"
     ".76, %pad.3), custom_call_target=\"tpu_custom_call\"",
     "_combine_multi_jit.10", "combine"),
    ("%custom-call.2 = bf16[48,64,512]{2,1,0} custom-call(), "
     "custom_call_target=\"AllocateBuffer\"", "custom-call.2", "compute"),
    ("%while.1083 = (s32[], bf16[48,64,512]{2,1,0}) while((s32[], bf16[48,"
     "64,512]{2,1,0}) %tuple.5), condition=%c, body=%b", "while.1083",
     "container"),
    ("%copy.5 = f32[8]{0} copy(f32[8]{0} %all-reduce.3)", "copy.5",
     "compute"),
])
def test_op_kind(text, name, want):
    assert T.op_kind(text) == (name, want)


def test_busy_idle_and_classes_on_one_device():
    # window 0..10 ms; compute 0-4, collective 3-6 (1 ms under compute),
    # combine 7-8, nothing 8-10 and 6-7
    tr = {"devices": {"0": [op("fusion.1", 0, 4), op("all-reduce.1", 3, 3, "collective"),
                            op("_combine_jit.2", 7, 1, "combine")]},
          "host": host_steps((0, 5), (5, 5)) + [["python_work", int(8.5 * MS), MS]]}
    red = T.reduce(tr, [0])
    assert red["window_s"] == pytest.approx(0.010)
    assert red["steps"] == 2
    assert red["busy_s"] == pytest.approx(0.007)
    assert red["compute_s"] == pytest.approx(0.004)
    assert red["collective_s"] == pytest.approx(0.003)
    assert red["combine_s"] == pytest.approx(0.001)
    gaps = red["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.002, 0.001])
    assert gaps[0][0] == "python_work"        # the innermost span at 9 ms
    assert gaps[1][0] == "bench.step"
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.004)


def test_containers_count_as_busy_but_not_as_ops():
    tr = {"devices": {"0": [op("while.1", 0, 8, "container"),
                            op("fusion.1", 1, 2), op("fusion.2", 4, 2)]},
          "host": host_steps((0, 10))}
    red = T.reduce(tr, [0])
    assert red["busy_s"] == pytest.approx(0.008)
    assert red["compute_s"] == pytest.approx(0.008)
    assert [n for n, _ in red["breakdown"]["device_ops"]] == ["fusion.1",
                                                             "fusion.2"]


def test_ops_outside_the_window_do_not_count():
    tr = {"devices": {"0": [op("fusion.1", -5, 6), op("fusion.2", 9, 3)]},
          "host": host_steps((0, 10))}
    red = T.reduce(tr, [0])
    assert red["busy_s"] == pytest.approx(0.002)
    assert 100 * (1 - red["busy_s"] / red["window_s"]) == pytest.approx(80)


def test_devices_are_averaged():
    tr = {"devices": {"0": [op("fusion.1", 0, 10)],
                      "1": [op("fusion.1", 0, 4),
                            op("all-reduce.3", 4, 2, "collective")]},
          "host": host_steps((0, 10))}
    red = T.reduce(tr, [0, 1])
    assert red["busy_s"] == pytest.approx(0.008)
    assert red["compute_s"] == pytest.approx(0.007)
    assert red["collective_s"] == pytest.approx(0.001)
    assert red["per_device"]["1"]["busy_ns"] == 6 * MS


def test_no_step_span_is_an_error():
    with pytest.raises(ValueError, match="bench.step"):
        T.reduce({"devices": {"0": []}, "host": []}, [0])


def test_metric_readers_on_a_reduced_trace():
    from bench import harness
    tr = {"devices": {"0": [op("fusion.1", 0, 6),
                            op("all-reduce.1", 6, 2, "collective"),
                            op("_combine_jit", 8, 1, "combine")]},
          "host": host_steps((0, 5), (5, 5))}
    red = T.reduce(tr, [0])
    ctx = {"trace": red, "device_kind": "TPU v5 lite", "chips": 1,
           "flops_per_step": 197e12 * 0.001}
    read = lambda n: harness.metric_reader(n)(ctx)
    assert read("step.mfu") == pytest.approx(100 * 2 * 0.001 / 0.010)
    assert read("device.idle_share") == pytest.approx(10.0)
    assert read("model.compute_ms") == pytest.approx(3.0)


def _recorded(name):
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        name)
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_one_chip_trace():
    """Three steps of ``transformer-wmt.train.1chip`` (as it stood at a
    32768-token vocabulary) traced on one TPU v5 lite (device ops of the
    "XLA Ops" line, host spans of the stepping thread of at least 20 us),
    reduced to known numbers."""
    tr = _recorded("wmt-1chip-3steps.json.gz")
    assert len(tr["devices"]["0"]) == 12075
    red = T.reduce(tr, [0])
    assert red["steps"] == 3
    assert red["window_s"] == pytest.approx(0.072340743)
    assert red["busy_s"] == pytest.approx(0.054521484)
    assert red["compute_s"] == pytest.approx(red["busy_s"])
    assert red["collective_s"] == red["combine_s"] == 0.0
    assert red["breakdown"]["device_ops"][0] == ["fusion.933",
                                                 pytest.approx(0.00259605)]
    # the longest idle gap: the host fetching the step's loss
    assert red["breakdown"]["idle_gaps"][0] == [
        "$array.py:631 _value", pytest.approx(0.005640478)]
    assert len(red["breakdown"]["device_ops"]) == T.TOP
