"""Device time by the program's scopes and device idle inside its host spans
(``bench/scopes.py``), on traces with known answers."""

import pytest

import _paths  # noqa: F401
from bench import harness
from bench import scopes as S

MS = 1_000_000   # ns
STEP = "jit(train_step)"
FWD = f"{STEP}/jvp(forward)/while/body/closed_call"
BWD = f"{STEP}/transpose(jvp(forward))/while/body/closed_call/checkpoint"
RECOMPUTE = f"{BWD}/rematted_computation"


def op(start_ms, dur_ms, op_name="", kind="compute", name="fusion.1"):
    return [name, int(start_ms * MS), int(dur_ms * MS), kind, op_name]


def span(name, start_ms, dur_ms, **stats):
    return [name, int(start_ms * MS), int(dur_ms * MS), stats]


@pytest.mark.parametrize("op_name,want", [
    (f"{FWD}/dot_general:", ("forward",)),
    (f"{FWD}/attention/exp", ("forward", "attention")),
    (f"{STEP}/forward/attention/exp", ("forward", "attention")),
    (f"{BWD}/dot_general", ("backward",)),
    (f"{BWD}/attention/mul", ("backward", "attention")),
    (f"{RECOMPUTE}/add", ("backward", "recompute")),
    (f"{RECOMPUTE}/attention/while/body/exp",
     ("backward", "recompute", "attention")),
    (f"{STEP}/optimizer/add", ("optimizer",)),
    (f"{STEP}/average/convert_element_type", ("average",)),
    (f"{STEP}/sync/psum", ("average",)),
    (f"{STEP}/squeeze", ("unscoped",)),
    (f"{STEP}/attention/broadcast_in_dim", ("unscoped", "attention")),
    ("", ("unscoped",)),
    # a fusion of ops of several origins: the first name decides
    (f"{STEP}/optimizer/add;{BWD}/mul", ("optimizer",)),
    (f"{BWD}/mul;{STEP}/optimizer/add", ("backward",)),
    # names that only contain a scope's letters
    (f"{STEP}/jvp()/while/body/dot_general", ("unscoped",)),
    (f"{STEP}/transpose(jvp())/checkpoint/rematted_computation/mul",
     ("unscoped",)),
    (f"{STEP}/forwarding/attention_mask/mul", ("unscoped",)),
])
def test_scopes_of(op_name, want):
    assert S.scopes_of(op_name) == want


def _steps(*spans):
    return [span("bench.step", s, d) for s, d in spans]


def test_scope_time_leaf_ops_only_clipped_to_the_window():
    # window 0..20 ms; a loop container 1-15 holds the forward and
    # backward ops, whose time counts once, in their own scopes
    dev = [op(1, 14, FWD, "container", "while.1"),
           op(1, 3, f"{FWD}/attention/exp"),             # forward, attention
           op(4, 2, f"{FWD}/dot_general"),               # forward
           op(6, 4, f"{RECOMPUTE}/attention/exp"),       # backward, both
           op(10, 5, f"{BWD}/dot_general"),              # backward
           op(15, 1, f"{STEP}/optimizer/add"),
           op(16, 1, f"{STEP}/average/add"),
           op(17, 1, f"{STEP}/squeeze"),
           op(-2, 3, f"{FWD}/dot_general"),              # 1 ms in the window
           op(19, 4, f"{STEP}/optimizer/mul")]           # 1 ms in the window
    red = S.reduce({"devices": {"0": dev}, "host": _steps((0, 10), (10, 10))},
                   [0])
    ms = {k: v * 1e3 for k, v in red["scope_s"].items()}
    assert ms == pytest.approx({"forward": 6, "backward": 9, "recompute": 4,
                                "attention": 7, "optimizer": 2,
                                "average": 1, "unscoped": 1})
    assert red["leaf_s"] * 1e3 == pytest.approx(19)
    assert sum(red["scope_s"][k] for k in S.PHASES) == \
        pytest.approx(red["leaf_s"])
    assert red["scope_ops"]["forward"] == 3
    assert red["scope_ops"]["optimizer"] == 2


def test_an_op_without_a_name_takes_the_scope_before_it():
    # a copy the compiler added (no op_name) inside the backward, and one
    # before any named op
    dev = [op(0, 1, name="copy-done.1"), op(1, 2, f"{BWD}/attention/mul"),
           op(3, 1, name="copy-done.2"), op(4, 2, f"{STEP}/optimizer/add"),
           op(6, 1, name="broadcast.3")]
    red = S.reduce({"devices": {"0": dev}, "host": _steps((0, 10))}, [0])
    ms = {k: v * 1e3 for k, v in red["scope_s"].items()}
    assert ms == pytest.approx({"forward": 0, "backward": 3, "recompute": 0,
                                "attention": 3, "optimizer": 3,
                                "average": 0, "unscoped": 1})
    assert red["scope_ops"]["backward"] == 2


def test_scope_time_is_a_union_and_averaged_over_devices():
    # two overlapping backward ops on device 0 count 3 ms, not 4
    devs = {"0": [op(0, 2, f"{BWD}/a"), op(1, 2, f"{BWD}/b")],
            "1": [op(0, 1, f"{BWD}/a")]}
    red = S.reduce({"devices": devs, "host": _steps((0, 10))}, [0, 1])
    assert red["scope_s"]["backward"] * 1e3 == pytest.approx(2.0)
    assert red["leaf_s"] * 1e3 == pytest.approx(2.0)


def test_idle_inside_the_program_spans():
    # window 0..20 ms; device busy 0-2, 6-9 and 18-20: idle 2-6 and 9-18
    dev = [op(0, 2), op(6, 3), op(18, 2)]
    host = _steps((0, 20)) + [
        span("trainer.put_batch", 1, 2),            # idle 2-3 inside
        span("trainer.dispatch", 3, 2, variant="group:0", step=7),
        # nested in dispatch: counted once
        span("trainer.dispatch", 3.5, 1, variant="sync", step=8),
        # straddles the idle gap's end at 6 ms: idle 5-6 inside
        span("trainer.read_metrics", 5, 3, host_reads=3),
        # a read nested in the read span adds nothing
        span("np.asarray(jax.Array)", 5.5, 1),
        # idle 12-14 inside; 9-12 and 14-18 lie outside every program span
        span("trainer.read_metrics", 12, 2, host_reads=6),
        span("python_work", 14, 4)]
    red = S.reduce({"devices": {"0": dev}, "host": host}, [0])
    assert red["idle_in_s"]["launch"] * 1e3 == pytest.approx(3.0)
    assert red["idle_in_s"]["read"] * 1e3 == pytest.approx(3.0)
    assert red["span_s"]["launch"] * 1e3 == pytest.approx(4.0)
    assert red["span_s"]["read"] * 1e3 == pytest.approx(5.0)
    assert red["host_reads"] == [3, 6]


def test_intersect():
    assert S.intersect([[0, 2], [4, 8]], [[1, 5], [7, 9]]) == \
        [[1, 2], [4, 5], [7, 8]]
    assert S.intersect([[0, 2]], [[2, 3]]) == []


def _ctx(red, steps=2):
    return {"scopes": red, "trace": {"steps": steps}}


def test_metric_readers_on_reduced_scopes():
    dev = [op(0, 4, f"{FWD}/attention/exp"), op(4, 8, f"{RECOMPUTE}/mul"),
           op(12, 2, f"{STEP}/optimizer/add")]
    host = _steps((0, 10), (10, 10)) + [
        span("trainer.read_metrics", 14, 4, host_reads=3),
        span("trainer.dispatch", 18, 1, variant="sync", step=3),
        span("trainer.read_metrics", 19, 1, host_reads=6)]
    ctx = _ctx(S.reduce({"devices": {"0": dev}, "host": host}, [0]))
    read = lambda n: harness.metric_reader(n)(ctx)
    assert read("model.forward_ms") == pytest.approx(2.0)
    assert read("model.backward_ms") == pytest.approx(4.0)
    assert read("model.recompute_ms") == pytest.approx(4.0)
    assert read("model.attention_ms") == pytest.approx(2.0)
    assert read("step.optimizer_ms") == pytest.approx(1.0)
    assert read("driver.read_idle_ms") == pytest.approx(2.5)
    assert read("driver.launch_idle_ms") == pytest.approx(0.5)
    assert read("driver.reads_per_step") == pytest.approx(3.0)


def test_metric_readers_are_silent_without_the_program_names():
    """A program without the scopes, spans and counter reads nothing, and
    raises nothing."""
    dev = [op(0, 4, f"{STEP}/jvp()/dot_general"), op(4, 4, "")]
    ctx = _ctx(S.reduce({"devices": {"0": dev}, "host": _steps((0, 10))},
                        [0]), steps=1)
    for name in ("model.forward_ms", "model.backward_ms",
                 "model.recompute_ms", "model.attention_ms",
                 "step.optimizer_ms", "driver.read_idle_ms",
                 "driver.launch_idle_ms", "driver.reads_per_step"):
        assert harness.metric_reader(name)(ctx) is None, name


def test_op_names_reads_the_event_metadata(tmp_path):
    """``tf_op`` as a string and as a reference to a stat metadata's name;
    host planes and ops without the stat are left out."""
    space = S._xspace()()
    tpu = space.planes.add(name="/device:TPU:0")
    tpu.stat_metadata[1].name = S.OP_NAME_STAT
    tpu.stat_metadata[2].name = "hlo_category"
    tpu.stat_metadata[3].name = f"{BWD}/mul"
    md = tpu.event_metadata[10]
    md.name = "%fusion.1 = f32[8]{0} fusion()"
    md.stats.add(metadata_id=2, str_value="loop fusion")
    md.stats.add(metadata_id=1, str_value=f"{FWD}/dot_general")
    tpu.event_metadata[11].name = "%fusion.2 = f32[8]{0} fusion()"
    tpu.event_metadata[11].stats.add(metadata_id=1, ref_value=3)
    tpu.event_metadata[12].name = "%copy.3 = f32[8]{0} copy()"
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata[1].name = S.OP_NAME_STAT
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert S.op_names(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]{0} fusion()": f"{FWD}/dot_general",
        "%fusion.2 = f32[8]{0} fusion()": f"{BWD}/mul"}}


def test_clock_shift_moves_an_early_device_after_its_dispatch():
    host = [span("trainer.dispatch", 1, 1), span("trainer.dispatch", 11, 1)]
    runs = lambda *starts: [[int(s * MS), 5 * MS] for s in starts]
    # the second run reads 0.5 ms before its dispatch began
    assert S.clock_shift(host, runs(1.2, 10.5)) == int(0.5 * MS)
    # a device that reads no earlier than its dispatch stays
    assert S.clock_shift(host, runs(1.3, 11.4)) == 0
    # runs and spans that do not pair up one to one: no shift
    assert S.clock_shift(host, runs(0.5)) == 0
    assert S.clock_shift(host, []) == 0


def test_idle_is_attributed_after_the_clock_shift():
    # the device reads 1 ms early: its step seems to start at 2 ms, before
    # the dispatch span (3-4 ms) that launched it
    dev = [op(2, 5, f"{FWD}/dot_general")]
    host = _steps((0, 10)) + [
        span("trainer.put_batch", 1, 2),
        span("trainer.dispatch", 3, 1, variant="group:0", step=1),
        span("trainer.read_metrics", 4, 6, host_reads=0)]
    tr = {"devices": {"0": dev}, "host": host,
          "modules": {"0": [[2 * MS, 5 * MS]]}}
    red = S.reduce(tr, [0])
    assert red["clock_shift_s"] * 1e3 == pytest.approx(1.0)
    # shifted to 3-8 ms: idle 0-3 (1-3 in put_batch) and 8-10 (in the read)
    assert red["idle_s"] * 1e3 == pytest.approx(5.0)
    assert red["idle_in_s"]["launch"] * 1e3 == pytest.approx(2.0)
    assert red["idle_in_s"]["read"] * 1e3 == pytest.approx(2.0)
    assert red["scope_s"]["forward"] * 1e3 == pytest.approx(5.0)


def _recorded(name):
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        name)
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_scoped_trace():
    """Three steps of ``transformer-wmt.train.1chip`` with the program's
    scopes, spans and read counter, traced on one TPU v5 lite (device ops
    of the "XLA Ops" line with their ``op_name``, program runs of the "XLA
    Modules" line, the stepping thread's host spans of at least 20 us and
    the program's), reduced to known numbers."""
    from bench import trace as T
    tr = _recorded("wmt-1chip-3steps-scoped.json.gz")
    assert len(tr["devices"]["0"]) == 12084
    lo, hi, steps = T.window([h[:3] for h in tr["host"]])
    assert steps == 3
    red = S.reduce(tr, [0])
    # the scopes and the unscoped rest make up the leaf ops' time
    assert sum(red["scope_s"][k] for k in S.PHASES) == \
        pytest.approx(red["leaf_s"], rel=1e-9)
    assert red["scope_s"]["unscoped"] <= 0.05 * red["leaf_s"]
    assert red["scope_s"] == pytest.approx({
        "forward": 0.015309015, "backward": 0.033238247,
        "recompute": 0.008467572, "attention": 0.026263578,
        "optimizer": 0.007077698, "average": 0.0, "unscoped": 0.000125825})
    # three blocking reads in each step
    assert red["host_reads"] == [18, 21, 24]
    # the device clock read 0.88 ms early: its first op of the window began
    # before the first dispatch did; shifted, every op of the window lies
    # between the first dispatch's start and the last read's end
    shift = int(round(red["clock_shift_s"] * 1e9))
    assert shift == 882146
    dispatch = min(s for n, s, _, _ in tr["host"] if n == S.DISPATCH_SPAN)
    read_end = max(s + d for n, s, d, _ in tr["host"] if n in S.READ_SPANS)
    ops = [(s, s + d) for _, s, d, _, _ in tr["devices"]["0"]
           if s + shift < hi and s + d + shift > lo]
    assert min(s for s, _ in ops) < dispatch
    assert all(dispatch <= s + shift and e + shift <= read_end
               for s, e in ops)
    # the program's spans hold nearly all of the device's idle time
    assert sum(red["idle_in_s"].values()) >= 0.8 * red["idle_s"]
    assert red["idle_in_s"] == pytest.approx({"read": 0.007642648,
                                              "launch": 0.001367734})
