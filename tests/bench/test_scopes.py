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


# the open segments of the paths above, after the phase and its parts
LOOP = ("train_step", "while", "body", "closed_call")


@pytest.mark.parametrize("op_name,want", [
    (f"{FWD}/dot_general:", ("forward",) + LOOP),
    (f"{FWD}/attention/exp", ("forward", "attention") + LOOP),
    (f"{STEP}/forward/attention/exp", ("forward", "attention", "train_step")),
    (f"{BWD}/dot_general", ("backward",) + LOOP + ("checkpoint",)),
    (f"{BWD}/attention/mul",
     ("backward", "attention") + LOOP + ("checkpoint",)),
    (f"{RECOMPUTE}/add", ("backward", "recompute") + LOOP
     + ("checkpoint", "rematted_computation")),
    (f"{RECOMPUTE}/attention/while/body/exp",
     ("backward", "recompute", "attention") + LOOP
     + ("checkpoint", "rematted_computation")),
    (f"{STEP}/optimizer/add", ("optimizer", "train_step")),
    (f"{STEP}/average/convert_element_type", ("average", "train_step")),
    (f"{STEP}/sync/psum", ("average", "train_step", "sync")),
    (f"{STEP}/squeeze", ("unscoped", "train_step")),
    (f"{STEP}/attention/broadcast_in_dim",
     ("unscoped", "attention", "train_step")),
    ("", ("unscoped",)),
    # a fusion of ops of several origins: the first name decides
    (f"{STEP}/optimizer/add;{BWD}/mul", ("optimizer", "train_step")),
    (f"{BWD}/mul;{STEP}/optimizer/add",
     ("backward",) + LOOP + ("checkpoint",)),
    # names that only contain a scope's letters
    (f"{STEP}/jvp()/while/body/dot_general",
     ("unscoped", "train_step", "while", "body")),
    (f"{STEP}/transpose(jvp())/checkpoint/rematted_computation/mul",
     ("unscoped", "train_step", "checkpoint", "rematted_computation")),
    (f"{STEP}/forwarding/attention_mask/mul",
     ("unscoped", "train_step", "forwarding", "attention_mask")),
    # scopes a program adds, under transforms as the fixed ones are
    (f"{STEP}/transpose(jvp(forward))/moe/experts/dot_general",
     ("backward", "train_step", "moe", "experts")),
    (f"{FWD}/attention/attention_kernel/shard_map/pallas_call",
     ("forward", "attention") + LOOP + ("attention_kernel", "shard_map")),
])
def test_scopes_of(op_name, want):
    assert S.scopes_of(op_name) == want


def _steps(*spans):
    return [span("bench.step", s, d) for s, d in spans]


def _ctx(tr, steps=2, ids=(0,)):
    """A metric reader's context over the plain-form trace ``tr`` of the
    devices ``ids``, ``steps`` steps long."""
    return {"scope_trace": tr, "scopes": S.reduce(tr, list(ids)),
            "trace": {"steps": steps}}


def _fixed(ctx, key="s"):
    """``by_name``'s ``key`` (seconds or ops) of each scope of ``SCOPES``."""
    return {k: S.by_name(ctx, k)[key] for k in S.SCOPES}


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
    ctx = _ctx({"devices": {"0": dev}, "host": _steps((0, 10), (10, 10))})
    red, got = ctx["scopes"], _fixed(ctx)
    ms = {k: v * 1e3 for k, v in got.items()}
    assert ms == pytest.approx({"forward": 6, "backward": 9, "recompute": 4,
                                "attention": 7, "optimizer": 2,
                                "average": 1, "unscoped": 1})
    assert red["leaf_s"] * 1e3 == pytest.approx(19)
    assert sum(got[k] for k in S.PHASES) == pytest.approx(red["leaf_s"])
    assert S.by_name(ctx, "forward")["ops"] == 3
    assert S.by_name(ctx, "optimizer")["ops"] == 2


def test_an_op_without_a_name_takes_the_scope_before_it():
    # a copy the compiler added (no op_name) inside the backward, and one
    # before any named op
    dev = [op(0, 1, name="copy-done.1"), op(1, 2, f"{BWD}/attention/mul"),
           op(3, 1, name="copy-done.2"), op(4, 2, f"{STEP}/optimizer/add"),
           op(6, 1, name="broadcast.3")]
    ctx = _ctx({"devices": {"0": dev}, "host": _steps((0, 10))})
    ms = {k: v * 1e3 for k, v in _fixed(ctx).items()}
    assert ms == pytest.approx({"forward": 0, "backward": 3, "recompute": 0,
                                "attention": 3, "optimizer": 3,
                                "average": 0, "unscoped": 1})
    assert S.by_name(ctx, "backward")["ops"] == 2


def test_scope_time_is_a_union_and_averaged_over_devices():
    # two overlapping backward ops on device 0 count 3 ms, not 4
    devs = {"0": [op(0, 2, f"{BWD}/a"), op(1, 2, f"{BWD}/b")],
            "1": [op(0, 1, f"{BWD}/a")]}
    ctx = _ctx({"devices": devs, "host": _steps((0, 10))}, ids=(0, 1))
    assert S.by_name(ctx, "backward")["s"] * 1e3 == pytest.approx(2.0)
    assert ctx["scopes"]["leaf_s"] * 1e3 == pytest.approx(2.0)


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
    assert S.stamps(host, *red["window_ns"], "trainer.read_metrics",
                    "host_reads") == [3, 6]


def test_intersect():
    assert S.intersect([[0, 2], [4, 8]], [[1, 5], [7, 9]]) == \
        [[1, 2], [4, 5], [7, 8]]
    assert S.intersect([[0, 2]], [[2, 3]]) == []


def test_metric_readers_on_reduced_scopes():
    dev = [op(0, 4, f"{FWD}/attention/exp"), op(4, 8, f"{RECOMPUTE}/mul"),
           op(12, 2, f"{STEP}/optimizer/add")]
    host = _steps((0, 10), (10, 10)) + [
        span("trainer.read_metrics", 14, 4, host_reads=3),
        span("trainer.dispatch", 18, 1, variant="sync", step=3),
        span("trainer.read_metrics", 19, 1, host_reads=6)]
    ctx = _ctx({"devices": {"0": dev}, "host": host})
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
    ctx = _ctx({"devices": {"0": dev}, "host": _steps((0, 10))}, steps=1)
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
    ctx = _ctx(tr)
    red = ctx["scopes"]
    assert red["clock_shift_s"] * 1e3 == pytest.approx(1.0)
    # shifted to 3-8 ms: idle 0-3 (1-3 in put_batch) and 8-10 (in the read)
    assert red["idle_s"] * 1e3 == pytest.approx(5.0)
    assert red["idle_in_s"]["launch"] * 1e3 == pytest.approx(2.0)
    assert red["idle_in_s"]["read"] * 1e3 == pytest.approx(2.0)
    assert S.by_name(ctx, "forward")["s"] * 1e3 == pytest.approx(5.0)


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
    ctx = _ctx(tr, steps)
    red, got = ctx["scopes"], _fixed(ctx)
    # the scopes and the unscoped rest make up the leaf ops' time
    assert sum(got[k] for k in S.PHASES) == \
        pytest.approx(red["leaf_s"], rel=1e-9)
    assert got["unscoped"] <= 0.05 * red["leaf_s"]
    assert got == pytest.approx({
        "forward": 0.015309015, "backward": 0.033238247,
        "recompute": 0.008467572, "attention": 0.026263578,
        "optimizer": 0.007077698, "average": 0.0, "unscoped": 0.000125825})
    # three blocking reads in each step
    assert S.stamps(tr["host"], *red["window_ns"], "trainer.read_metrics",
                    "host_reads") == [18, 21, 24]
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


def test_recorded_scoped_trace_reads_as_pinned():
    """The seven scopes and the read counter on the recorded trace, to the
    last bit, as the reduction read them before scopes were opened: each
    scope reduced by name on demand, as every metric reads it."""
    from bench import trace as T
    tr = _recorded("wmt-1chip-3steps-scoped.json.gz")
    plain = {"devices": {k: [o[:4] for o in v]
                         for k, v in tr["devices"].items()},
             "host": [h[:3] for h in tr["host"]]}
    ctx = {"scope_trace": tr, "trace": T.reduce(plain, [0])}
    red = S.readings(ctx)
    assert _fixed(ctx) == {
        "forward": 0.015309015, "backward": 0.033238247,
        "optimizer": 0.007077698, "average": 0.0, "unscoped": 0.000125825,
        "recompute": 0.008467572, "attention": 0.026263578}
    assert red["leaf_s"] == 0.055750785
    assert _fixed(ctx, "ops") == {
        "forward": 1713, "backward": 8358, "optimizer": 1836, "average": 0,
        "unscoped": 87, "recompute": 1512, "attention": 3915}
    assert red["idle_in_s"] == {"read": 0.007642648, "launch": 0.001367734}
    assert red["idle_s"] == 0.009136412
    assert red["clock_shift_s"] == 0.000882146
    read = lambda n: harness.metric_reader(n)(ctx)
    assert read("model.forward_ms") == 5.1030050000000005
    assert read("model.backward_ms") == 11.079415666666668
    assert read("model.recompute_ms") == 2.8225239999999996
    assert read("model.attention_ms") == 8.754526
    assert read("step.optimizer_ms") == 2.3592326666666668
    assert read("driver.read_idle_ms") == 2.5475493333333334
    assert read("driver.launch_idle_ms") == 0.45591133333333334
    assert read("driver.reads_per_step") == 3.0
    # open names of the same trace: the layer scans' checkpointed bodies,
    # and the one-chip cell's absent exchange
    assert 0 < S.scope_ms(ctx, "checkpoint") < read("model.backward_ms")
    assert S.scope_ms(ctx, "sync") is None
    assert read("averaging.exposed_ms") is None


def _open_trace():
    """Window 0..20 ms, two steps: a ``moe`` scope the fixed ones do not
    know, and an exchange whose ops an optimiser op overlaps."""
    moe = f"{STEP}/jvp(forward)/moe/experts"
    dev = [op(0, 3, f"{moe}/dot_general", name="fusion.1"),
           op(3, 1, name="copy.2"),                        # takes moe's name
           op(4, 2, f"{BWD}/moe/dot_general", name="fusion.3"),
           op(6, 4, f"{STEP}/average/psum", "collective", "all-reduce.4"),
           op(8, 1, f"{STEP}/optimizer/add", name="fusion.5"),
           op(12, 2, f"{STEP}/sync/psum", "collective", "all-reduce.6"),
           op(15, 1, f"{moe}/custom", name="gmm_kernel.7"),
           op(17, 1, f"{moe}/custom", name="gmm_kernel.8")]
    host = _steps((0, 10), (10, 10)) + [
        span("trainer.read_metrics", 9, 1, host_reads=3, moe_tokens=100),
        span("trainer.read_metrics", 19, 1, host_reads=6, moe_tokens=400)]
    return {"devices": {"0": dev}, "host": host}


def test_an_open_scope_reduces_on_demand_and_is_kept():
    ctx = _ctx(_open_trace())
    # moe: 0-4 (the copy inherits), 4-6 in the backward, 15-16 and 17-18
    assert S.scope_ms(ctx, "moe") == pytest.approx(8 / 2)
    assert S.scope_ms(ctx, "experts") == pytest.approx(6 / 2)
    assert S.by_name(ctx, "moe")["ops"] == 5
    assert "moe" in ctx["scopes"]["by_name"]
    assert S.scope_ms(ctx, "no_such_scope") is None


def test_averaging_time_is_the_average_and_sync_scopes_device_time():
    ctx = _ctx(_open_trace())
    # average (average and sync): 6-10 and 12-14, the optimiser op at 8-9
    # beside it included
    assert S.scope_ms(ctx, "average") == pytest.approx(6 / 2)
    assert harness.metric_reader("averaging.exposed_ms")(ctx) == \
        pytest.approx(6 / 2)
    assert S.scope_ms(ctx, "sync") == pytest.approx(2 / 2)


def test_calls_per_step_count_a_kernels_ops_in_the_window():
    ctx = _ctx(_open_trace())
    assert S.calls_per_step(ctx, r"gmm_kernel") == pytest.approx(1.0)
    assert S.calls_per_step(ctx, r"all-reduce") == pytest.approx(1.0)
    assert S.calls_per_step(ctx, r"kernel") == 0
    # only the calls under a scope, where one is named
    assert S.calls_per_step(ctx, r"gmm_kernel", "moe") == pytest.approx(1.0)
    assert S.calls_per_step(ctx, r"gmm_kernel", "average") == 0


def test_a_counter_stamped_on_a_host_span_reads_its_rise_per_step():
    ctx = _ctx(_open_trace())
    assert S.stat_rise_per_step(ctx, "trainer.read_metrics",
                                "moe_tokens") == pytest.approx(300)
    assert S.stat_rise_per_step(ctx, "trainer.read_metrics",
                                "host_reads") == pytest.approx(3)
    assert S.stat_rise_per_step(ctx, "trainer.dispatch", "step") is None


def test_attention_kernel_roofline_over_the_kernel_scope():
    """Two steps, each with one forward, dq and dk/dv call of qwen3's
    splash kernels at 4 x 2048 tokens, under ``attention_kernel`` with a
    scaling op beside them: the share is their roofline time over the
    scope's device time."""
    from bench import yardstick as Y
    r = harness.resolve(harness.load_manifest(), "qwen3-0.6b.train-2k.1chip")
    sc = f"{FWD}/attention/attention_kernel"
    dev = []
    for t0 in (0, 10):
        dev += [op(t0, 0.5, f"{sc}/mul", name="fusion.1"),
                op(t0 + 1, 1, f"{sc}/pallas_call",
                   name="splash_mqa_fwd_residuals.2"),
                op(t0 + 2, 1, f"{sc}/pallas_call",
                   name="splash_mqa_dq_no_residuals.3"),
                op(t0 + 3, 2, f"{sc}/pallas_call",
                   name="splash_mqa_dkv_no_residuals.4"),
                # a call outside the scope is not in the share's time, and
                # its work does not count either
                op(t0 + 6, 1, f"{FWD}/pallas_call",
                   name="splash_mqa_fwd_residuals.5")]
    ctx = _ctx({"devices": {"0": dev}, "host": _steps((0, 10), (10, 10))})
    ctx.update(spec=r["spec"], traffic=r["traffic"], device_kind="TPU v5 lite")
    p = Y.peaks("TPU v5 lite")
    # a forward pass (the fwd kernel) and a backward pass (dq and dk/dv)
    want = sum(Y.roofline_s(*Y.attention_pass(k, 4, 2048, 16, 8, 128), p)
               for k in ("fwd", "bwd"))
    share = harness.metric_reader("attention.kernel_roofline")(ctx)
    assert share == pytest.approx(100 * want / 4.5e-3)
    # a program without the kernels reads nothing, not 0
    quiet = _ctx({"devices": {"0": [op(0, 1, f"{FWD}/dot_general")]},
                  "host": _steps((0, 10))}, steps=1)
    quiet.update(spec=r["spec"], traffic=r["traffic"],
                 device_kind="TPU v5 lite")
    assert harness.metric_reader("attention.kernel_roofline")(quiet) is None
