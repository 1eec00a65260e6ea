"""A new model family, traffic mix, averager for a cell, named scope and
host-span counter plug into the benchmark as new files and manifest entries
alone: in a copy of the benchmark with only such additions, a cell of them
resolves, builds and runs traced at a tiny size on two CPU devices."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

import _paths  # noqa: F401
from _paths import ROOT
from bench import harness

CELL = "plainlm.allreduce.2chip"

FAMILY = '''\
"""A family added as a file of its own: the dense decoder's parameter tree
and loss under a new name, with a FLOP count of its own."""

from bench.reference.decoder import loss_and_grad, shapes  # noqa: F401

TINY = {
    "spec": dict(hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                 num_attention_heads=2, num_key_value_heads=1, head_dim=16,
                 vocab_size=256, embedding_rows=256),
    "program": dict(d_model=32, d_ff=64, n_layers=2, n_heads=2,
                    n_kv_heads=1, head_dim=16, vocab=256),
    "traffic": dict(seq_len=16, batch_per_worker=2, distinct_batches=4),
}


def fwd_flops(spec, traffic):
    """2 FLOPs per weight of the matrices a token passes, every token."""
    d, ff, v = spec["hidden_size"], spec["intermediate_size"], \\
        spec["vocab_size"]
    hd, h, kh = spec["head_dim"], spec["num_attention_heads"], \\
        spec["num_key_value_heads"]
    per_layer = d * hd * (2 * h + 2 * kh) + 3 * d * ff
    tokens = traffic["workers"] * traffic["batch_per_worker"] * \\
        traffic["seq_len"]
    return 2.0 * tokens * (spec["num_hidden_layers"] * per_layer + d * v)
'''

SCOPE_METRIC = '''\
"""Device time per step under a ``moe`` named scope."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "moe")
'''

COUNTER_METRIC = '''\
"""The program's ``host_reads`` counter, stamped on each
``trainer.read_metrics`` span, per step."""

from bench import scopes


def read(ctx):
    return scopes.stat_rise_per_step(ctx, "trainer.read_metrics",
                                     "host_reads")
'''

RUN = '''\
import json, sys, time
import _paths  # noqa: F401
from tiny import tiny_cell
from bench import harness, yardstick
# a made-up peak for the CPU test device, so that step.mfu divides by it
yardstick.PEAKS["cpu"] = yardstick.Peaks(1e12, 1e11, 1e10, 2**30)
cell = sys.argv[1]
res = harness.run(cell, 2**31 + 5, 0.2, True, time.perf_counter(),
                  require_accelerator=False, cell=tiny_cell(cell))
print(json.dumps(res))
'''


def _copy_benchmark(dst):
    """``BENCHMARK.json`` and the files under its paths, as a checkout
    holds them, without the recorded traces."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    ignore = shutil.ignore_patterns("__pycache__", "data", ".bench_cache")
    for p in harness.load_manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), os.path.join(dst, p),
                        ignore=ignore)


def _snapshot(root):
    out = {}
    for p in harness.load_manifest()["paths"]:
        for d, dirs, files in os.walk(os.path.join(root, p)):
            dirs[:] = [x for x in dirs if x not in ("__pycache__", "data",
                                                    ".bench_cache")]
            for f in files:
                path = os.path.join(d, f)
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, root)] = fh.read()
    return out


def _add_files(root):
    """The new cell's files and manifest entries; nothing else changes."""
    def write(rel, text):
        path = os.path.join(root, rel)
        assert not os.path.exists(path), rel
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)

    with open(os.path.join(ROOT, "bench", "configs", "qwen3-0.6b.json")) as f:
        spec = json.load(f)
    spec.update(name="plainlm", family="plainlm", reference="plainlm")
    write("bench/reference/plainlm.py", FAMILY)
    write("bench/configs/plainlm.json", json.dumps(spec))
    write("bench/traffic/lm-16x2x2.json", json.dumps(
        {"kind": "teacher_lm", "workers": 2, "batch_per_worker": 2,
         "seq_len": 16, "source_len": 0, "order_mix": 0.75,
         "distinct_batches": 4, "why": "two workers, tiny rows"}))
    write(f"bench/cells/{CELL}.json",
          json.dumps({"train": {"averager": "allreduce"}}))
    with open(os.path.join(ROOT, "bench", "limits",
                           "qwen3-0.6b.train-2k.1chip.json")) as f:
        write(f"bench/limits/{CELL}.json", f.read())
    write("bench/metrics/plainlm.moe_ms.py", SCOPE_METRIC)
    write("bench/metrics/plainlm.reads_per_step.py", COUNTER_METRIC)

    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "plainlm", "source": "https://huggingface.co/Qwen/Qwen3-0.6B",
         "file": "bench/configs/plainlm.json", "reduced": [],
         "why": "a family of its own"})
    manifest["workloads"].append(
        {"name": CELL, "config": "plainlm", "traffic": "lm-16x2x2",
         "chips": 2, "why": "two workers under allreduce"})
    for name, unit, source in (("plainlm.moe_ms", "ms", "device_trace"),
                               ("plainlm.reads_per_step", "reads",
                                "program_counter")):
        manifest["per_layer"].append(
            {"name": name, "unit": unit, "better": "lower", "source": source,
             "layer": "model (models/)", "moves": "tokens_per_s_per_chip",
             "workloads": [CELL]})
    with open(path, "w") as f:
        json.dump(manifest, f)


@pytest.fixture(scope="module")
def plugged(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    _copy_benchmark(root)
    before = _snapshot(root)
    _add_files(root)
    after = _snapshot(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax_cache")),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    here = os.path.join(root, "tests", "bench")
    p = subprocess.run([sys.executable, "-c", RUN, CELL], cwd=here, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return {"root": root, "before": before, "after": after,
            "result": json.loads(p.stdout.splitlines()[-1])}


def test_the_new_cell_adds_files_and_edits_none(plugged):
    before, after = plugged["before"], plugged["after"]
    assert all(after[k] == v for k, v in before.items())
    assert sorted(set(after) - set(before)) == sorted([
        "bench/reference/plainlm.py", "bench/configs/plainlm.json",
        "bench/traffic/lm-16x2x2.json", f"bench/cells/{CELL}.json",
        f"bench/limits/{CELL}.json", "bench/metrics/plainlm.moe_ms.py",
        "bench/metrics/plainlm.reads_per_step.py"])


def test_the_new_cell_runs_correct_and_reads_its_metrics(plugged):
    res = plugged["result"]
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    assert res["device"]["count"] == 2
    m = res["metrics"]
    # the whole step's share of the peak, from the family's FLOP count
    assert m["step.mfu"]["value"] > 0
    # the counter stamped on a host span: three blocking reads a step
    assert m["plainlm.reads_per_step"]["value"] == 3.0
    # a CPU trace holds no device ops: the scope metric reads nothing and
    # is left out of the line, as every device-time metric is
    assert "plainlm.moe_ms" not in m and "model.forward_ms" not in m


def test_the_new_scope_metric_reads_a_device_trace(plugged):
    """The copy's scope metric over a device trace in which the program
    names a ``moe`` scope."""
    import importlib.util
    from bench import scopes as S
    path = os.path.join(plugged["root"], "bench", "metrics",
                        "plainlm.moe_ms.py")
    spec = importlib.util.spec_from_file_location("plainlm_moe_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ms = 1_000_000
    moe = "jit(group_step)/transpose(jvp(forward))/moe/dot_general"
    tr = {"devices": {"0": [["fusion.1", 1 * ms, 3 * ms, "compute", moe],
                            ["fusion.2", 5 * ms, 1 * ms, "compute",
                             "jit(group_step)/optimizer/add"]]},
          "host": [["bench.step", 0, 10 * ms, {}]]}
    ctx = {"scope_trace": tr, "scopes": S.reduce(tr, [0]),
           "trace": {"steps": 1}}
    assert mod.read(ctx) == pytest.approx(3.0)


def test_the_family_module_is_the_one_the_copy_resolves(plugged):
    code = textwrap.dedent('''\
        import json, _paths
        from bench import harness, yardstick
        r = harness.resolve(harness.load_manifest(), "%s")
        print(json.dumps([harness.family_module(r["spec"]).__file__,
                          r["train"]["averager"],
                          yardstick.train_flops_per_step(r["spec"],
                                                         r["traffic"])]))
        ''' % CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code],
                       cwd=os.path.join(plugged["root"], "tests", "bench"),
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    path, averager, flops = json.loads(p.stdout.splitlines()[-1])
    assert path.startswith(plugged["root"]) and averager == "allreduce"
    # 2 workers x 2 rows x 16 tokens over qwen3's published sizes
    d, ff, hd, v = 1024, 3072, 128, 151936
    per_layer = d * hd * (2 * 16 + 2 * 8) + 3 * d * ff
    assert flops == 3 * 2.0 * 64 * (28 * per_layer + d * v)
