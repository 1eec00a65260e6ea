"""BENCHMARK.json resolves by name, keeps to the contract's alphabet, and
the traffic generator is deterministic per seed."""

import json
import os
import re

import numpy as np
import pytest

import _paths  # noqa: F401
from _paths import ROOT
from bench import harness
from bench.traffic import generator

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = harness.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_manifest_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.isfile(os.path.join(ROOT, MANIFEST["command"][1]))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    r = harness.resolve(MANIFEST, cell)
    assert r["spec"]["name"] == r["cell"]["config"]
    assert r["traffic"]["workers"] == r["cell"]["chips"]
    assert harness.family_module(r["spec"]).shapes(r["spec"])
    assert os.path.isfile(os.path.join(ROOT, "bench", "limits",
                                       f"{cell}.json"))
    for m in r["end_to_end"] + r["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    names = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert r["per_layer"]


def test_pairs_and_names_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    for group in (MANIFEST["configs"], MANIFEST["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("entry", MANIFEST["configs"] + MANIFEST["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_and_units_use_the_allowed_characters(entry):
    assert NAME.match(entry["name"])
    for k in ("config", "traffic"):
        if k in entry:
            assert NAME.match(entry[k])
    for k in entry.get("reduced", []):
        assert NAME.match(k)
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for k in ("why", "layer", "source"):
        if k in entry:
            assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k] \
                and "\t" not in entry[k]


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert metric["moves"] in e2e
    moved = e2e[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"]


@pytest.mark.parametrize("traffic", sorted(
    {w["traffic"] for w in MANIFEST["workloads"]}))
def test_traffic_is_deterministic_per_seed(traffic):
    mix = dict(generator.load(traffic), distinct_batches=3)
    seed = 2**31 + 17
    a = generator.batches(mix, 1000, seed)
    b = generator.batches(mix, 1000, seed)
    c = generator.batches(mix, 1000, seed + 1)
    assert len(a) == 3
    for x, y, z in zip(a, b, c):
        assert set(x) == set(y) == set(z)
        for k in x:
            assert x[k].shape == z[k].shape and x[k].dtype == np.int32
            np.testing.assert_array_equal(x[k], y[k])
        assert not np.array_equal(x["tokens"], z["tokens"])
        np.testing.assert_array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    rows = np.concatenate([x["tokens"] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_teacher_follows_the_permutation():
    rng = np.random.default_rng(0)
    perm = rng.permutation(50)
    toks = generator.teacher_rows(rng, perm, 64, 40, 1.0, 50)
    np.testing.assert_array_equal(toks[:, 1:], perm[toks[:, :-1]])


def test_limits_files_hold_every_number():
    from bench import correct
    for cell in CELLS:
        with open(os.path.join(ROOT, "bench", "limits", f"{cell}.json")) as f:
            lim = json.load(f)["limits"]
        assert set(lim) == set(correct.NUMBERS)


def test_cell_files_name_cells_and_averagers_the_reference_follows():
    from bench import correct
    folder = os.path.join(ROOT, "bench", "cells")
    for name in sorted(os.listdir(folder)):
        cell = name[:-len(".json")]
        assert name.endswith(".json") and cell in CELLS
        over = harness.cell_overrides(cell)
        assert set(over) <= {"train", "why"}
        assert set(over["train"]) <= {"averager", "group_size", "tau"}
        train = harness.resolve(MANIFEST, cell)["train"]
        assert train["averager"] in correct.AVERAGERS
    assert harness.cell_overrides("qwen3-0.6b.train-2k.1chip") == \
        {"train": {}}
    assert harness.resolve(MANIFEST, "transformer-wmt.allreduce.4chip")[
        "train"]["averager"] == "allreduce"
