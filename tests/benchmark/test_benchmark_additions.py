"""BENCHMARK.json takes additions (PR 45): everything the tests of this
directory hold of the real file (each test module's `HELD`: functions of a
path, which its own tests call on the real file) holds of a copy to which a
later PR's addition is appended as the driver takes one: a configuration, a
one-chip cell under an unedited mix and a per-layer entry, each LAST in its
list, and the cell last in the lists that name every cell of its kind. A
test that pins an entry, a cell or a configuration by its place (`[-1]`, "the
last of", "in no other list") fails here before it stops a PR.

Listed in BENCHMARK.json's `paths`. No device or topology call at import
time.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REAL = os.path.join(ROOT, "BENCHMARK.json")
MODULES = ("test_benchmark", "test_benchmark_lm", "test_benchmark_lm_sparse",
           "test_benchmark_lm_window", "test_benchmark_lm_linear",
           "test_device_scopes", "test_span_metrics")
LAYER = "model: models/lm (frozen base, latent attention, expert layer)"


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run():
    return _load("_hefl_bench_run_additions",
                 os.path.join(ROOT, "benchmarks", "run.py"))


def with_an_addition(bench: dict) -> dict:
    """`bench` with one more configuration (a token model's: the file of the
    tests' linear-attention cell, whose `check` is `lm_linear_subset`), one
    more one-chip cell of it under `sync_s8k` and one more per-layer entry
    (reader: `layer_metrics/appended_last.py` beside this file), each
    appended last, and the cell appended to the lists of `sgd_dev_s` (every
    cell) and `attention_dev_s` (every token cell)."""
    out = copy.deepcopy(bench)
    out["configs"].append({
        "name": "appended-l1", "source": "tests only: a later PR's model",
        "file": "tests/benchmark/tiny_lm_linear/configs/tiny-lm-linear.json",
        "reduced": [], "why": "what a model_config PR appends"})
    out["workloads"].append({
        "name": "appended.sync_s8k", "config": "appended-l1",
        "traffic": "sync_s8k", "chips": 1,
        "why": "what a model_config PR appends: one cell under the unedited mix"})
    out["per_layer"].append({
        "name": "appended_last", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": LAYER, "moves": "round_s",
        "workloads": ["appended.sync_s8k"]})
    for m in out["per_layer"]:
        if m["name"] in ("sgd_dev_s", "attention_dev_s"):
            m["workloads"].append("appended.sync_s8k")
    return out


@pytest.mark.parametrize("which", ["the_real_file", "a_copy_with_an_addition"])
def test_what_the_tests_hold_of_the_file_survives_an_addition(
        run, tmp_path, which):
    path = REAL
    if which == "a_copy_with_an_addition":
        with open(REAL) as f:
            added = with_an_addition(json.load(f))
        path = str(tmp_path / "BENCHMARK.json")
        with open(path, "w") as f:
            json.dump(added, f, indent=1)
        assert run.load_cell(path, "appended.sync_s8k")["per_layer"][-1][
            "name"] == "appended_last"
    held = 0
    for name in MODULES:
        module = _load("_held_" + name, os.path.join(HERE, name + ".py"))
        for holds in module.HELD:
            holds(run, path)
            held += 1
    assert held == 11
