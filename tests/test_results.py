"""Unit tests for the evidence loaders/renderers in results.py.

These are pure-host functions (no backend): seed-sweep loading,
platform-pinned accuracy runs, rolling partials with (seed, platform)
suppression, and offline markdown rendering are what the committed
evidence rests on, so their filtering rules get pinned here.
"""

import importlib.util
import json
import os
import sys

import pytest

_spec = importlib.util.spec_from_file_location(
    "results", os.path.join(os.path.dirname(__file__), "..", "results.py")
)
results = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(results)


@pytest.fixture()
def artifact_dir(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)

    def write(name, rec):
        with open(tmp_path / name, "w") as f:
            f.write(json.dumps(rec) + "\n")

    return write


def test_seed_runs_exclude_smoke_and_pinned(artifact_dir):
    artifact_dir("seeds_0.json", {"seed": 0, "device": "TPU v5 lite"})
    artifact_dir("seeds_1.json", {"seed": 1, "smoke": True})
    artifact_dir("seeds_2.json", {"seed": 2, "platform_pinned": "cpu"})
    runs = results.load_seed_runs()
    assert [r["seed"] for r in runs] == [0]
    # ...and the pinned loader picks up exactly the pinned one
    assert [r["seed"] for r in results.load_pinned_runs()] == [2]


def test_flagship_runs_fold_into_markdown(artifact_dir):
    # flagship_acc.py artifacts (indent-formatted JSON, unlike the
    # one-line bench outputs) surface in their own RESULTS.md section;
    # smoke shakeouts stay out.
    with open("flagship_acc_0.json", "w") as f:
        json.dump(
            {"task": "flagship_accuracy", "seed": 0, "device": "cpu",
             "local_epochs": 10, "accuracy": 0.9, "precision": 0.9,
             "recall": 0.9, "f1": 0.9, "acc_vs_reference": 0.06,
             "wallclock_s_total": 123.0},
            f, indent=2,
        )
    with open("flagship_acc_smoke_0.json", "w") as f:
        json.dump({"task": "flagship_accuracy", "smoke": True, "seed": 0}, f)
    runs = results.load_flagship_runs()
    assert [r["_seed_file"] for r in runs] == ["flagship_acc_0.json"]
    md = results.write_markdown({"presets": [], "convergence": []})
    assert "Flagship accuracy" in md and "flagship_acc_0.json" in md
    assert "flagship_acc_smoke_0" not in md


def test_corrupt_artifact_is_skipped(artifact_dir, tmp_path):
    artifact_dir("seeds_0.json", {"seed": 0})
    (tmp_path / "seeds_1.json").write_text("{truncated")
    assert [r["seed"] for r in results.load_seed_runs()] == [0]


def test_partial_suppressed_by_same_platform_complete_only(artifact_dir):
    # CPU-pinned complete run must NOT hide the rescued TPU partial of the
    # same seed (the r4 review finding): they key on different pins.
    artifact_dir(
        "acc_cpu_seed0.json",
        {"seed": 0, "platform_pinned": "cpu", "accuracy": 0.9},
    )
    artifact_dir(
        "bench_partial_hw_0.json",
        {"seed": 0, "partial": True, "rounds_completed": 3,
         "rounds_planned": 5, "accuracy_by_round": [0.8, 0.9, 0.91]},
    )
    partials = results.load_partial_runs()
    assert len(partials) == 1 and partials[0]["rounds_completed"] == 3
    # a complete TPU artifact for the same seed DOES suppress it
    artifact_dir("seeds_0.json", {"seed": 0, "accuracy": 0.95})
    assert results.load_partial_runs() == []


def test_smoke_partials_never_surface(artifact_dir):
    artifact_dir(
        "bench_partial_smoke_0.json",
        {"seed": 0, "partial": True, "smoke": True},
    )
    assert results.load_partial_runs() == []


def test_render_reports_measured_devices_not_render_host(artifact_dir):
    artifact_dir(
        "seeds_0.json",
        {"seed": 0, "device": "TPU v5 lite", "value": 90.0,
         "steady_round_s": 5.5, "rounds_per_sec_per_chip": 0.18,
         "accuracy_by_round": [0.9], "enc_plain_max_abs_diff": 1e-6,
         "encode_overflow_count": 0},
    )
    md = results.write_markdown({"presets": [], "convergence": []})
    assert "TPU v5 lite" in md
    assert "(no measured records)" not in md
    # pinned-accuracy section absent without pinned artifacts
    assert "platform-pinned" not in md


def test_render_pinned_table_omits_timing(artifact_dir):
    artifact_dir(
        "acc_cpu_seed0.json",
        {"seed": 0, "device": "cpu", "platform_pinned": "cpu",
         "rounds": 2, "accuracy": 0.91, "accuracy_by_round": [0.88, 0.91],
         "acc_vs_reference": 0.07, "enc_plain_max_abs_diff": None,
         "encode_overflow_count": 0, "value": 9999.0},
    )
    md = results.write_markdown({"presets": [], "convergence": []})
    assert "Accuracy & fidelity evidence" in md
    assert "0.91" in md and "9999" not in md  # timing deliberately omitted


def test_convergence_unknown_name_fails_before_backend(artifact_dir):
    with pytest.raises(SystemExit) as e:
        results.run_convergence(["definitely-not-a-config"])
    assert "available" in str(e.value)


def test_merge_records_keeps_good_rows_on_failure():
    old = [{"preset": "a", "accuracy": 0.9}, {"preset": "b", "accuracy": 0.8}]
    new = [{"preset": "a", "error": "boom"}, {"preset": "c", "accuracy": 0.7}]
    merged = {r["preset"]: r for r in results._merge_records(old, new)}
    assert merged["a"]["accuracy"] == 0.9      # failure never clobbers
    assert merged["b"]["accuracy"] == 0.8      # untouched rows kept
    assert merged["c"]["accuracy"] == 0.7      # new rows added
    # a successful re-measure DOES replace
    merged2 = {r["preset"]: r for r in results._merge_records(
        old, [{"preset": "a", "accuracy": 0.95}])}
    assert merged2["a"]["accuracy"] == 0.95
