"""The device-scope readers (PR 36): `benchmarks/device_scopes.py` and the
eighteen `layer_metrics/*_dev_s.py` / `unscoped_dev_share.py` that read the
program's own reduction of a traced run's `.xplane.pb`, on the fixture cut
from a chip's trace (`tests/fixtures/chip_trace.xplane.pb.gz`, cut by
`tests/fixtures/cut_xplane.py`), on a trace with no TPU plane, and on a
program that has no reader.

Listed in BENCHMARK.json's `paths`. No device or topology call at import.
"""

from __future__ import annotations

import gzip
import importlib.util
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks")
CHIP_TRACE_GZ = os.path.join(ROOT, "tests", "fixtures", "chip_trace.xplane.pb.gz")
TRACE = {"rounds_traced": 2}
NEW = ("sgd_dev_s", "val_dev_s", "he_round_dev_s", "evaluate_dev_s",
       "decrypt_ops_dev_s", "adam_dev_s", "unscoped_dev_share", "conv_dev_s",
       "augment_dev_s", "batch_dev_s", "norm_dev_s", "attention_dev_s",
       "attention_kernel_dev_s", "moe_dev_s", "moe_gmm_dev_s", "lm_head_dev_s",
       "dsa_index_dev_s", "swa_attend_dev_s")


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run():   # loading run.py puts benchmarks/ on sys.path, as the command does
    return _load("_hefl_bench_run_scopes", os.path.join(BENCH, "run.py"))


@pytest.fixture()
def ds(run, monkeypatch, tmp_path):
    """`device_scopes` looking at a run's workdir that holds the fixture as
    `run_cell` would have left it: trace/ beside the event log."""
    import device_scopes
    from hefl_tpu.obs import events

    os.makedirs(tmp_path / "trace" / "plugins" / "profile" / "run")
    _trace_file(tmp_path).write_bytes(gzip.open(CHIP_TRACE_GZ).read())
    monkeypatch.setattr(events, "current_path",
                        lambda: str(tmp_path / "events.jsonl"))
    device_scopes._attribution.cache_clear()
    yield device_scopes
    device_scopes._attribution.cache_clear()


def _trace_file(workdir):
    return workdir / "trace" / "plugins" / "profile" / "run" / "host.xplane.pb"


def _reader(run, name):
    return run._module_at(os.path.join(BENCH, "layer_metrics", name + ".py")).read


def test_the_record_is_made_once_and_printed_once(ds, capsys):
    from hefl_tpu.obs import trace as obs_trace

    rec = ds.record(TRACE)
    assert ds.record(TRACE) is rec and ds.record(None) is None
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1 and set(lines[0]) == {"device_scopes"}
    printed = lines[0]["device_scopes"]
    want = obs_trace.trace_attribution(CHIP_TRACE_GZ)
    for key in ("paths", "under", "families", "modules", "backward_s", "flops",
                "bytes_accessed", "device_total_s", "unattributed_s"):
        assert printed[key] == json.loads(json.dumps(want[key])), key
    assert printed["trace_bytes"] == len(gzip.open(CHIP_TRACE_GZ).read())
    assert "trace_file" not in printed and printed["decode_s"] >= 0


def test_seconds_are_self_seconds_per_traced_round(ds, run):
    from hefl_tpu.obs import trace as obs_trace

    want = obs_trace.trace_attribution(CHIP_TRACE_GZ)
    # a reader of each kind: under scopes, a kernel family, the share
    assert _reader(run, "decrypt_ops_dev_s")(None, TRACE) == pytest.approx(
        want["under"]["hefl.decrypt"]["device_seconds"] / 2)
    assert _reader(run, "sgd_dev_s")(None, TRACE) == pytest.approx(
        want["under"]["hefl.sgd_core"]["device_seconds"] / 2)
    assert ds.family(TRACE, "hefl.decrypt") == pytest.approx(
        want["families"]["hefl.decrypt"]["device_seconds"] / 2)
    assert _reader(run, "unscoped_dev_share")(None, TRACE) == pytest.approx(
        100 * want["unattributed_s"] / want["device_total_s"])
    # an op under two of the asked scopes counts once
    both = ds.under(TRACE, "hefl.sgd_core", "hefl.conv")
    assert both == pytest.approx(ds.under(TRACE, "hefl.sgd_core"))
    assert ds.under({"rounds_traced": 1}, "hefl.decrypt") == pytest.approx(
        2 * ds.under(TRACE, "hefl.decrypt"))
    # nothing under the scope, no such family: left out, not 0
    assert ds.under(TRACE, "hefl.serve_rotate") is None
    assert ds.family(TRACE, "no_such_kernel") is None
    assert _reader(run, "dsa_index_dev_s")(None, TRACE) is None
    for name in NEW:  # and without a trace (`--trace 0`) every reader is silent
        assert _reader(run, name)(None, None) is None


@pytest.mark.parametrize("why", ["no_tpu_plane", "no_trace_dir",
                                 "a_program_without_the_reader"])
def test_nothing_to_read_leaves_the_metrics_out(ds, run, monkeypatch, tmp_path,
                                                why):
    from hefl_tpu.obs import trace as obs_trace

    target = _trace_file(tmp_path)
    blob = target.read_bytes()
    if why == "no_tpu_plane":  # the CPU's trace
        target.write_bytes(blob.replace(b"/device:TPU:0", b"/device:XPU:0"))
    elif why == "no_trace_dir":
        shutil.rmtree(tmp_path / "trace")
    else:  # the parent commit's obs/trace.py
        monkeypatch.delattr(obs_trace, "read_xplane")
    assert ds.record(TRACE) is None
    for name in NEW:
        assert _reader(run, name)(None, TRACE) is None


@pytest.mark.parametrize("why", ["truncated", "a_tpu_plane_without_tf_op"])
def test_a_broken_yardstick_raises(ds, run, tmp_path, why):
    """With the reader there and a TPU plane in the file, nothing to read is a
    fault (a libtpu that dropped the stat, a file cut short), not silence."""
    from hefl_tpu.obs import trace as obs_trace

    target = _trace_file(tmp_path)
    blob = target.read_bytes()
    if why == "truncated":
        target.write_bytes(blob[:50_000])
        error = obs_trace.TraceParseError
    else:
        target.write_bytes(blob.replace(b"tf_op", b"tf_oq"))
        error = obs_trace.NoScopeMetadata
    with pytest.raises(error):
        ds.record(TRACE)
    with pytest.raises(error):
        _reader(run, "sgd_dev_s")(None, TRACE)


# a token round's chains, a second each where not said: the model's scopes
# open in the step, in validation, in evaluation and in the prediction module
CHAINS = {
    "hefl.sgd_core": 1.0,
    "hefl.sgd_core/hefl.conv": 8.0, "hefl.val/hefl.conv": 1.0,
    "hefl.evaluate/hefl.conv": 1.0,
    "hefl.sgd_core/hefl.norm": 4.0, "hefl.val/hefl.norm": 1.0,
    "hefl.sgd_core/hefl.mla": 2.0, "hefl.val/hefl.mla": 1.0,
    "hefl.sgd_core/hefl.mla/hefl.dsa.index": 3.0,
    "hefl.evaluate/hefl.mla/hefl.dsa.index": 1.0,
    "hefl.sgd_core/hefl.gqa/hefl.swa.attend": 5.0,
    "hefl.val/hefl.gqa/hefl.swa.attend": 1.0,
    "hefl.sgd_core/hefl.mtp/hefl.mla/hefl.dsa.attend": 6.0,
    "hefl.sgd_core/hefl.moe.experts/hefl.moe_gmm": 7.0,
    "hefl.sgd_core/hefl.mtp/hefl.moe.route": 9.0, "hefl.val/hefl.moe.route": 1.0,
    "hefl.sgd_core/hefl.mtp": 10.0, "hefl.sgd_core/hefl.lm_head": 11.0,
    "hefl.evaluate/hefl.lm_head": 1.0, "hefl.val/hefl.mtp": 1.0,
}
STEP_PARTS = {"conv_dev_s": 8.0, "norm_dev_s": 4.0, "dsa_index_dev_s": 3.0,
              "swa_attend_dev_s": 5.0, "attention_dev_s": 2.0 + 3.0 + 5.0 + 6.0,
              "moe_dev_s": 7.0 + 9.0, "lm_head_dev_s": 10.0 + 11.0}


@pytest.mark.parametrize("name", sorted(STEP_PARTS))
def test_a_model_layer_metric_is_a_part_of_the_step(ds, run, monkeypatch, name):
    """Read inside `hefl.sgd_core` alone, and the prediction module's
    attention and expert layer once: the metrics of a cell add up to no more
    than `sgd_dev_s` (REVIEW, PR 36: read at any depth they came to 104%)."""
    rec = {"paths": {k: {"device_seconds": v} for k, v in CHAINS.items()}}
    monkeypatch.setattr(ds, "record", lambda trace: rec)
    one = {"rounds_traced": 1}
    assert _reader(run, name)(None, one) == pytest.approx(STEP_PARTS[name])
    step = _reader(run, "sgd_dev_s")(None, one)
    token = sum(_reader(run, n)(None, one)
                for n in ("attention_dev_s", "moe_dev_s", "lm_head_dev_s"))
    image = sum(_reader(run, n)(None, one) for n in ("conv_dev_s", "norm_dev_s"))
    assert token + image + CHAINS["hefl.sgd_core"] == pytest.approx(step)


def cells_by_kind(bench: dict) -> tuple[set, set]:
    """(image cells, token cells) of a loaded BENCHMARK.json, read from the
    configurations' files: a configuration without the key `check` runs the
    image classifier's, one whose `check` starts with `lm_` a token model's."""
    checks = {}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            checks[c["name"]] = json.load(f).get("check")
    image = {w["name"] for w in bench["workloads"] if checks[w["config"]] is None}
    token = {w["name"] for w in bench["workloads"]
             if (checks[w["config"]] or "").startswith("lm_")}
    return image, token


def the_eighteen_are_one_block_and_name_cells_by_kind(run, path) -> None:
    """What must stay true of the BENCHMARK.json at `path` however much is
    appended after PR 36's entries: they are one block in `NEW`'s order,
    found by name (later PRs append after it), each with a reader, and their
    lists follow the kinds of the cells, whichever cells there are."""
    with open(path) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == list(NEW)
    for name in NEW:
        m = entries[name]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert (m["source"], m["moves"], m["better"]) == (
            "device_trace", "round_s", "lower")
        assert m["unit"] == ("%" if name.endswith("_share") else "s")
    image, token = cells_by_kind(bench)
    assert image and token and not image & token
    assert set(entries["conv_dev_s"]["workloads"]) == image
    assert set(entries["adam_dev_s"]["workloads"]) == image  # PERF.md, PR 36
    assert set(entries["attention_dev_s"]["workloads"]) == token
    assert set(entries["sgd_dev_s"]["workloads"]) == cells


HELD = (the_eighteen_are_one_block_and_name_cells_by_kind,)  # of a copy too:
# `test_benchmark_additions.py`


def test_every_new_entry_has_a_reader_and_names_cells_that_exist(run):
    the_eighteen_are_one_block_and_name_cells_by_kind(
        run, os.path.join(ROOT, "BENCHMARK.json"))
