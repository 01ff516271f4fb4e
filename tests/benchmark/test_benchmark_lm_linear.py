"""A cell of the frozen-base kind whose model has delta-rule linear-attention
layers and a gated latent layer (PR 41): `tests/benchmark/tiny_lm_linear/`
holds its BENCHMARK.json, configuration and mix; its check
(`checks/lm_linear_subset.py`), the check it shares with
(`checks/lm_subset.py`), its reference (`reference/ling_3_flash.py`) and its
readers are found by name under `benchmarks/`. The harness runs it end to end
with no edit, a sound run passes every limit, every control fails one, and
the new readers read a recorded trace.

Listed in BENCHMARK.json's `paths`. No device or topology call at import
time.
"""

from __future__ import annotations

import gzip
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks")
TINY = os.path.join(HERE, "tiny_lm_linear", "BENCHMARK.json")
CELL = "tinylmlinear.sync_tiny"
REAL = "ling-3-flash.sync_s8k"
CHIP_TRACE_GZ = os.path.join(ROOT, "tests", "fixtures", "chip_trace.xplane.pb.gz")
NUMBERS = {"logit_err_vs_fp8", "route_agree_share", "loss_gap", "grad_norm_gap",
           "decay_grad_gap", "dropped_pairs", "future_leak", "kda_layer_gap",
           "gated_layer_gap", "step_norm_gap", "leaf_step_gap", "val_loss_gap",
           "he_avg_err", "base_moved"}
CONTROLS = {"control_fp8", "control_router_bf16", "control_dropped_expert",
            "control_state_bf16", "control_decay_bf16", "control_state_dropped",
            "control_softplus_gate", "control_no_beta", "control_conv_3",
            "control_gate_a_channel", "control_no_gate",
            "control_kinds_exchanged"}
READERS = ("kda_dev_s", "kda_scan_dev_s", "linear_attention_layers",
           "kda_scan_roofline_pct", "kda_front_kernel_layers",
           "kda_front_kernel_dev_s", "kda_front_roofline_pct")  # listed: PR 45
OLDER = ("medcnn.sync_e10", "resnet20.sync_e1", "joyai-flash.sync_s4k",
         "deepseek-v32.sync_s8k", "mimo-v2-flash.sync_s8k")  # cells before it
JOINED = ("sgd_dev_s", "val_dev_s", "he_round_dev_s", "evaluate_dev_s",
          "decrypt_ops_dev_s", "unscoped_dev_share", "attention_dev_s",
          "attention_kernel_dev_s", "moe_dev_s", "moe_gmm_dev_s",
          "lm_head_dev_s", "encrypt_rows", "setup_base_s",
          "moe_load_max_over_mean", "moe_rows_over_held_pairs",
          "fused_attention_layers")


@pytest.fixture(scope="module")
def run():
    name = "_hefl_bench_run_lm_linear"
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cell(run):
    return run.load_cell(TINY, CELL)


@pytest.fixture(scope="module")
def check(run, cell):
    return run._module_at(cell["check"])


def test_the_check_the_reference_and_the_readers_are_found_by_name(
        run, cell, check):
    assert cell["check"] == os.path.join(BENCH, "checks", "lm_linear_subset.py")
    for fn in ("round_work", "numbers", "control_data", "control_numbers"):
        assert callable(getattr(check, fn))
    ref = cell["module"]("reference", cell["config"]["reference"])
    for fn in ("init", "forward", "loss", "forward_flops", "kda_scan_flops",
               "kda_scan_bytes", "kda_front_flops", "kda_front_bytes", "block",
               "delta_rule"):
        assert callable(getattr(ref, fn))
    assert set(cell["config"]["limits"]) == NUMBERS
    for m in cell["per_layer"]:
        assert callable(cell["module"]("layer_metrics", m["name"]).read)
    assert set(READERS) <= {m["name"] for m in cell["per_layer"]}
    lings_cell_is_found_by_name(run, os.path.join(ROOT, "BENCHMARK.json"))


def lings_cell_is_found_by_name(run, path) -> None:
    """The benchmark's own cell, looked up by name in the BENCHMARK.json at
    `path`, wherever it stands among the cells: the same check, the limits
    naming exactly the numbers it gives, a reason beside each, the older
    lists it joined, the mix the benchmark had, one chip, and the seven
    readers this model brings each with an entry that names this cell and
    none of the cells that were there before it."""
    real = run.load_cell(path, REAL)
    assert real["check"] == os.path.join(BENCH, "checks", "lm_linear_subset.py")
    assert set(real["config"]["limits"]) == NUMBERS
    assert NUMBERS <= set(real["config"]["limit_reasons"])
    assert real["cell"]["chips"] == 1 and len(real["cell"]["why"]) <= 200
    assert real["cell"]["traffic"] == "sync_s8k"
    names = {m["name"] for m in real["per_layer"]}
    assert set(JOINED) | set(READERS) | {"train_mfu", "peak_hbm_gb"} <= names
    with open(path) as f:
        whole = json.load(f)
    entries = {m["name"]: m for m in whole["per_layer"]}
    for name in READERS:
        assert REAL in entries[name]["workloads"]
        assert not set(OLDER) & set(entries[name]["workloads"])
    conf = next(c for c in whole["configs"]
                if c["name"] == real["cell"]["config"])
    assert conf["reduced"] == real["config"]["reduced"]


def every_entry_has_a_reader_and_names_cells_that_exist(run, path) -> None:
    """Of the BENCHMARK.json at `path`, whatever later PRs appended to it:
    PR 36's eighteen entries are one block found by name
    (`test_device_scopes.py`), every entry has a reader under `paths` and
    names cells that exist, each once; ling's cell is in every older list
    whose reader finds something to read in it (the chip's traced run,
    PERF.md) and in its own readers' lists, and in no other list that was
    there when its readers were listed. What is appended after them is the
    appending PR's to hold."""
    scopes = importlib.util.spec_from_file_location(
        "_scopes_test", os.path.join(HERE, "test_device_scopes.py"))
    older = importlib.util.module_from_spec(scopes)
    scopes.loader.exec_module(older)
    older.the_eighteen_are_one_block_and_name_cells_by_kind(run, path)
    with open(path) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    assert REAL in cells and len(set(cells)) == len(cells)
    assert set(READERS) <= set(names)
    mine = max(names.index(name) for name in READERS)
    for at, m in enumerate(bench["per_layer"]):
        assert os.path.exists(run._find(bench["paths"], "layer_metrics",
                                        m["name"] + ".py"))
        listed = m.get("workloads", cells)
        assert set(listed) <= set(cells) and len(set(listed)) == len(listed)
        if m["name"] in JOINED:
            assert REAL in listed and len(listed) > 1
        elif m["name"] in READERS:
            assert REAL in listed
        elif at < mine:
            assert "workloads" not in m or REAL not in listed


HELD = (lings_cell_is_found_by_name,    # of a copy with additions too:
        every_entry_has_a_reader_and_names_cells_that_exist)
# `test_benchmark_additions.py`


def test_every_per_layer_entry_has_a_reader_and_names_cells_that_exist(run):
    every_entry_has_a_reader_and_names_cells_that_exist(
        run, os.path.join(ROOT, "BENCHMARK.json"))


def test_a_rounds_work_counts_the_recurrence_by_the_models_own_count(
        run, cell, check):
    import numpy as np

    cfg = run.build_config(cell, 7, events_path="")
    data = ((np.zeros((4, 66), np.int32), np.zeros(4, np.int32)), None)
    work = check.round_work(cell, cfg, data)
    # 2 clients x 1 step x 1 sequence; 2 x forward a trained token + 1 x
    # forward a validation token
    assert work["samples_per_round"] == 2
    ref = cell["module"]("reference", "ling_3_flash")
    per_seq = ref.forward_flops(check._conf(cell), 64)["total"] * 64
    assert work["train_flops_per_round"] == pytest.approx((2 * 2 + 2) * per_seq)
    real = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), REAL)
    cfg = run.build_config(real, 7, events_path="")
    data = ((np.zeros((4, 8194), np.int32), np.zeros(4, np.int32)), None)
    work = check.round_work(real, cfg, data)
    assert work["samples_per_round"] == 2
    # ISSUE 41: about 58 TFLOP of training and validation a round
    assert work["train_flops_per_round"] == pytest.approx(57.9e12, rel=5e-3)


def test_the_fronts_counts_at_the_published_widths(run):
    """`kda_front_flops` / `kda_front_bytes` by hand at H d = 32 x 128 = 4,096
    channels and 4 taps, a position a layer a pass, and the passes of the
    benchmark's own round; the bytes bound the time both ways."""
    conf = run._module_at(os.path.join(
        BENCH, "layer_metrics", "kda_scan_roofline_pct.py")).configuration()
    front = run._module_at(os.path.join(
        BENCH, "layer_metrics", "kda_front_roofline_pct.py"))
    ref = run._module_at(os.path.join(BENCH, "reference", "ling_3_flash.py"))
    assert (conf["num_attention_heads"], conf["head_dim"],
            conf["short_conv_kernel_size"], conf["positions"]) == (32, 128, 4, 8192)
    # forward: three convolutions of 4 taps (8) and SiLU (4), two head norms
    # (3 a channel, 2 a head), q's scale (1), the decay (6)
    assert ref.kda_front_flops(conf) == (
        3 * 12 + 2 * 3 + 1 + 6) * 4096 + 4 * 32 == 200_832
    # backward: made again, through SiLU and the transposed taps (25) three
    # times, through two norms (9 a channel, 4 a head), the decay's 13
    assert ref.kda_front_flops(conf, backward=True) == (
        3 * 25 + 2 * 9 + 13) * 4096 + 8 * 32 == 434_432
    assert ref.kda_front_bytes(conf) == 8 * 4096 * 4 == 131_072
    assert ref.kda_front_bytes(conf, backward=True) == 14 * 4096 * 4 == 229_376
    assert front.passes(conf, 2) == (35, 10)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for back in (False, True):      # a pass: 1.311 ms and 2.294 ms by bytes
        by_bytes = ref.kda_front_bytes(conf, back) / 819e9
        assert by_bytes > 100 * ref.kda_front_flops(conf, back) / 197e12
    assert front.must_take_s(conf, 2, peaks) == pytest.approx(
        35 * 1.3110e-3 + 10 * 2.2943e-3, rel=1e-4)


def test_tiny_linear_cell_end_to_end(run, monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("HEFL_EVENTS", "1")
    result = run.run_cell(TINY, CELL, 4100000007, 1.0, False,
                          require_tpu=False, workdir=str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"round_s", "samples_per_s", "setup_s"}
    assert set(result["checks"]) == NUMBERS | {
        "encode_overflow", "executables_in_window", "failed_rounds"}
    for name in ("dropped_pairs", "base_moved", "encode_overflow", "future_leak"):
        assert result["checks"][name]["value"] == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    info = lines[-1]
    assert info["samples_per_round"] == 2
    assert info["selections"]["client_fusion"]["backend"] == "serial"
    counters = info["compile"]["warmup_call"]
    # 3 x 16 x 64 + 9 x 64 + 32 + 3 x 84 = 3,932 trained parameters: 16 rows
    # of 256, the last ragged
    assert counters["he.encrypt_rows"] == 2 * 2 * 16
    assert counters["model.linear_attention_layers"] == 3
    assert counters["model.gated_attention_layers"] == 1
    assert counters["model.fused_attention_layers"] == 1
    assert counters["model.kda_front_kernel_layers"] == 0   # heads of 16: XLA's
    assert 1.0 <= counters["moe.rows_over_held_pairs"] < 8.0
    check_round = next(ln["check_round"] for ln in lines if "check_round" in ln)
    assert check_round["ciphertext_rows_a_client"] == 16
    # the reader gives what the gauge holds now; a program without the gauge
    # (the parent's) leaves the metric out
    from hefl_tpu.obs import metrics as obs_metrics

    reader = run.load_cell(TINY, CELL)["module"](
        "layer_metrics", "linear_attention_layers")
    assert reader.read({}, None) == 3.0
    obs_metrics.gauge("model.linear_attention_layers").set(0)
    assert reader.read({}, None) is None
    # the front of this preset (heads of 16) is XLA's: the count of kernel
    # layers is 0 and left out; where the kernel pair runs it is the gauge
    reader = run.load_cell(TINY, CELL)["module"](
        "layer_metrics", "kda_front_kernel_layers")
    assert reader.read({}, None) is None
    obs_metrics.gauge("model.kda_front_kernel_layers").set(3)
    assert reader.read({}, None) == 3.0
    obs_metrics.gauge("model.kda_front_kernel_layers").set(0)
    # no device trace on the CPU: the five that read one say nothing
    for name in ("kda_dev_s", "kda_scan_dev_s", "kda_scan_roofline_pct",
                 "kda_front_kernel_dev_s", "kda_front_roofline_pct"):
        reader = run.load_cell(TINY, CELL)["module"]("layer_metrics", name)
        assert reader.read({"samples_per_round": 2, "peaks": {}}, None) is None


def test_the_new_readers_on_a_recorded_trace(run, monkeypatch, tmp_path):
    """The chip's recorded trace (a round of the image model: no linear
    layer in it) leaves all five out; with the recurrence's scopes among its
    paths and the front's kernels among its families they read them, the
    scopes inside the training step alone and the kernels wherever they ran,
    and each roofline share is the model's own count over what was read."""
    import device_scopes
    from hefl_tpu.obs import events

    os.makedirs(tmp_path / "trace" / "plugins" / "profile" / "run")
    (tmp_path / "trace" / "plugins" / "profile" / "run" / "host.xplane.pb"
     ).write_bytes(gzip.open(CHIP_TRACE_GZ).read())
    monkeypatch.setattr(events, "current_path",
                        lambda: str(tmp_path / "events.jsonl"))
    device_scopes._attribution.cache_clear()
    trace = {"rounds_traced": 2}
    record = {"samples_per_round": 2,
              "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: run._module_at(os.path.join(  # noqa: E731
        BENCH, "layer_metrics", name + ".py")).read(record, trace)
    try:
        assert device_scopes.record(trace) is not None
        for name in ("kda_dev_s", "kda_scan_dev_s", "kda_scan_roofline_pct",
                     "kda_front_kernel_dev_s", "kda_front_roofline_pct"):
            assert read(name) is None
        rec = device_scopes.record(trace)
        rec["paths"].update({
            "hefl.sgd_core/hefl.kda": {"device_seconds": 0.30},
            "hefl.sgd_core/hefl.kda/hefl.kda.scan": {"device_seconds": 0.50},
            "hefl.val/hefl.kda/hefl.kda.scan": {"device_seconds": 0.07},
            "hefl.evaluate/hefl.kda": {"device_seconds": 0.02}})
        assert read("kda_dev_s") == pytest.approx(0.40)        # per round
        assert read("kda_scan_dev_s") == pytest.approx(0.25)
        # 3 x 5 layers x 2 sequences x 8,192 positions x 82,048 bytes / 819 GB/s
        must = 3 * 5 * 2 * 8192 * 82048 / 819e9
        assert read("kda_scan_roofline_pct") == pytest.approx(100 * must / 0.25)
        assert 0 < read("kda_scan_roofline_pct") < 100
        # the front's kernel pair, by family, wherever the calls ran (two
        # traced rounds: 70 forward calls, 20 backward); another kernel's
        # family is not read
        rec["families"].update({
            "kda_front_fwd": {"device_seconds": 0.12, "op_events": 70},
            "kda_front_bwd": {"device_seconds": 0.10, "op_events": 20},
            "kda_frontier": {"device_seconds": 9.0, "op_events": 1}})
        assert read("kda_front_kernel_dev_s") == pytest.approx(0.11)
        # a round of 2 trained sequences: 5 layers x (2 x 2 + 2 validated + 1
        # evaluated) = 35 forward passes of 8 H d float32 a position and 5 x
        # 2 = 10 backward passes of 14 H d, over 819 GB/s
        must = 8192 * 4096 * 4 * (35 * 8 + 10 * 14) / 819e9
        assert read("kda_front_roofline_pct") == pytest.approx(100 * must / 0.11)
        assert 0 < read("kda_front_roofline_pct") < 100
    finally:
        device_scopes._attribution.cache_clear()


def _judged(run, cell, numbers):
    limits = cell["config"]["limits"]
    return {r["check"]: r["ok"] for r in run.judge(
        {k: numbers[k] for k in limits if k in numbers}, limits)}


@pytest.fixture(scope="module")
def readings(run, cell, check):
    cfg = run.build_config(cell, 4100000011, events_path="")
    return check.control_numbers(cell, cfg, check.control_data(cfg))


def test_a_sound_run_passes_every_limit(run, cell, readings):
    assert set(readings) == {"sound", *CONTROLS}
    assert NUMBERS <= set(readings["sound"])
    assert all(_judged(run, cell, readings["sound"]).values())
    assert readings["sound"]["future_leak"] == 0
    assert 0.8 < readings["sound"]["decay_mean"] < 0.999   # the state weighs


@pytest.mark.parametrize("control,fails", [
    ("control_fp8", "logit_err_vs_fp8"),
    ("control_dropped_expert", "logit_err_vs_fp8"),
    ("control_state_dropped", "kda_layer_gap"),
    ("control_softplus_gate", "kda_layer_gap"),
    ("control_no_beta", "kda_layer_gap"),
    ("control_conv_3", "kda_layer_gap"),
    ("control_gate_a_channel", "gated_layer_gap"),
    ("control_no_gate", "gated_layer_gap"),
    ("control_kinds_exchanged", "logit_err_vs_fp8"),
])
def test_every_control_fails_a_limit(run, cell, readings, control, fails):
    failed = {k for k, ok in _judged(run, cell, readings[control]).items()
              if not ok}
    assert fails in failed, (control, readings[control])


def test_what_this_size_cannot_separate_reads_no_better_than_sound(readings):
    """Over 64 positions a state rounded to bfloat16 after every position has
    not drifted yet, a decay rounded to bfloat16 is off by a part in 500 of a
    few hundredths, and a bfloat16 router moves few selections at hidden 64:
    the three controls are held to reading a gap and no better than the
    sound run here (the chip's readings at 8,192 positions, where each fails
    a limit: PERF.md)."""
    sound = readings["sound"]
    assert (readings["control_router_bf16"]["route_agree_share"]
            <= sound["route_agree_share"])
    for name in ("control_state_bf16", "control_decay_bf16"):
        assert readings[name]["kda_layer_gap"] > 0
        assert readings[name]["gated_layer_gap"] == 0
