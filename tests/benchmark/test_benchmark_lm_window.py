"""A cell of the frozen-base kind whose model has grouped-query attention of
two kinds, window layers with a trained sink among them (PR 34):
`tests/benchmark/tiny_lm_window/` holds its BENCHMARK.json, configuration
and mix; its check (`checks/lm_window_subset.py`), the check it shares with
(`checks/lm_subset.py`) and its reference (`reference/mimo_v2_flash.py`) are
found by name under `benchmarks/`. The harness runs it end to end with no
edit, and every control fails a limit.

Listed in BENCHMARK.json's `paths`. No device or topology call at import
time.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks")
TINY = os.path.join(HERE, "tiny_lm_window", "BENCHMARK.json")
CELL = "tinylmwindow.sync_tiny"
REAL = "mimo-v2-flash.sync_s8k"
NUMBERS = {"logit_err_vs_fp8", "route_agree_share", "loss_gap", "grad_norm_gap",
           "sink_grad_gap", "dropped_pairs", "window_leak", "window_edge_gap",
           "step_norm_gap", "leaf_step_gap", "val_loss_gap", "he_avg_err",
           "base_moved"}
CONTROLS = {"control_fp8", "control_router_bf16", "control_dropped_expert",
            "control_no_sink", "control_window_129", "control_window_127",
            "control_kinds_exchanged", "control_thetas_exchanged",
            "control_all_rotated", "control_no_value_scale",
            "control_kv_head_mod"}
READERS = ("window_attention_layers", "swa_block_pairs_over_window_pairs")
JOINED = ("encrypt_rows", "setup_base_s", "moe_load_max_over_mean",
          "fused_attention_layers", "moe_rows_over_held_pairs")  # since PR 45


@pytest.fixture(scope="module")
def run():
    name = "_hefl_bench_run_lm_window"
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
    assert cell["check"] == os.path.join(BENCH, "checks", "lm_window_subset.py")
    for fn in ("round_work", "numbers", "control_data", "control_numbers"):
        assert callable(getattr(check, fn))
    ref = cell["module"]("reference", cell["config"]["reference"])
    for fn in ("init", "forward", "loss", "forward_flops", "window_pairs"):
        assert callable(getattr(ref, fn))
    assert set(cell["config"]["limits"]) == NUMBERS
    for m in cell["per_layer"]:
        assert callable(cell["module"]("layer_metrics", m["name"]).read)
    mimos_cell_is_found_by_name(run, os.path.join(ROOT, "BENCHMARK.json"))


def mimos_cell_is_found_by_name(run, path) -> None:
    """The benchmark's own cell, looked up by name in the BENCHMARK.json at
    `path`: the same check, the limits naming exactly the numbers it gives, a
    reason beside each, the mix the benchmark had, one chip, the two readers
    this model brings, the five lists of the older token models' metrics
    that it joined (PR 45: the program sets all five here), none of what an
    indexer alone sets, and no cell without window layers in its readers'
    lists."""
    real = run.load_cell(path, REAL)
    assert real["check"] == os.path.join(BENCH, "checks", "lm_window_subset.py")
    assert set(real["config"]["limits"]) == NUMBERS
    assert NUMBERS <= set(real["config"]["limit_reasons"])
    assert real["cell"]["chips"] == 1 and len(real["cell"]["why"]) <= 200
    assert real["cell"]["traffic"] == "sync_s8k"
    names = {m["name"] for m in real["per_layer"]}
    assert set(READERS) | set(JOINED) | {"train_mfu", "peak_hbm_gb"} <= names
    assert not names & {"dsa_selected_share", "sparse_attention_layers",
                        "dsa_kept_selection_layers", "dsa_kept_attention_layers"}
    for other in ("joyai-flash.sync_s4k", "deepseek-v32.sync_s8k",
                  "ling-3-flash.sync_s8k"):
        theirs = run.load_cell(path, other)
        assert not {m["name"] for m in theirs["per_layer"]} & set(READERS)


def test_the_benchmarks_configuration_is_the_catalogs_row(run):
    the_configuration_is_the_catalogs_row(
        run, os.path.join(ROOT, "BENCHMARK.json"))


def the_configuration_is_the_catalogs_row(run, path) -> None:
    """Every number of the catalog's `config` under its key; the three cut
    keys listed; no width among them; the two lists a layer kept whole."""
    real = run.load_cell(path, REAL)["config"]
    published = {
        "attention_value_scale": 0.707, "hidden_size": 4096,
        "intermediate_size": 16384, "max_position_embeddings": 262144,
        "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
        "rope_theta": 5000000, "tie_word_embeddings": False,
        "vocab_size": 152576, "partial_rotary_factor": 0.334,
        "sliding_window": 128, "swa_rope_theta": 10000,
        "attention_bias": False, "v_head_dim": 128,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "sliding_window_size": 128,
        "attention_chunk_size": 128, "moe_intermediate_size": 2048,
        "n_routed_experts": 256, "n_shared_experts": None,
        "num_experts_per_tok": 8, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": None,
        "swa_num_attention_heads": 64, "swa_num_key_value_heads": 8,
        "swa_head_dim": 192, "swa_v_head_dim": 128}
    cut = {k for k, v in published.items() if real[k] != v}
    assert cut == set(real["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: real["published"][k] for k in cut} == {
        k: published[k] for k in cut}
    assert real["hybrid_layer_pattern"] == ([0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7
                                            + [0])
    assert real["moe_layer_freq"] == [0] + [1] * 47
    assert real["model_type"] == "mimo_v2_flash"
    for key in ("source", "published", "deployment", "assumed"):
        assert real[key]
    assert "env" not in real
    assert real["experiment"]["dataset"] == "tokens-v19072-s8192"


HELD = (mimos_cell_is_found_by_name,    # of a copy with additions too:
        the_configuration_is_the_catalogs_row)
# `test_benchmark_additions.py`


def test_a_rounds_work_counts_the_windows_pairs(run, cell, check):
    import numpy as np

    cfg = run.build_config(cell, 7, events_path="")
    data = ((np.zeros((4, 66), np.int32), np.zeros(4, np.int32)), None)
    work = check.round_work(cell, cfg, data)
    # 2 clients x 1 step x 1 sequence; 2 x forward a trained token + 1 x
    # forward a validation token
    assert work["samples_per_round"] == 2
    ref = cell["module"]("reference", "mimo_v2_flash")
    per_seq = ref.forward_flops(check._conf(cell), 64)["total"] * 64
    assert work["train_flops_per_round"] == pytest.approx((2 * 2 + 2) * per_seq)
    real = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), REAL)
    cfg = run.build_config(real, 7, events_path="")
    data = ((np.zeros((4, 8194), np.int32), np.zeros(4, np.int32)), None)
    work = check.round_work(real, cfg, data)
    assert work["samples_per_round"] == 2
    assert work["train_flops_per_round"] == pytest.approx(117.2e12, rel=1e-3)


def test_tiny_window_cell_end_to_end(run, monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("HEFL_EVENTS", "1")
    result = run.run_cell(TINY, CELL, 3400000007, 1.0, False,
                          require_tpu=False, workdir=str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"round_s", "samples_per_s", "setup_s"}
    assert set(result["checks"]) == NUMBERS | {
        "encode_overflow", "executables_in_window", "failed_rounds"}
    for name in ("dropped_pairs", "base_moved", "encode_overflow", "window_leak"):
        assert result["checks"][name]["value"] == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    info = lines[-1]
    assert info["samples_per_round"] == 2
    assert info["selections"]["client_fusion"]["backend"] == "serial"
    counters = info["compile"]["warmup_call"]
    # 9 x 64 + 3 x 8 x 64 + 2 x 4 = 2,120 trained parameters: 9 rows of 256,
    # the last ragged
    assert counters["he.encrypt_rows"] == 2 * 2 * 9
    assert counters["model.window_attention_layers"] == 2
    assert counters["model.fused_attention_layers"] == 4
    assert counters["swa.block_pairs_over_window_pairs"] == pytest.approx(
        128 * 128 / (36 + 120 * 8))
    assert 1.0 <= counters["moe.rows_over_held_pairs"] < 8.0
    # the readers give what the gauges hold now
    from hefl_tpu.obs import metrics as obs_metrics

    for name, key in (
            ("window_attention_layers", "model.window_attention_layers"),
            ("swa_block_pairs_over_window_pairs",
             "swa.block_pairs_over_window_pairs"),
            ("moe_rows_over_held_pairs", "moe.rows_over_held_pairs")):
        reader = run.load_cell(TINY, CELL)["module"]("layer_metrics", name)
        assert reader.read({}, None) == pytest.approx(
            obs_metrics.gauge(key).value)
    # a program without the gauges (the parent's) leaves both metrics out
    obs_metrics.gauge("model.window_attention_layers").set(0)
    obs_metrics.gauge("swa.block_pairs_over_window_pairs").set(0)
    for name in ("window_attention_layers", "swa_block_pairs_over_window_pairs"):
        reader = run.load_cell(TINY, CELL)["module"]("layer_metrics", name)
        assert reader.read({}, None) is None


def _judged(run, cell, numbers):
    limits = cell["config"]["limits"]
    return {r["check"]: r["ok"] for r in run.judge(
        {k: numbers[k] for k in limits if k in numbers}, limits)}


@pytest.fixture(scope="module")
def readings(run, cell, check):
    cfg = run.build_config(cell, 3400000011, events_path="")
    return check.control_numbers(cell, cfg, check.control_data(cfg))


def test_a_sound_run_passes_every_limit(run, cell, readings):
    assert set(readings) == {"sound", *CONTROLS}
    assert NUMBERS <= set(readings["sound"])
    assert all(_judged(run, cell, readings["sound"]).values())
    assert readings["sound"]["window_leak"] == 0
    assert 0.2 < readings["sound"]["sink_mass_share"] < 0.9


@pytest.mark.parametrize("control,fails", [
    ("control_fp8", "logit_err_vs_fp8"),
    ("control_dropped_expert", "logit_err_vs_fp8"),
    ("control_no_sink", "window_edge_gap"),
    ("control_window_129", "window_leak"),
    ("control_window_127", "window_edge_gap"),
    ("control_kinds_exchanged", "window_leak"),
    ("control_all_rotated", "window_edge_gap"),
    ("control_no_value_scale", "window_edge_gap"),
    ("control_kv_head_mod", "window_edge_gap"),
])
def test_every_control_fails_a_limit(run, cell, readings, control, fails):
    failed = {k for k, ok in _judged(run, cell, readings[control]).items()
              if not ok}
    assert fails in failed, (control, readings[control])


def test_what_this_size_cannot_separate_reads_no_better_than_sound(readings):
    """At hidden 64 and weights of 0.02 the scores are near 0, so the softmax
    hardly sees a rotation, and a bfloat16 router moves few selections: the
    two controls are held to reading no better than the sound run here (the
    chip's readings, where each fails a limit: PERF.md)."""
    sound = readings["sound"]
    assert (readings["control_router_bf16"]["route_agree_share"]
            <= sound["route_agree_share"])
    swapped = readings["control_thetas_exchanged"]
    assert swapped["window_edge_gap"] > 0 and swapped["logit_err_vs_fp8"] > 0
    assert swapped["window_leak"] == 0
