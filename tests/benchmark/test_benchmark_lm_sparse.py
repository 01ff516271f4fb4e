"""A cell of the frozen-base kind whose model selects its keys (PR 31):
`tests/benchmark/tiny_lm_sparse/` holds its BENCHMARK.json, configuration
and mix; its check (`checks/lm_sparse_subset.py`), the check it shares with
(`checks/lm_subset.py`) and its reference (`reference/deepseek_v32.py`) are
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
TINY = os.path.join(HERE, "tiny_lm_sparse", "BENCHMARK.json")
CELL = "tinylmsparse.sync_tiny"
REAL = "deepseek-v32.sync_s8k"
NUMBERS = {"logit_err_vs_fp8", "logit_err_late_vs_fp8", "route_agree_share",
           "select_agree_share", "selected_outside_causal",
           "selected_count_gap", "loss_gap", "grad_norm_gap", "dropped_pairs",
           "step_norm_gap", "leaf_step_gap", "val_loss_gap", "he_avg_err",
           "base_moved"}
READERS = ("dsa_selected_share", "sparse_attention_layers",
           "dsa_kept_selection_layers", "dsa_kept_attention_layers")
JOINED = ("encrypt_rows", "setup_base_s", "moe_load_max_over_mean",
          "fused_attention_layers")     # joyai's lists, joined in PR 45


@pytest.fixture(scope="module")
def run():
    name = "_hefl_bench_run_lm_sparse"
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
    assert cell["check"] == os.path.join(BENCH, "checks", "lm_sparse_subset.py")
    for fn in ("round_work", "numbers", "control_data", "control_numbers"):
        assert callable(getattr(check, fn))
    ref = cell["module"]("reference", cell["config"]["reference"])
    for fn in ("init", "forward", "loss", "forward_flops", "selected_pairs"):
        assert callable(getattr(ref, fn))
    assert set(cell["config"]["limits"]) == NUMBERS
    for m in cell["per_layer"]:
        assert callable(cell["module"]("layer_metrics", m["name"]).read)
    deepseeks_cell_is_found_by_name(run, os.path.join(ROOT, "BENCHMARK.json"))


def deepseeks_cell_is_found_by_name(run, path) -> None:
    """The benchmark's own cell, looked up by name in the BENCHMARK.json at
    `path`: the same check, limits of its own, one chip, the readers this
    model brings (PR 31's three, PR 42's two kept-for-the-gradient counts:
    listed since PR 45), the four lists of the first token model's metrics
    that it joined (PR 45: the program sets all four here), and no cell
    without an indexer in the lists of what an indexer alone sets."""
    real = run.load_cell(path, REAL)
    assert real["check"] == os.path.join(BENCH, "checks", "lm_sparse_subset.py")
    assert set(real["config"]["limits"]) == NUMBERS
    assert real["cell"]["chips"] == 1 and len(real["cell"]["why"]) <= 200
    names = {m["name"] for m in real["per_layer"]}
    assert set(READERS) | set(JOINED) | {
        "moe_rows_over_held_pairs", "train_mfu", "peak_hbm_gb"} <= names
    for other in ("joyai-flash.sync_s4k", "mimo-v2-flash.sync_s8k",
                  "ling-3-flash.sync_s8k"):
        theirs = run.load_cell(path, other)
        assert not {m["name"] for m in theirs["per_layer"]} & set(READERS)


def test_the_benchmarks_configuration_is_the_catalogs_row(run):
    the_configuration_is_the_catalogs_row(
        run, os.path.join(ROOT, "BENCHMARK.json"))


def the_configuration_is_the_catalogs_row(run, path) -> None:
    """Every number of the catalog's `config` under its key; the three cut
    keys listed; no width among them."""
    real = run.load_cell(path, REAL)["config"]
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64,
        "index_topk": 2048, "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "tie_word_embeddings": False, "topk_group": 4, "v_head_dim": 128,
        "vocab_size": 129280}
    cut = {k for k, v in published.items() if real[k] != v}
    assert cut == set(real["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: real["published"][k] for k in cut} == {
        k: published[k] for k in cut}
    assert real["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    for key in ("source", "published", "deployment", "assumed"):
        assert real[key]
    assert "env" not in real


HELD = (deepseeks_cell_is_found_by_name,    # of a copy with additions too:
        the_configuration_is_the_catalogs_row)
# `test_benchmark_additions.py`


def test_a_rounds_work_counts_selected_pairs(run, cell, check):
    import numpy as np

    cfg = run.build_config(cell, 7, events_path="")
    data = ((np.zeros((4, 42), np.int32), np.zeros(4, np.int32)), None)
    work = check.round_work(cell, cfg, data)
    # 2 clients x 1 step x 1 sequence; 2 x forward a trained token + 1 x
    # forward a validation token
    assert work["samples_per_round"] == 2
    ref = cell["module"]("reference", "deepseek_v32")
    per_seq = ref.forward_flops(check._conf(cell), 40)["total"] * 40
    assert work["train_flops_per_round"] == pytest.approx((2 * 2 + 2) * per_seq)
    real = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), REAL)
    cfg = run.build_config(real, 7, events_path="")
    data = ((np.zeros((4, 8194), np.int32), np.zeros(4, np.int32)), None)
    work = check.round_work(real, cfg, data)
    assert work["samples_per_round"] == 2
    assert work["train_flops_per_round"] == pytest.approx(281.5e12, rel=1e-3)


def test_tiny_sparse_cell_end_to_end(run, monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("HEFL_EVENTS", "1")
    result = run.run_cell(TINY, CELL, 3000000007, 1.0, False,
                          require_tpu=False, workdir=str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"round_s", "samples_per_s", "setup_s"}
    assert set(result["checks"]) == NUMBERS | {
        "encode_overflow", "executables_in_window", "failed_rounds"}
    for name in ("dropped_pairs", "base_moved", "encode_overflow",
                 "selected_outside_causal", "selected_count_gap"):
        assert result["checks"][name]["value"] == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    info = lines[-1]
    assert info["samples_per_round"] == 2
    assert info["selections"]["client_fusion"]["backend"] == "serial"
    counters = info["compile"]["warmup_call"]
    assert counters["he.encrypt_rows"] == 2 * 2 * 17
    assert counters["model.sparse_attention_layers"] == 4
    # min(t + 1, 8) of t + 1 keys a query over 40 positions
    assert counters["dsa.selected_share"] == pytest.approx(100 * 292 / 820)
    assert 1.0 <= counters["moe.rows_over_held_pairs"] < 16.0
    # the readers give what the gauges hold now (the rows a held pair move
    # with the routers, round by round)
    from hefl_tpu.obs import metrics as obs_metrics

    # every attention layer keeps its selection and its kernel's output
    assert counters["dsa.kept_selection_layers"] == 4
    assert counters["dsa.kept_attention_layers"] == 4
    for name, key in (("dsa_selected_share", "dsa.selected_share"),
                      ("sparse_attention_layers", "model.sparse_attention_layers"),
                      ("dsa_kept_selection_layers", "dsa.kept_selection_layers"),
                      ("dsa_kept_attention_layers", "dsa.kept_attention_layers"),
                      ("moe_rows_over_held_pairs", "moe.rows_over_held_pairs")):
        reader = run.load_cell(TINY, CELL)["module"]("layer_metrics", name)
        assert reader.read({}, None) == pytest.approx(
            obs_metrics.gauge(key).value)
    assert obs_metrics.gauge("dsa.selected_share").value == pytest.approx(
        counters["dsa.selected_share"])
    # a count that reads 0 (a model that keeps nothing, or has no indexer)
    # is left out of the line, not reported as 0
    for name in ("selection", "attention"):
        reader = run.load_cell(TINY, CELL)["module"](
            "layer_metrics", f"dsa_kept_{name}_layers")
        assert reader.read({}, None) == 4.0
        obs_metrics.gauge(f"dsa.kept_{name}_layers").set(0)
        assert reader.read({}, None) is None


def test_the_float8_stand_in_of_the_indexer_has_float8s_values(check):
    """`fp8_values` (integer operations the chip's compiler cannot drop, as
    it drops a float32 -> float8 -> float32 round trip) against ml_dtypes'
    e4m3 over its normal range, ties to even and the carry into the next
    power of two among them; under `jit` too."""
    import jax
    import ml_dtypes
    import numpy as np

    rng = np.random.default_rng(0)
    a = np.concatenate([
        rng.normal(size=4096) * np.exp2(rng.integers(-5, 8, 4096)),
        [1.0625, 1.1875, 1.9375, 1.96875, -1.0625, 0.0, 448.0, 2.0 ** -6],
    ]).astype(np.float32)
    a = a[(np.abs(a) <= 448) & ((np.abs(a) >= 2.0 ** -6) | (a == 0))]
    want = a.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    assert np.array_equal(np.asarray(check.fp8_values(a)), want)
    assert np.array_equal(np.asarray(jax.jit(check.fp8_values)(a)), want)
    assert 0.01 < np.max(np.abs(want - a) / np.maximum(np.abs(a), 1e-9)) <= 2 ** -4


def _judged(run, cell, numbers):
    limits = cell["config"]["limits"]
    return {r["check"]: r["ok"] for r in run.judge(
        {k: numbers[k] for k in limits if k in numbers}, limits)}


def test_every_control_fails_a_limit(run, cell, check):
    cfg = run.build_config(cell, 3000000011, events_path="")
    got = check.control_numbers(cell, cfg, check.control_data(cfg))
    assert set(got) == {"sound", "control_fp8", "control_router_bf16",
                        "control_no_mtp", "control_dropped_expert",
                        *check.SPARSE_VARIANTS}
    assert all(_judged(run, cell, got["sound"]).values())
    fails = {name: {k for k, ok in _judged(run, cell, numbers).items() if not ok}
             for name, numbers in got.items() if name != "sound"}
    assert "logit_err_vs_fp8" in fails["control_fp8"]
    assert "loss_gap" in fails["control_no_mtp"]
    assert "select_agree_share" in fails["control_index_fp8"]
    assert {"select_agree_share", "selected_count_gap"} <= fails["control_top_half"]
    assert {"selected_count_gap", "logit_err_late_vs_fp8"} <= fails["control_dense"]
    assert "select_agree_share" in fails["control_no_relu"]
    assert "route_agree_share" in fails["control_no_groups"]
    assert "select_agree_share" in fails["control_no_yarn"]
    assert fails["control_dropped_expert"]
    # at this size a bfloat16 router moves few selections: held to reading no
    # better than the float32 one (the chip's reading: PERF.md)
    assert (got["control_router_bf16"]["route_agree_share"]
            <= got["sound"]["route_agree_share"])
