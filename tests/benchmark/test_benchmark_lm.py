"""A cell of the frozen-base kind, added as files only (PR 27): a language
model whose base stays on the client and whose trained subset alone goes
through the encrypted round. `tests/benchmark/tiny_lm/` holds its
BENCHMARK.json, configuration and mix; its check (`checks/lm_subset.py`) and
reference (`reference/joyai_llm_flash.py`) are found by name under
`benchmarks/`. The harness runs it end to end with no edit, its controls
fail, and a planted fault is not `correct`.

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
TINY_LM = os.path.join(HERE, "tiny_lm", "BENCHMARK.json")
CELL = "tinylm.sync_tiny"
NUMBERS = {"logit_err_vs_fp8", "route_agree_share", "loss_gap", "grad_norm_gap",
           "dropped_pairs", "step_norm_gap", "leaf_step_gap", "val_loss_gap",
           "he_avg_err", "base_moved"}


@pytest.fixture(scope="module")
def run():
    name = "_hefl_bench_run_lm"
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cell(run):
    return run.load_cell(TINY_LM, CELL)


@pytest.fixture(scope="module")
def check(run, cell):
    return run._module_at(cell["check"])


def test_the_check_and_the_reference_are_found_by_name(run, cell, check):
    assert cell["check"] == os.path.join(BENCH, "checks", "lm_subset.py")
    assert cell["config"]["check"] == "lm_subset"
    for fn in ("round_work", "numbers", "control_data", "control_numbers"):
        assert callable(getattr(check, fn))
    ref = cell["module"]("reference", cell["config"]["reference"])
    for fn in ("init", "forward", "loss", "forward_flops"):
        assert callable(getattr(ref, fn))
    assert set(cell["config"]["limits"]) == NUMBERS
    for m in cell["per_layer"]:
        assert callable(cell["module"]("layer_metrics", m["name"]).read)
    joyais_cell_is_found_by_name(run, os.path.join(ROOT, "BENCHMARK.json"))


def joyais_cell_is_found_by_name(run, path) -> None:
    """The benchmark's own cell, by name in the BENCHMARK.json at `path`,
    names the same check, with limits of its own."""
    real = run.load_cell(path, "joyai-flash.sync_s4k")
    assert real["check"] == os.path.join(BENCH, "checks", "lm_subset.py")
    assert set(real["config"]["limits"]) == NUMBERS
    assert real["cell"]["chips"] == 1 and len(real["cell"]["why"]) <= 200


HELD = (joyais_cell_is_found_by_name,)  # of a copy with additions too:
# `test_benchmark_additions.py`


def test_a_rounds_work_counts_trained_sequences_and_token_operations(
        run, cell, check):
    import numpy as np

    cfg = run.build_config(cell, 7, events_path="")
    data = ((np.zeros((10, 26), np.int32), np.zeros(10, np.int32)), None)
    work = check.round_work(cell, cfg, data)
    # 2 clients x 2 steps x 2 sequences; 2 x forward a trained token (no
    # weight gradient of the base) + 1 x forward a validation token
    assert work["samples_per_round"] == 8
    conf = {k: v for k, v in cell["config"].items()
            if not isinstance(v, (dict, list, str)) or k == "held"}
    ref = cell["module"]("reference", "joyai_llm_flash")
    per_seq = ref.forward_flops(conf, 24)["total"] * 24
    assert work["train_flops_per_round"] == pytest.approx((2 * 8 + 2) * per_seq)
    # the published widths: ISSUE 27's arithmetic, 1.046 GFLOP a token
    real = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                         "joyai-flash.sync_s4k")["config"]
    conf = {k: v for k, v in real.items()
            if not isinstance(v, (dict, list, str)) or k == "held"}
    parts = ref.forward_flops(conf, 4096)
    assert parts["total"] == pytest.approx(1.046e9, rel=2e-3)
    assert parts["expert_layer"] == pytest.approx(142.8e6, rel=2e-3)
    assert parts["attention"] == pytest.approx(94.6e6, rel=2e-3)


def test_tiny_lm_cell_end_to_end(run, monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("HEFL_EVENTS", "1")
    result = run.run_cell(TINY_LM, CELL, 3000000007, 1.0, False,
                          require_tpu=False, workdir=str(tmp_path))
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"round_s", "samples_per_s", "setup_s"}
    assert set(result["checks"]) == NUMBERS | {
        "encode_overflow", "executables_in_window", "failed_rounds"}
    for name in ("dropped_pairs", "base_moved", "encode_overflow"):
        assert result["checks"][name]["value"] == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    info = lines[-1]
    assert info["samples_per_round"] == 8
    assert info["selections"]["client_fusion"]["backend"] == "serial"
    assert info["compile"]["warmup_call"]["he.encrypt_rows"] == 2 * 2 * 11
    would = next(ln for ln in lines if "skipped_step_reads" in ln)
    limits = run.load_cell(TINY_LM, CELL)["config"]["limits"]
    assert would["skipped_step_reads"] > 1.5 * limits["step_norm_gap"]["max"]
    assert would["router_left_out_reads"] > 1.5 * limits["leaf_step_gap"]["max"]


def _judged(run, cell, numbers):
    limits = cell["config"]["limits"]
    return {r["check"]: r["ok"] for r in run.judge(
        {k: numbers[k] for k in limits if k in numbers}, limits)}


def test_controls_fail_a_limit(run, cell, check):
    cfg = run.build_config(cell, 3000000011, events_path="")
    got = check.control_numbers(cell, cfg, check.control_data(cfg))
    assert set(got) == {"sound", *check.VARIANTS}
    assert all(_judged(run, cell, got["sound"]).values())
    assert _judged(run, cell, got["control_fp8"])["logit_err_vs_fp8"] is False
    assert got["control_fp8"]["logit_err_vs_fp8"] == pytest.approx(1.0)
    assert _judged(run, cell, got["control_no_mtp"])["loss_gap"] is False
    assert _judged(run, cell, got["control_dropped_expert"])[
        "logit_err_vs_fp8"] is False
    # at this size a bfloat16 router moves few of 288 selections: held to
    # reading no better than the float32 one (the chip's reading: PERF.md)
    assert (got["control_router_bf16"]["route_agree_share"]
            <= got["sound"]["route_agree_share"])
    sound = got["sound"]
    limits = cell["config"]["limits"]
    assert sound["skipped_step_reads"] > limits["step_norm_gap"]["max"]
    assert sound["router_left_out_reads"] > limits["leaf_step_gap"]["max"]


def test_a_planted_fault_is_not_correct(run, cell, check, monkeypatch):
    """The timed path broken underneath the check: the prediction module's
    loss left out of what the step differentiates, and an owner's decrypt
    that returns the round's input."""
    import hefl_tpu.fl as fl
    from hefl_tpu.models import lm

    cfg = run.build_config(cell, 3000000013, events_path="")
    data = check.control_data(cfg)
    real_loss = lm.JoyAIFlash.loss

    def main_head_only(self, variables, tokens):
        arch = self.arch
        object.__setattr__(self, "arch", type(arch)(**{
            **arch.__dict__, "mtp_weight": 0.0}))
        try:
            return real_loss(self, variables, tokens)
        finally:
            object.__setattr__(self, "arch", arch)

    def stuck(ctx, sk, ct_sum, num_clients=None, spec=None, **kw):
        return kw["base_params"]

    check._sys_fns.cache_clear()
    fl.secure._build_secure_round_fn.cache_clear()
    monkeypatch.setattr(lm.JoyAIFlash, "loss", main_head_only)
    monkeypatch.setattr(fl, "decrypt_average", stuck)
    try:
        broken = check.numbers(cell, cfg, data)
    finally:
        check._sys_fns.cache_clear()
        fl.secure._build_secure_round_fn.cache_clear()
    ok = _judged(run, cell, broken)
    assert ok["loss_gap"] is False       # the loss is not the stated one
    assert ok["he_avg_err"] is False     # the decrypt is not the plain mean
    assert ok["base_moved"] is True and ok["dropped_pairs"] is True
