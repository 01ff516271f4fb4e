"""Orchestration tests: multi-round loop, CLI config plumbing, resume."""

import json

import numpy as np
import jax.numpy as jnp
import pytest

from hefl_tpu.cli import build_parser, config_from_args
from hefl_tpu.experiment import ExperimentConfig, HEConfig, run_experiment
from hefl_tpu.fl import TrainConfig


TINY_TRAIN = TrainConfig(
    epochs=1, batch_size=8, num_classes=10, augment=False, val_fraction=0.25
)


def _tiny_cfg(**kw) -> ExperimentConfig:
    base = dict(
        model="smallcnn",
        dataset="mnist",
        num_clients=2,
        rounds=2,
        train=TINY_TRAIN,
        he=HEConfig(n=256),
        n_train=64,
        n_test=32,
        seed=3,
    )
    base.update(kw)
    return ExperimentConfig(**base)


def test_encrypted_experiment_two_rounds():
    out = run_experiment(_tiny_cfg(), verbose=False)
    assert len(out["history"]) == 2
    for rec in out["history"]:
        assert {"train+encrypt+aggregate", "decrypt", "evaluate", "total"} <= set(
            rec["phases"]
        )
        assert 0.0 <= rec["accuracy"] <= 1.0
        assert len(rec["val_acc"]) == 2
        # per-client encoder-saturation diagnostic must be recorded (and 0)
        assert rec["encode_overflow"] == [0, 0]
        assert "phase_roofline" not in rec
    assert out["augment_backend"]["requested"] == "auto"
    for leaf in np.asarray(out["params"]["Conv_0"]["kernel"]).ravel()[:5]:
        assert np.isfinite(leaf)


def test_two_rounds_leave_one_of_each_sub_span_a_round(tmp_path, monkeypatch):
    """Every host-side timing of a call is a span of the one recorder:
    the set-up steps once, each round's phases and their steps once a
    round, children inside their parents; `history[r]["phases"]` is what it
    was. The same call's result names its three backends, keeps no
    utilization of its own, and its event log has the run's schema. The
    owner's decode is one program, compiled in round 0 and launched once a
    round: round 1 compiles nothing."""
    from hefl_tpu.obs import events as obs_events
    from hefl_tpu.obs import spans as obs_spans

    monkeypatch.setenv("HEFL_EVENTS", "1")   # conftest defaults it off
    events = str(tmp_path / "events.jsonl")
    out = run_experiment(_tiny_cfg(events_path=events), verbose=False)
    # the three selections, each what was asked and what the rule gave (a
    # CPU, an n=256 ring, no augmentation, an image model)
    assert out["he_backend"] == {"requested": "auto", "backend": "xla"}
    assert out["augment_backend"] == {"requested": "auto", "backend": None}
    assert out["client_fusion"] == {"requested": "auto", "backend": "vmap"}
    # the program keeps no utilization of its own: `train_mfu` is the
    # benchmark's (benchmarks/layer_metrics/train_mfu.py)
    assert all("phase_roofline" not in rec for rec in out["history"])
    # events.jsonl parses strictly and carries the run's kinds, the fused
    # train phase, and a metrics snapshot in which the pre-flight static
    # analysis counted no violation
    evs = obs_events.read_events(events)
    kinds = {e["event"] for e in evs}
    assert {"experiment_start", "round_phase", "round_end",
            "experiment_end", "analysis_check"} <= kinds
    assert "train+encrypt+aggregate" in {
        e["phase"] for e in evs if e["event"] == "round_phase"}
    end = [e for e in evs if e["event"] == "experiment_end"][-1]
    assert end["metrics"]["analysis.violations"] == 0
    # the compiled decode: launched once a round, compiled at most once and
    # then in round 0's decrypt (an earlier call of this process with the
    # same tree and ring has left its program: equal PackSpecs share it);
    # nothing at all compiles once round 0 has ended
    round_ends = [e["ts"] for e in evs if e["event"] == "round_end"]
    compiles = [e for e in evs if e["event"] == "compile"]
    assert len(round_ends) == 2
    assert [e["fun_name"] for e in compiles if e["ts"] > round_ends[0]] == []
    assert [e["fun_name"] for e in compiles].count("jit(_decode_unpack)") <= 1
    assert out["obs"]["metrics"]["he.decode_programs"] == 2
    rows = obs_spans.recorded()
    call = max(s.call for s in rows if s.call is not None)
    rows = [s for s in rows if s.call == call]
    by_id = {s.id: s for s in rows}
    train = "hefl.phase.train+encrypt+aggregate"
    in_a_round = {
        "hefl.round": None,
        train: "hefl.round",
        train + ".dispatch": train,
        train + ".prefetch": train,
        train + ".device_wait": train,
        "hefl.phase.decrypt": "hefl.round",
        "hefl.phase.decrypt.kernel": "hefl.phase.decrypt",
        "hefl.phase.decrypt.decode": "hefl.phase.decrypt",
        "hefl.phase.decrypt.unpack": "hefl.phase.decrypt",
        "hefl.phase.decrypt.wait": "hefl.phase.decrypt",
        "hefl.phase.evaluate": "hefl.round",
    }
    for r in (0, 1):
        of_round = [s for s in rows if s.round == r]
        assert sorted(s.name for s in of_round) == sorted(in_a_round)
        for s in of_round:
            parent = by_id.get(s.parent)
            assert (parent.name if parent else None) == in_a_round[s.name]
            if parent is not None:
                assert parent.round == r
                assert parent.t0_ns <= s.t0_ns <= s.t1_ns <= parent.t1_ns
    setup = [s for s in rows if s.round is None]
    root = [s for s in setup if s.name == "hefl.setup"]
    assert len(root) == 1 and root[0].parent is None
    steps = [s.name for s in setup if s.parent == root[0].id]
    assert sorted(steps) == sorted([
        "hefl.setup.data", "hefl.setup.stage", "hefl.setup.stage",
        "hefl.setup.model", "hefl.setup.base", "hefl.setup.context",
        "hefl.setup.preflight", "hefl.setup.keygen"])
    assert len(setup) == 1 + len(steps)
    first_round = next(s for s in rows if s.name == "hefl.round" and s.round == 0)
    assert root[0].t1_ns <= first_round.t0_ns
    # PhaseTimer's record is its spans': same keys, same seconds
    for r, rec in enumerate(out["history"]):
        assert list(rec["phases"]) == [
            "train+encrypt+aggregate", "decrypt", "evaluate", "total"]
        for s in rows:
            if s.round == r and s.parent is not None and by_id[
                    s.parent].name == "hefl.round":
                key = s.name[len("hefl.phase."):]
                assert rec["phases"][key] == round(s.seconds, 4)


def test_frames_under_the_round_programs_trace_keep_their_size():
    """CPython 3.12 keeps Python frames in 16 KB chunks and is 40-100x slower
    on a call that straddles two of them. JAX traces the round program some
    hundreds of frames above `run_experiment`, so the size of the frames
    below that trace decides which of its hot calls land on a boundary: on
    the chip's host, one more frame (or a few more locals) here cost the
    resnet20 cell 8-15 s of `setup_s`, 10-19% (PERF.md, PR 24). A change
    that moves these numbers is not wrong, but it owes a chip run of
    `resnet20.sync_e1` and `medcnn.sync_e10` that shows `setup_s` held."""
    import sys

    from hefl_tpu.fl import secure

    if sys.version_info[:2] != (3, 12):
        pytest.skip("frame sizes are the interpreter's: pinned for 3.12")
    size = lambda f: (  # noqa: E731
        f.__code__.co_nlocals + f.__code__.co_stacksize
        + len(f.__code__.co_cellvars) + len(f.__code__.co_freevars))
    assert size(run_experiment) == 116
    assert size(secure.secure_fedavg_round) == 53
    assert size(secure.decrypt_average) == 29
    # and no frame of the program stands between the two
    assert "secure_fedavg_round" in run_experiment.__code__.co_names


def test_plaintext_experiment_and_label_skew():
    out = run_experiment(
        _tiny_cfg(encrypted=False, partition="label_skew", rounds=1), verbose=False
    )
    assert len(out["history"]) == 1
    assert "train+aggregate" in out["history"][0]["phases"]


def test_checkpoint_resume_continues_rounds(tmp_path):
    path = str(tmp_path / "ck.npz")
    cfg = _tiny_cfg(rounds=1, checkpoint_path=path)
    out1 = run_experiment(cfg, verbose=False)
    # bump rounds to 2 and resume: only round 1 should run
    cfg2 = _tiny_cfg(rounds=2, checkpoint_path=path)
    out2 = run_experiment(cfg2, resume=True, verbose=False)
    assert [r["round"] for r in out2["history"]] == [1]
    # resumed params start from the round-0 result, not from init
    a = np.asarray(out1["params"]["Dense_0"]["kernel"])
    b = np.asarray(out2["params"]["Dense_0"]["kernel"])
    assert a.shape == b.shape and not np.allclose(a, b)


def test_cli_flags_map_to_config():
    args = build_parser().parse_args(
        [
            "--model", "resnet20", "--dataset", "cifar10", "--num-clients", "8",
            "--rounds", "3", "--plaintext", "--partition", "label_skew",
            "--prox-mu", "0.1", "--he-n", "2048", "--no-augment",
        ]
    )
    cfg = config_from_args(args)
    assert cfg.model == "resnet20" and cfg.dataset == "cifar10"
    assert cfg.num_clients == 8 and cfg.rounds == 3
    assert cfg.encrypted is False and cfg.partition == "label_skew"
    assert cfg.train.prox_mu == 0.1 and cfg.train.augment is False
    assert cfg.train.num_classes == 10  # resnet20 registry default
    assert cfg.he.n == 2048
    assert cfg.faults is None and cfg.max_round_retries == 0  # defaults


def test_cli_robustness_flags_map_to_config():
    args = build_parser().parse_args(
        [
            "--drop-fraction", "0.25", "--nan-clients", "1",
            "--huge-clients", "2", "--straggler-delay", "1.5",
            "--fail-rounds", "1,3", "--fault-seed", "7",
            "--max-round-retries", "2", "--retry-backoff", "0.1",
            "--on-overflow", "exclude", "--max-update-norm", "50",
        ]
    )
    cfg = config_from_args(args)
    assert cfg.faults is not None
    assert cfg.faults.drop_fraction == 0.25 and cfg.faults.nan_clients == 1
    assert cfg.faults.huge_clients == 2 and cfg.faults.seed == 7
    assert cfg.faults.straggler_delay_s == 1.5
    assert cfg.faults.straggler_fraction == 0.25
    assert cfg.faults.fail_rounds == (1, 3)
    assert cfg.max_round_retries == 2 and cfg.retry_backoff_s == 0.1
    assert cfg.train.on_overflow == "exclude"
    assert cfg.train.max_update_norm == 50.0
    # no fault knob set -> no FaultConfig, legacy fast path
    assert config_from_args(build_parser().parse_args([])).faults is None


def test_data_dir_experiment(tmp_path):
    # Reference layout: DIR/Train/<class>/*.png + DIR/Test/<class>/*.png
    # (FLPyfhelin.py:38-55). A full encrypted round must run straight off
    # the folder.
    from PIL import Image

    rng = np.random.default_rng(0)
    for split, n_per in (("Train", 16), ("Test", 4)):
        for cname in ("covid", "normal"):
            d = tmp_path / split / cname
            d.mkdir(parents=True)
            for i in range(n_per):
                arr = rng.integers(0, 256, (20, 20, 3), dtype=np.uint8)
                Image.fromarray(arr).save(d / f"{i}.png")
    cfg = _tiny_cfg(
        data_dir=str(tmp_path),
        image_size=(16, 16),
        rounds=1,
        n_train=None,
        n_test=None,
        train=TrainConfig(
            epochs=1, batch_size=4, num_classes=10,  # wrong on purpose:
            augment=False, val_fraction=0.25         # folder must override
        ),
    )
    out = run_experiment(cfg, verbose=False)
    assert len(out["history"]) == 1
    assert 0.0 <= out["history"][0]["accuracy"] <= 1.0
    # 2 classes from the folder, not the 10 in the config
    assert np.asarray(out["params"]["Dense_1"]["kernel"]).shape[-1] == 2


def test_load_folder_splits_single_dir(tmp_path):
    from PIL import Image

    from hefl_tpu.data import load_folder_splits

    rng = np.random.default_rng(1)
    for cname in ("a", "b"):
        d = tmp_path / cname
        d.mkdir()
        for i in range(10):
            arr = rng.integers(0, 256, (8, 8, 3), dtype=np.uint8)
            Image.fromarray(arr).save(d / f"{i}.png")
    (x, y), (xt, yt), names = load_folder_splits(
        str(tmp_path), image_size=(8, 8), test_fraction=0.2
    )
    assert names == ["a", "b"]
    assert x.shape == (16, 8, 8, 3) and xt.shape == (4, 8, 8, 3)
    assert len(y) == 16 and len(yt) == 4


def test_presets_cover_baseline_configs():
    # BASELINE.json names five configurations; every one must have a preset
    # and each preset must be a valid, internally-consistent config.
    from hefl_tpu.models import MODEL_REGISTRY
    from hefl_tpu.presets import BASELINE_PRESET_NAMES, PRESETS

    assert len(BASELINE_PRESET_NAMES) == 5
    baseline = {n: PRESETS[n] for n in BASELINE_PRESET_NAMES}
    assert [p.encrypted for p in baseline.values()].count(False) == 1  # config 1
    for name, cfg in PRESETS.items():
        assert cfg.model in MODEL_REGISTRY, name
        assert cfg.rounds >= 2, f"{name}: need a warm round to measure"
        assert cfg.num_clients in (2, 8, 16)
    assert PRESETS["medical-skew"].partition == "label_skew"
    assert PRESETS["medical-skew"].train.prox_mu > 0
    assert PRESETS["cifar-resnet16"].num_clients == 16
    # the baseline measurement sweep must stay clean: no fault injection
    for name, cfg in baseline.items():
        assert cfg.faults is None, name
    # the robustness gate preset (run_chaos_smoke.sh)
    chaos = PRESETS["chaos-smoke"]
    assert chaos.faults is not None and chaos.faults.drop_fraction == 0.25
    assert chaos.faults.nan_clients == 1 and chaos.max_round_retries >= 1
    assert chaos.train.on_overflow == "exclude"


def test_cli_main_json_output(capsys):
    from hefl_tpu.cli import main

    rc = main(
        [
            "--model", "smallcnn", "--dataset", "mnist", "--num-clients", "2",
            "--rounds", "1", "--epochs", "1", "--batch-size", "8",
            "--n-train", "64", "--n-test", "32", "--he-n", "256",
            "--no-augment", "--json", "--no-save-model",
        ]
    )
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.strip().splitlines() if l.startswith("{")]
    rec = json.loads(lines[-1])
    assert rec["round"] == 0 and "accuracy" in rec


def test_cli_save_model_and_centralized_flags(tmp_path):
    # The reference always persists the aggregated model (agg_model.hdf5,
    # FLPyfhelin.py:280): the CLI must default --save-model on, allow
    # opting out, and expose the train_server centralized baseline.
    args = build_parser().parse_args([])
    assert args.save_model == "agg_model.npz" and args.centralized is False
    args = build_parser().parse_args(["--no-save-model", "--centralized"])
    cfg = config_from_args(args)
    assert cfg.save_model_path is None and cfg.centralized is True
    args = build_parser().parse_args(["--save-model", str(tmp_path / "m.npz")])
    assert config_from_args(args).save_model_path == str(tmp_path / "m.npz")


def test_save_model_artifact_roundtrips(tmp_path):
    from hefl_tpu.models import create_model
    from hefl_tpu.utils import load_params

    path = str(tmp_path / "agg.npz")
    out = run_experiment(_tiny_cfg(rounds=1, save_model_path=path), verbose=False)
    _, template = create_model("smallcnn", num_classes=10,
                               input_shape=(28, 28, 1))
    loaded = load_params(path, template)
    import jax

    for a, b in zip(
        jax.tree_util.tree_leaves(loaded), jax.tree_util.tree_leaves(out["params"])
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_centralized_baseline(tmp_path):
    # `train_server` analog reachable from the experiment/CLI layer
    # (VERDICT r2 missing #3): trains one model on the whole set.
    path = str(tmp_path / "central.npz")
    out = run_experiment(
        _tiny_cfg(rounds=1, centralized=True, save_model_path=path),
        verbose=False,
    )
    rec = out["history"][0]
    assert "train" in rec["phases"] and "evaluate" in rec["phases"]
    assert "train+encrypt+aggregate" not in rec["phases"]
    assert 0.0 <= rec["accuracy"] <= 1.0
    assert len(rec["val_acc"]) == 1
    import os

    assert os.path.exists(path)


def test_cli_dp_experiment_reports_epsilon(capsys):
    # DP-FedAvg end-to-end through the CLI: the encrypted round runs the
    # clip+noise sanitizer and the history carries the accountant's epsilon.
    from hefl_tpu.cli import main

    rc = main(
        [
            "--model", "smallcnn", "--dataset", "mnist", "--num-clients", "2",
            "--rounds", "2", "--epochs", "1", "--batch-size", "8",
            "--n-train", "64", "--n-test", "32", "--he-n", "256",
            "--no-augment", "--json", "--no-save-model",
            "--dp-noise", "2.0", "--dp-clip", "0.8",
        ]
    )
    assert rc == 0
    lines = [l for l in capsys.readouterr().out.strip().splitlines() if l.startswith("{")]
    recs = [json.loads(l) for l in lines]
    eps = [r["dp_epsilon"] for r in recs if "dp_epsilon" in r]
    assert len(eps) == 2
    assert 0 < eps[0] < eps[1]  # composition: privacy spend grows per round
