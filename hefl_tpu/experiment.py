"""Experiment orchestration: the multi-round federated training loop.

The reference's driver is notebook cell 3 (SURVEY.md §2.11): keygen, build
global model, train clients, encrypt+export, aggregate under encryption,
decrypt, evaluate — exactly ONE communication round, with wall-clock and
sklearn metrics collected by hand. `run_experiment` generalizes that to R
rounds with the same phase structure, per-phase timing matching BASELINE.md's
schema, label-skew/FedProx options (BASELINE.json configs 4-5), an optional
plaintext-aggregation mode (the notebook's cell-6 comparison path), and
checkpoint/resume.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from hefl_tpu.ckks.keys import CkksContext, keygen
from hefl_tpu.ckks.packing import PackedSpec, PackSpec
from hefl_tpu.ckks.quantize import PackingConfig
from hefl_tpu.data import (
    RoundPrefetcher,
    iid_contiguous,
    label_skew,
    load_folder_splits,
    make_dataset,
    stack_federated,
)
from hefl_tpu.fl import (
    DeviceLost,
    DpConfig,
    FaultConfig,
    HheConfig,
    StreamConfig,
    TrainConfig,
    decrypt_average,
    epsilon_spent,
    evaluate,
    fedavg_round,
    schedule_for_round,
    secure_fedavg_round,
    train_centralized,
)
from hefl_tpu.fl.faults import (
    POISON_HUGE,
    POISON_NAN,
    CrashConfig,
    record_round_meta,
)
from hefl_tpu.fl.fedavg import masked_mode, pad_federated
from hefl_tpu.models import count_params, create_model
from hefl_tpu.obs import events as obs_events
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import scopes as obs_scopes
from hefl_tpu.obs import spans as obs_spans
from hefl_tpu.parallel import (
    client_mesh_size,
    client_sharding,
    ct_shard_count,
    make_mesh,
    make_mesh_2d,
)
from hefl_tpu.utils import PhaseTimer, load_checkpoint, save_checkpoint, save_params
from hefl_tpu.utils import roofline


@dataclasses.dataclass(frozen=True)
class HEConfig:
    """CKKS parameters (the reference's `gen_pk(s=128, m=1024)` knobs,
    /root/reference/FLPyfhelin.py:330-344, modernized)."""

    n: int = 4096
    num_primes: int = 3
    prime_bits: int = 27
    scale: float = 2.0**30
    sigma: float = 3.2

    def build(self) -> CkksContext:
        return CkksContext.create(
            n=self.n,
            num_primes=self.num_primes,
            prime_bits=self.prime_bits,
            scale=self.scale,
            sigma=self.sigma,
        )


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    """Everything notebook cells 0-3 hard-code, as one declarative config."""

    model: str = "medcnn"
    dataset: str = "medical"
    data_dir: str | None = None       # real image folder (reference layout);
                                      # overrides `dataset` when set
    image_size: tuple[int, int] = (256, 256)
    num_clients: int = 2
    rounds: int = 1
    encrypted: bool = True
    partition: str = "iid"            # "iid" (reference) | "label_skew"
    skew_alpha: float = 0.5
    train: TrainConfig = TrainConfig()
    he: HEConfig = HEConfig()
    seed: int = 0
    n_train: int | None = None        # dataset-size overrides (None = spec default)
    n_test: int | None = None
    checkpoint_path: str | None = None
    exact_final_decode: bool = False  # bignum CRT decode on the last round
    profile_dir: str | None = None    # write a jax.profiler trace of round 0
    # Final aggregated model artifact (the reference ALWAYS persists
    # `agg_model.hdf5`, FLPyfhelin.py:280); the CLI defaults this on.
    save_model_path: str | None = None
    # Centralized (non-federated) baseline: run `train_server`
    # (FLPyfhelin.py:161-177) on the whole training set instead of the FL
    # loop — measures what federation costs in accuracy.
    centralized: bool = False
    # DP-FedAvg (beyond parity, fl/dp.py): clip client deltas and add
    # distributed Gaussian noise INSIDE the encrypted round program. None
    # keeps the reference's HE-only behavior.
    dp: "DpConfig | None" = None
    # Deterministic fault injection (fl/faults.py): per-round scheduled
    # dropout, NaN/huge-norm update poisoning, straggler delays, and
    # simulated device loss. None = no faults AND no masked engine (the
    # historical all-clients-present fast path, seeds untouched).
    faults: "FaultConfig | None" = None
    # Streaming quorum aggregation (fl/stream.py): per-round sampled
    # cohorts, arriving encrypted updates folded online into a running
    # modular sum, per-client deadlines with retry/backoff, bounded
    # staleness, quorum commit with graceful degradation. Encrypted runs
    # only. None = the synchronous wait-for-everyone round loop.
    stream: "StreamConfig | None" = None
    # Driver-level resilience: how many times to retry a round whose
    # execution died (device loss / runtime error), with exponential
    # backoff, auto-resuming params+RNG from the round checkpoint when one
    # matching the current round exists. 0 = fail fast (historical).
    max_round_retries: int = 0
    retry_backoff_s: float = 0.5
    # Quantized bit-interleaved CKKS packing (ckks.quantize / ckks.packing):
    # clients upload b-bit quantized updates interleaved k-to-a-slot, so
    # every HE phase and the uplink shrink by the packing factor. None (or
    # bits=0) keeps the historical one-float-per-coefficient path
    # bit-for-bit. Encrypted runs only.
    packing: "PackingConfig | None" = None
    # Structured run-event log (obs.events): one JSONL line per noteworthy
    # runtime occurrence (phase seconds, exclusions, retries, resumes,
    # autoselect outcomes, compiles). None = the default location
    # (events.jsonl next to the checkpoint, else the working directory);
    # "" = disabled for this run. HEFL_EVENTS=0 disables globally without
    # code changes (the test suite sets it).
    events_path: str | None = None
    # Span export (obs.spans): this call's host spans (set-up steps, rounds,
    # phases and their steps, on the profiler's clock) and every streaming
    # round's span tree (arrival/fold/ship/commit/recovery on the engine's
    # virtual clock) written as ONE Chrome trace-viewer JSON (.gz honored)
    # at the end of the run — rendered by the same tooling as device
    # traces. None = no export.
    span_trace_path: str | None = None
    # Durable aggregation service (fl.journal / fl.server): a write-ahead
    # round journal recording every streaming-engine transition, with
    # crash-anywhere recovery — on restart the server replays the journal,
    # re-folds persisted uploads, and reaches the bitwise state of an
    # uninterrupted run. Streaming runs only. None = the in-memory engine.
    journal_path: str | None = None
    # Journal fsync policy: "always" (every append), "commit" (transaction
    # boundaries — commit/degrade/round_close), "never" (OS-paced).
    # None defers to HEFL_JOURNAL_FSYNC, then "commit" — so the env
    # override reaches driver/CLI runs that never set the knob.
    fsync_policy: str | None = None
    # Recover-then-serve lifecycle: implies a journal (defaulted next to
    # the checkpoint when journal_path is unset) and auto-resumes from an
    # existing round checkpoint — re-running the same command after a
    # crash picks up exactly where the journal left off.
    serve: bool = False
    # Deterministic process-crash injection (fl.faults.CrashConfig): the
    # journal session raises SimulatedCrash at the configured boundary.
    # Requires the journal (a crash without a WAL is just data loss).
    crash: "CrashConfig | None" = None
    # Hybrid-HE uplink key knobs (hhe.cipher.HheConfig): used when
    # stream.upload_kind == "hhe" — clients encrypt packed quantized
    # updates under a per-client symmetric stream cipher (~1x wire, no
    # client NTTs) and the server transciphers into CKKS before the
    # quorum fold. None with upload_kind=hhe uses the default key seed;
    # set with upload_kind=ckks it is rejected loudly (a run the user
    # believes is HHE but is not).
    hhe: "HheConfig | None" = None
    # 2-D ("clients", "ct") round mesh (ISSUE 15): K > 1 gives every
    # client block K devices that split its in-round ciphertext rows
    # (fl.secure._ct_sharded_encrypt_core) — bitwise-identical results,
    # HE throughput scaled by K. 0/1 keeps the historical 1-D client mesh
    # (HEFL_MESH_CT can still flip the default at the mesh layer for CI).
    mesh_ct: int = 0


# The host's steps inside the train phase, as spans: `<prefix>dispatch`,
# `<prefix>prefetch`, `<prefix>device_wait`.
_ENC_STEP = obs_spans.PHASE_PREFIX + "train+encrypt+aggregate."
_PLAIN_STEP = obs_spans.PHASE_PREFIX + "train+aggregate."


def _train_roofline_inputs(module, params, train_cfg: TrainConfig,
                           sample_shape, n_samples: int, num_clients: int):
    """Per-round train FLOPs + image count for the roofline columns.

    Batch geometry comes from `fl.client.train_batch_geometry` — the same
    helper `_train_split` uses, so the numerator cannot drift from what
    training runs. FLOPs are XLA's own `cost_analysis()` of one
    fused-batch forward x3 (fwd+bwd ~= 3x fwd) — never a hand FLOP model.
    -> (train_flops, images_per_round); (None, n) when the backend offers
    no cost analysis.
    """
    import jax.numpy as jnp

    from hefl_tpu.fl.client import train_batch_geometry

    _, grp, steps = train_batch_geometry(train_cfg, int(n_samples))
    if grp < 1:  # degenerate tiny client; no meaningful roofline
        return None, 0
    fwd = roofline.program_flops(
        lambda p, xb: module.apply({"params": p}, xb),
        params,
        jnp.zeros((grp, *sample_shape), jnp.float32),
    )
    flops = roofline.train_flops_per_round(
        fwd, steps, train_cfg.epochs, num_clients
    )
    return flops, num_clients * train_cfg.epochs * steps * grp


def _hhe_wire_record(pspec, ctx) -> dict:
    """The result record's hybrid-HE wire story (hhe.cipher): symmetric
    upload bytes vs the plain quantized baseline (`expansion_hhe`, the
    <= 1.1x perf-smoke gate currency) and vs the packed CKKS ciphertext
    the upload replaces."""
    from hefl_tpu.hhe.cipher import hhe_bytes_on_wire_record

    return hhe_bytes_on_wire_record(pspec, ctx.num_primes)


def _record_round_obs(r: int, phases: dict, dev) -> None:
    """Per-round observability, shared by the centralized and federated
    paths: phase gauges + round_phase events, the rounds.completed
    counter, and the device-memory high-water mark."""
    for ph, sec in phases.items():
        if ph == "total":
            continue
        obs_metrics.gauge(f"phase_seconds.{ph}").set(sec)
        obs_events.emit("round_phase", round=r, phase=ph, seconds=sec)
    obs_metrics.counter("rounds.completed").inc()
    obs_metrics.record_device_memory(dev)


def _finish_run_obs(metrics_base: dict, rounds: int) -> dict:
    """End-of-run observability: the experiment_end event and THIS RUN's
    metrics (counters as deltas against the run-start baseline — the
    registry is process-global, and a second experiment in one process
    must not inherit the first one's counts). Returns the 'obs' record
    run_experiment embeds in its result."""
    run_metrics = obs_metrics.snapshot_delta(metrics_base)
    obs_events.emit("experiment_end", rounds=rounds, metrics=run_metrics)
    return {"events_path": obs_events.current_path(), "metrics": run_metrics}


def _partition(cfg: ExperimentConfig, y: np.ndarray) -> list[np.ndarray]:
    if cfg.partition == "iid":
        return iid_contiguous(len(y), cfg.num_clients)
    if cfg.partition == "label_skew":
        return label_skew(y, cfg.num_clients, alpha=cfg.skew_alpha, seed=cfg.seed)
    raise ValueError(f"unknown partition {cfg.partition!r}")


def run_experiment(
    cfg: ExperimentConfig, resume: bool = False, verbose: bool = True
) -> dict[str, Any]:
    """Run R federated rounds; -> {history, final_metrics, params, timers}.

    `history[r]` = {round, phases (seconds per phase), accuracy, precision,
    recall, f1, val_acc (per-client)} — the reference's cell-4/cell-5
    DataFrames as one record per round.

    Every host-side timing of the call is a span of `obs.spans`' recorder
    carrying this call's number: `hefl.setup` and its children for the
    start, then one `hefl.round` a round holding the PhaseTimer phases
    (`hefl.phase.<phase>`) and their steps (`hefl.phase.<phase>.<step>`).
    """
    say = print if verbose else (lambda *_: None)
    if cfg.dp is not None and (not cfg.encrypted or cfg.centralized):
        # Silently dropping a requested privacy mechanism would be the
        # worst possible failure mode: the user believes the release is DP
        # and it is not. The sanitizer lives inside the encrypted round
        # program (fl/secure.py), so that is the only path that honors it.
        raise ValueError(
            "dp is only applied on the encrypted federated path; remove "
            "--plaintext/--centralized or drop the dp config"
        )
    if cfg.faults is not None and cfg.centralized:
        # Same fail-loud rationale as dp: a chaos run that silently ran
        # no faults would let unhardened code pass a robustness gate.
        raise ValueError(
            "fault injection targets the federated round loop; remove "
            "--centralized or drop the faults config"
        )
    if (
        cfg.packing is not None
        and cfg.packing.enabled
        and (not cfg.encrypted or cfg.centralized)
    ):
        # Fail fast, before any event/log/dataset work: packing quantizes
        # the CKKS upload, so a plaintext/centralized run cannot honor it.
        raise ValueError(
            "packing quantizes the CKKS upload; remove "
            "--plaintext/--centralized or drop the packing config"
        )
    if cfg.stream is not None and (not cfg.encrypted or cfg.centralized):
        # The streaming engine folds ENCRYPTED uploads into a running
        # modular sum; a plaintext/centralized run has no such stream.
        raise ValueError(
            "streaming quorum aggregation runs on the encrypted federated "
            "path; remove --plaintext/--centralized or drop the stream "
            "config"
        )
    if (cfg.journal_path or cfg.serve) and cfg.stream is None:
        # The journal records STREAMING-engine transitions; a synchronous
        # run has none, and silently running without durability would be
        # the worst failure mode for a flag named --serve.
        raise ValueError(
            "the durable aggregation journal/--serve wraps the streaming "
            "engine; add a stream config (--stream) or drop "
            "journal_path/serve"
        )
    if cfg.crash is not None and not (cfg.journal_path or cfg.serve):
        raise ValueError(
            "crash injection without a write-ahead journal is just data "
            "loss; add journal_path (--journal-path) or serve (--serve)"
        )
    ef_on = (
        cfg.packing is not None
        and cfg.packing.enabled
        and getattr(cfg.packing, "error_feedback", False)
    )
    if ef_on and cfg.stream is None:
        # The EF residual is CROSS-ROUND state only the streaming engine
        # carries (fl.stream.StreamEngine._ef_residual); the batched
        # one-shot round has nowhere to hold it — fl.secure refuses too,
        # but this catches it before any dataset/compile work.
        raise ValueError(
            "PackingConfig.error_feedback requires the streaming engine's "
            "cross-round residual state; add a stream config (--stream) "
            "or drop error_feedback"
        )
    if ef_on and cfg.dp is not None:
        # Mirrors fl.stream.run_round's refusal: the residual carries
        # round r's clipped-and-noised signal into round r+1's upload,
        # breaking per-round sensitivity accounting and the
        # cohort-subsampling amplification.
        raise ValueError(
            "dp cannot be combined with error-feedback packing: the "
            "residual gives a client cross-round influence the per-round "
            "sensitivity accounting does not cover — drop error_feedback "
            "for dp runs"
        )
    hhe_on = cfg.stream is not None and cfg.stream.upload_kind == "hhe"
    if hhe_on and (cfg.packing is None or not cfg.packing.enabled):
        # The symmetric cipher lives in the PACKED integer domain: without
        # a quantized packing there is nothing for the keystream to add to
        # and nothing for the server to transcipher.
        raise ValueError(
            "upload_kind=hhe ships the packed quantized update under the "
            "stream cipher; add a PackingConfig (--pack-bits) or use "
            "upload_kind=ckks"
        )
    if cfg.hhe is not None and not hhe_on:
        # Same fail-loud rationale as dp/packing: silently ignoring an HHE
        # key config would leave the user believing clients skip their
        # CKKS work when they don't.
        raise ValueError(
            "an HheConfig is set but the stream upload_kind is not 'hhe'; "
            "set StreamConfig(upload_kind='hhe') (--hhe) or drop the hhe "
            "config"
        )
    if (
        cfg.dp is not None
        and cfg.stream is not None
        and cfg.stream.staleness_rounds > 0
    ):
        # A carried upload gives one client 2x the accounted per-round
        # sensitivity and breaks cohort-subsampling amplification (see
        # fl.stream.run_round, which enforces the same rule) — reject up
        # front, before any dataset/compile work.
        raise ValueError(
            "dp cannot be combined with a staleness budget: set "
            "StreamConfig.staleness_rounds=0 for dp runs (a carried "
            "upload would double a client's accounted sensitivity)"
        )
    if (
        cfg.dp is not None
        and cfg.stream is not None
        and cfg.stream.host_staleness_rounds > 0
    ):
        # The same hazard one tier up (see fl.stream.run_round, which
        # enforces the same rule): a carried host partial re-releases
        # every client fold it holds in a later round.
        raise ValueError(
            "dp cannot be combined with a tier staleness budget: set "
            "StreamConfig.host_staleness_rounds=0 for dp runs (a carried "
            "host partial would double its clients' accounted sensitivity)"
        )
    # dp under partial participation: each client's distributed noise
    # share is calibrated to the surviving-cohort floor
    # (DpConfig.min_surviving; fl/dp.py) — conservative over-noising whose
    # effective noise provably never drops below the full-participation
    # calibration. When faults or streaming make exclusions expected and
    # the user declared no floor, derive a conservative one here: the
    # quorum (streaming commits guarantee at least that many uploads) or
    # the schedule's worst-case surviving count. fl.secure still fails
    # loudly if a round survives BELOW the floor.
    dp_cfg = cfg.dp
    if (
        dp_cfg is not None
        and dp_cfg.min_surviving <= 0
        and (cfg.faults is not None or cfg.stream is not None)
    ):
        from hefl_tpu.fl import quorum_count
        from hefl_tpu.fl.stream import sample_cohort

        if cfg.stream is not None:
            cohort = len(sample_cohort(cfg.stream, 0, cfg.num_clients))
            floor = quorum_count(cfg.stream, cohort)
        else:
            floor = max(
                1,
                cfg.num_clients
                - cfg.faults.max_scheduled_exclusions(cfg.num_clients),
            )
        dp_cfg = dataclasses.replace(dp_cfg, min_surviving=floor)
    # One call of the span recorder, begun and ended by hand, and spans with
    # no handle kept (obs_spans.start/stop): NOT a wrapper function, and no
    # new local variable. The tracing of the round program runs hundreds of
    # Python frames above this one, and CPython 3.12 is 40-100x slower on a
    # call that straddles a 16 KB chunk of its frame stack; one more frame,
    # or this frame a few slots larger, moved that boundary onto a hot call
    # and cost the resnet20 cell 8-15 s of set-up (PERF.md, PR 24;
    # tests/test_experiment.py pins the frame's size). An error that passes
    # leaves the call open; the next call ends it first.
    call_id = obs_spans.begin_call()
    # Observability (obs): route this run's structured events to one JSONL
    # file (events.jsonl next to the checkpoint by default; events_path=""
    # or HEFL_EVENTS=0 disables) and start counting new XLA executables /
    # device-memory peaks process-wide.
    obs_metrics.install_jax_listeners()
    # Per-run counter baseline: the registry is process-global, so this
    # run's snapshots report deltas against it (a second experiment in the
    # same process must not inherit the first one's counts).
    metrics_base = obs_metrics.snapshot()
    ev_path = cfg.events_path
    if ev_path is None:
        ev_path = obs_events.default_events_path(cfg.checkpoint_path)
    obs_events.configure(ev_path or None)
    obs_events.emit(
        "experiment_start",
        model=cfg.model, dataset=cfg.dataset, num_clients=cfg.num_clients,
        rounds=cfg.rounds, encrypted=cfg.encrypted,
        centralized=cfg.centralized, faults=cfg.faults is not None,
        dp=cfg.dp is not None, seed=cfg.seed,
        stream=cfg.stream is not None,
        hhe=hhe_on,
        # The event fires before the HE context exists, so it carries the
        # CONFIGURED interleave (0 = auto) under an unambiguous name; the
        # RESOLVED k lives in the result record's `packing.interleave`.
        packing=(
            {
                "bits": cfg.packing.bits,
                "interleave_configured": cfg.packing.interleave,
            }
            if cfg.packing is not None and cfg.packing.enabled
            else None
        ),
    )
    if cfg.dp is not None and dp_cfg.min_surviving != cfg.dp.min_surviving:
        say(
            f"dp: noise shares recalibrated to a surviving-cohort floor of "
            f"{dp_cfg.min_surviving}/{cfg.num_clients} clients "
            "(conservative over-noising; effective noise never below the "
            "full-participation calibration)"
        )
        obs_events.emit(
            "dp_recalibrated",
            min_surviving=dp_cfg.min_surviving,
            num_clients=cfg.num_clients,
        )
    # The call's start, as one span with a child a step. start()/stop() in
    # place of `with`, here and for a round's span, because the regions are
    # hundreds of lines; ending the call closes what an error left open.
    obs_spans.start(obs_spans.SETUP)
    train_cfg = cfg.train
    if cfg.data_dir is not None:
        # The reference's primary workflow: point the tool at a folder of
        # class-subdir images (FLPyfhelin.py:38-55, notebook `image/Train`).
        with obs_spans.span("hefl.setup.data"):
            (x, y), (xt, yt), class_names = load_folder_splits(
                cfg.data_dir, image_size=cfg.image_size, seed=cfg.seed
            )
        say(f"data dir {cfg.data_dir}: classes {class_names}, "
            f"train {x.shape}, test {xt.shape}")
        if train_cfg.num_classes != len(class_names):
            train_cfg = dataclasses.replace(
                train_cfg, num_classes=len(class_names)
            )
    else:
        # make_dataset records its own `hefl.setup.data` span
        (x, y), (xt, yt), _ = make_dataset(
            cfg.dataset, seed=cfg.seed, n_train=cfg.n_train, n_test=cfg.n_test
        )
    # Hoist the test set to device ONCE: evaluate() every round would
    # otherwise pay the full host->device copy (78 MB at the medical spec)
    # per round (VERDICT r2 weak #7).
    with obs_spans.span("hefl.setup.stage"):
        xt_d = jax.device_put(jnp.asarray(xt))

    with obs_spans.span("hefl.setup.model"):
        module, params = create_model(
            cfg.model,
            num_classes=train_cfg.num_classes,
            input_shape=tuple(int(d) for d in x.shape[1:]),
        )
    key = jax.random.key(cfg.seed)

    if cfg.centralized:
        obs_spans.stop(obs_spans.SETUP)
        # The reference's `train_server` baseline (FLPyfhelin.py:161-177):
        # one model, the whole training set, same callback semantics. Not a
        # federated round — no partition, no mesh, no HE.
        timer = PhaseTimer()
        key, k_tr = jax.random.split(key)
        with timer.phase("train"):
            params, metrics = train_centralized(
                module, train_cfg, params, jnp.asarray(x), jnp.asarray(y), k_tr
            )
            jax.block_until_ready(params)
        with timer.phase("evaluate"):
            results = evaluate(module, params, xt_d, yt)
        dev = jax.devices()[0]
        train_flops, train_images = _train_roofline_inputs(
            module, params, train_cfg, x.shape[1:], len(x), 1
        )
        phases = timer.summary()
        record = {
            "round": 0,
            "phases": phases,
            # Per-phase {seconds, flops, mfu, images_per_s} sourced from
            # hefl_tpu.utils.roofline — the same schema bench.py /
            # profile_round.py artifacts carry.
            "phase_roofline": {
                "train": roofline.phase_stats(
                    phases.get("train"), flops=train_flops, device=dev,
                    images=train_images,
                ),
                "evaluate": roofline.phase_stats(
                    phases.get("evaluate"), device=dev, images=len(xt)
                ),
            },
            "val_loss": [float(np.asarray(metrics)[-1, 0])],
            "val_acc": [float(np.asarray(metrics)[-1, 1])],
            **{k: float(results[k]) for k in ("accuracy", "precision", "recall", "f1")},
        }
        say(f"centralized: acc {record['accuracy']:.4f} f1 {record['f1']:.4f} "
            f"({timer})")
        if cfg.save_model_path:
            save_params(cfg.save_model_path, params)
            say(f"saved model to {cfg.save_model_path}")
        _record_round_obs(0, phases, dev)
        obs_spans.end_call()
        return {
            "history": [record],
            "final_metrics": record,
            "params": params,
            "obs": _finish_run_obs(metrics_base, rounds=1),
        }

    with obs_spans.span("hefl.setup.stage"):
        xs, ys = stack_federated(x, y, _partition(cfg, y))
        # Round topology: the 1-D client mesh, or — with mesh_ct > 1 — the
        # 2-D ("clients", "ct") mesh whose ct axis shards the in-round HE
        # rows within each client block (ISSUE 15; bitwise-identical rounds).
        mesh = (
            make_mesh_2d(cfg.num_clients, cfg.mesh_ct)
            if cfg.mesh_ct > 1
            else make_mesh(cfg.num_clients)
        )
        # Hoist the padding gather: pad the federated arrays to the mesh ONCE
        # here (host-side) instead of letting every round re-run the
        # device-side xs[pad_idx] gather; the round wrappers get the real
        # client count via num_real_clients and skip their own data gather.
        xs, ys, num_real = pad_federated(xs, ys, client_mesh_size(mesh))
        # Double-buffered host->device staging: with a static dataset this
        # holds one resident copy (the historical jnp.asarray-once behavior);
        # per-round data (client sampling, streaming shards) overlaps its copy
        # with the previous round's compute via prefetcher.prefetch below.
        # Placed with the mesh's client sharding: each device receives its own
        # client block once, instead of the whole client axis landing on the
        # first device and every round resharding it.
        prefetcher = RoundPrefetcher(client_sharding(mesh))
        xs_d, ys_d = prefetcher.get(xs, ys)

    ctx = sk = pk = spec = pspec = None
    if cfg.encrypted:
        with obs_spans.span("hefl.setup.context"):
            ctx = cfg.he.build()
        # Pre-flight static analysis (ISSUE 8): certify the aggregation
        # no-wrap bounds and the packed headroom for THIS config before
        # any training work — fails loudly with the offending op named,
        # and publishes the analysis.violations counter (0 here) into the
        # run's metrics snapshot.
        from hefl_tpu import analysis

        with obs_spans.span("hefl.setup.preflight"):
            analysis.check_experiment(cfg, ctx=ctx, say=say)
        key, k_he = jax.random.split(key)
        with obs_spans.span("hefl.setup.keygen"):
            sk, pk = keygen(ctx, k_he)
        spec = PackSpec.for_params(params, ctx.n)
        say(
            f"CKKS context: N={ctx.n} L={ctx.num_primes} "
            f"-> {spec.n_ct} ciphertexts for {count_params(params):,} params"
        )
        if cfg.packing is not None and cfg.packing.enabled:
            pspec = PackedSpec.for_params(
                params, ctx, cfg.packing, cfg.num_clients
            )
            say(
                f"packing: b={pspec.bits} k={pspec.k} "
                f"(guard {pspec.guard}, clip {pspec.clip}) -> "
                f"{pspec.n_ct} packed ciphertexts "
                f"({spec.n_ct / pspec.n_ct:.1f}x fewer), error budget "
                f"{pspec.error_budget:.2e}"
            )

    if cfg.serve and not resume and cfg.checkpoint_path:
        # Recover-then-serve: re-running the same command after a crash
        # must pick up where the journal left off, so an existing round
        # checkpoint auto-resumes (the journal replays the open round on
        # top of the restored params/RNG).
        ck_file = (
            cfg.checkpoint_path
            if cfg.checkpoint_path.endswith(".npz")
            else cfg.checkpoint_path + ".npz"
        )
        if os.path.exists(ck_file):
            resume = True
            say(f"serve: auto-resuming from {cfg.checkpoint_path}")

    start_round = 0
    if resume:
        if not cfg.checkpoint_path:
            raise ValueError("resume=True requires checkpoint_path")
        params, start_round, key, _ = load_checkpoint(cfg.checkpoint_path, params)
        say(f"resumed from {cfg.checkpoint_path} at round {start_round}")
        obs_metrics.counter("checkpoint.resumes").inc()
        obs_events.emit(
            "checkpoint_resume", round=start_round, path=cfg.checkpoint_path
        )

    dev = jax.devices()[0]
    # Train-phase roofline inputs (geometry is per-configuration, so one
    # cost-analysis compile serves every round).
    with obs_spans.span("hefl.setup.roofline_inputs"):
        train_flops, train_images = _train_roofline_inputs(
            module, params, train_cfg, x.shape[1:], int(xs.shape[1]),
            cfg.num_clients,
        )
    train_phase = "train+encrypt+aggregate" if cfg.encrypted else "train+aggregate"

    # Robustness mode: any of fault injection, a client count that needs
    # padding onto the mesh, or an update-sanitization knob routes rounds
    # through the participation-masked engine (fl.fedavg/fl.secure), whose
    # outputs carry a per-round RoundMeta. The predicate is the SAME
    # masked_mode the round functions use to decide their return arity —
    # one source, so producer and unpack cannot drift.
    robust = masked_mode(
        train_cfg, cfg.num_clients, client_mesh_size(mesh),
        explicit=cfg.faults is not None, secure=cfg.encrypted,
    )
    # Streaming quorum aggregation (fl.stream): ONE engine per experiment —
    # it owns the cross-round state (uploads carried under the staleness
    # budget, the dedup nonce window). Streaming rounds always carry a
    # RoundMeta, so they ride the robust unpack/record path.
    streaming = cfg.stream is not None
    engine = None
    server = None
    if streaming:
        with obs_spans.span("hefl.setup.engine"):
            jp = cfg.journal_path
            if cfg.serve and not jp:
                # Serve mode defaults the journal next to the checkpoint —
                # the "durable artifacts of this run" directory.
                jp = os.path.join(
                    os.path.dirname(cfg.checkpoint_path) or "."
                    if cfg.checkpoint_path
                    else ".",
                    "journal.wal",
                )
            if jp:
                # Durable aggregation service: the engine wrapped in the
                # recover-then-serve write-ahead-journal lifecycle
                # (fl.server). Construction IS recovery — a journal left by
                # a crashed process is replayed here, torn tail truncated,
                # carried uploads and the dedup window rebuilt.
                from hefl_tpu.fl import AggregationServer

                engine = server = AggregationServer(
                    cfg.stream, cfg.faults, journal_path=jp,
                    fsync_policy=cfg.fsync_policy, crash=cfg.crash,
                )
                rec = server.recovered
                if not rec.fresh_journal:
                    say(
                        f"journal {jp}: recovered {rec.records} records "
                        f"(sealed rounds {list(rec.sealed_rounds)}, open "
                        f"round {rec.open_round}, {rec.carried_uploads} "
                        f"carried uploads"
                        + (
                            f", torn tail of {rec.torn_bytes_truncated} bytes "
                            "truncated"
                            if rec.torn_bytes_truncated
                            else ""
                        )
                        + ")"
                    )
            else:
                from hefl_tpu.fl import StreamEngine

                engine = StreamEngine(cfg.stream, cfg.faults)
        robust = True
    dp_sample_rate = 1.0
    if streaming and 0 < cfg.stream.cohort_size < cfg.num_clients:
        # Per-round uniform cohorts: the dp accountant applies privacy
        # amplification by subsampling at this rate (fl.dp.epsilon_spent).
        dp_sample_rate = cfg.stream.cohort_size / cfg.num_clients

    history: list[dict[str, Any]] = []
    span_tracers: list[Any] = []   # one SpanTracer per streaming round
    obs_spans.stop(obs_spans.SETUP)
    for r in range(start_round, cfg.rounds):
        obs_spans.start(obs_spans.ROUND, round=r)
        # Tracing (SURVEY.md §5): the reference brackets phases with
        # time.time()+print; we keep that (PhaseTimer below) and add a real
        # profiler trace of the first executed round on request.
        profiling = cfg.profile_dir is not None and r == start_round
        if profiling:
            jax.profiler.start_trace(cfg.profile_dir)
        sched = (
            schedule_for_round(cfg.faults, r, cfg.num_clients)
            if cfg.faults is not None
            else None
        )
        part = sched.participation() if sched is not None else None
        pois = sched.poison if sched is not None else None
        straggler_s = (
            float(np.max(sched.straggler_s)) if sched is not None else 0.0
        )
        key, k_round = jax.random.split(key)
        attempt = 0
        while True:
            # Retry/backoff envelope (cfg.max_round_retries): a round whose
            # execution dies (device loss, runtime error) is retried with
            # exponential backoff, auto-resuming (params, RNG) from the
            # round checkpoint when one matching this round exists — the
            # in-memory state is otherwise retried as-is. Deliberate
            # config errors (ValueError/TypeError) are never retried.
            try:
                if sched is not None and sched.device_loss and attempt == 0:
                    raise DeviceLost(
                        f"fault injection: scheduled device loss at round {r}"
                    )
                timer = PhaseTimer()
                meta = None
                smeta = None
                if cfg.encrypted:
                    with timer.phase("train+encrypt+aggregate"):
                        # the host's steps inside the phase, as spans:
                        # dispatch (the round's entry point until it
                        # returns), prefetch, device_wait
                        obs_spans.start(_ENC_STEP + "dispatch")
                        if streaming:
                            # Streaming quorum aggregation: arrivals fold
                            # online into a running modular sum; straggler
                            # delays become ARRIVAL TIMES the engine
                            # consumes (no driver-side sleep), deadlines /
                            # retries / staleness / quorum per fl.stream.
                            ct_sum, metrics, overflow, smeta = (
                                engine.run_round(
                                    module, train_cfg, mesh, ctx, pk,
                                    params, xs_d, ys_d, k_round, r,
                                    dp=dp_cfg, packing=pspec,
                                    num_real_clients=num_real,
                                    hhe=cfg.hhe,
                                )
                            )
                            meta = smeta.meta
                            if cfg.span_trace_path:
                                # The round's lifecycle span tree
                                # (StreamEngine directly, or through the
                                # journaled server's wrapped engine).
                                tr = getattr(
                                    engine, "last_spans", None
                                ) or getattr(
                                    getattr(engine, "engine", None),
                                    "last_spans", None,
                                )
                                if tr is not None:
                                    span_tracers.append(tr)
                        elif robust:
                            ct_sum, metrics, overflow, meta = (
                                secure_fedavg_round(
                                    module, train_cfg, mesh, ctx, pk, params,
                                    xs_d, ys_d, k_round, dp=dp_cfg,
                                    participation=part, poison=pois,
                                    num_real_clients=num_real,
                                    packing=pspec,
                                )
                            )
                        else:
                            ct_sum, metrics, overflow = secure_fedavg_round(
                                module, train_cfg, mesh, ctx, pk, params,
                                xs_d, ys_d, k_round, dp=dp_cfg,
                                num_real_clients=num_real, packing=pspec,
                            )
                        obs_spans.stop(_ENC_STEP + "dispatch")
                        # Stage the next round's arrays while this round
                        # computes (no-op while the dataset stays
                        # resident; see RoundPrefetcher).
                        with obs_spans.span(_ENC_STEP + "prefetch"):
                            prefetcher.prefetch(xs, ys)
                        with obs_spans.span(_ENC_STEP + "device_wait"):
                            jax.block_until_ready(
                                (ct_sum.c0, ct_sum.c1, metrics)
                            )
                        if straggler_s > 0 and not streaming:
                            # The synchronous round waits for its slowest
                            # scheduled straggler (driver-level simulation;
                            # shows up in the phase wall-clock like a real
                            # straggler would). The span makes the wait
                            # a first-class host row in profiler traces
                            # (obs.trace `host_rows`) instead of an
                            # unexplained wall-vs-device gap. The streaming
                            # engine instead CONSUMES the schedule as
                            # per-client arrival times (hefl.quorum_wait
                            # carries any real waiting there).
                            with obs_spans.span(obs_scopes.STRAGGLER_WAIT):
                                time.sleep(straggler_s)
                    with timer.phase("decrypt"):
                        if meta is not None and meta.surviving == 0:
                            # Nobody made the round: the ciphertext is an
                            # encryption of zero. Keep the global model —
                            # the same carry-over the plaintext masked
                            # engine applies (masked_mean_tree's count==0
                            # branch) — instead of decoding a 0/0.
                            if smeta is not None and not smeta.committed:
                                why = (
                                    "released sum below the dp noise floor"
                                    if smeta.degraded_reason == "dp_floor"
                                    else f"quorum not reached ({smeta.fresh}"
                                    f"/{smeta.quorum} fresh arrivals)"
                                )
                                say(f"round {r}: {why}; keeping previous "
                                    "global model")
                            else:
                                say(f"round {r}: every client excluded "
                                    f"({meta.excluded}); keeping previous "
                                    "global model")
                            new_params = params
                        else:
                            exact = (
                                cfg.exact_final_decode
                                and r == cfg.rounds - 1
                            )
                            new_params = decrypt_average(
                                ctx, sk, ct_sum, cfg.num_clients, spec,
                                exact=exact, meta=meta,
                                packing=pspec, base_params=params,
                                hhe=hhe_on,
                            )
                            with obs_spans.span("hefl.phase.decrypt.wait"):
                                jax.block_until_ready(new_params)
                else:
                    overflow = None
                    with timer.phase("train+aggregate"):
                        obs_spans.start(_PLAIN_STEP + "dispatch")
                        if robust:
                            new_params, metrics, meta = fedavg_round(
                                module, train_cfg, mesh, params, xs_d, ys_d,
                                k_round, participation=part, poison=pois,
                                num_real_clients=num_real,
                            )
                        else:
                            new_params, metrics = fedavg_round(
                                module, train_cfg, mesh, params, xs_d, ys_d,
                                k_round, num_real_clients=num_real,
                            )
                        obs_spans.stop(_PLAIN_STEP + "dispatch")
                        with obs_spans.span(_PLAIN_STEP + "prefetch"):
                            prefetcher.prefetch(xs, ys)
                        with obs_spans.span(_PLAIN_STEP + "device_wait"):
                            jax.block_until_ready((new_params, metrics))
                        if straggler_s > 0:
                            with obs_spans.span(obs_scopes.STRAGGLER_WAIT):
                                time.sleep(straggler_s)
                params = new_params
                break
            except RuntimeError as e:
                from hefl_tpu.fl.faults import SimulatedCrash
                from hefl_tpu.fl.journal import JournalError

                if isinstance(e, (SimulatedCrash, JournalError)):
                    # Not retryable in-process: SimulatedCrash models the
                    # PROCESS dying (its journal writer is already closed;
                    # recovery is a fresh run's job), and a JournalError
                    # is the fail-loud verdict — retrying would append
                    # fresh records over divergent/damaged history.
                    obs_events.emit(
                        "round_failed", round=r, error=type(e).__name__,
                        attempts=attempt + 1,
                    )
                    raise
                if attempt >= cfg.max_round_retries:
                    obs_events.emit(
                        "round_failed", round=r, error=type(e).__name__,
                        attempts=attempt + 1,
                    )
                    raise
                backoff = cfg.retry_backoff_s * (2**attempt)
                attempt += 1
                obs_metrics.counter("round.retries").inc()
                obs_events.emit(
                    "round_retry", round=r, attempt=attempt,
                    error=type(e).__name__, backoff_s=round(backoff, 3),
                )
                say(
                    f"round {r} failed ({type(e).__name__}: {e}); "
                    f"retry {attempt}/{cfg.max_round_retries} "
                    f"in {backoff:.1f}s"
                )
                time.sleep(backoff)
                ck = cfg.checkpoint_path
                ck_file = (
                    ck if ck is None or ck.endswith(".npz") else ck + ".npz"
                )
                if ck_file and os.path.exists(ck_file):
                    ck_params, ck_round, ck_key, _ = load_checkpoint(
                        ck, params
                    )
                    if ck_round == r:
                        # The checkpoint holds exactly this round's entry
                        # state (params after round r-1, pre-split RNG):
                        # restore both so the retried round is identical.
                        params = ck_params
                        key, k_round = jax.random.split(ck_key)
                        obs_metrics.counter("checkpoint.resumes").inc()
                        obs_events.emit("checkpoint_resume", round=r, path=ck)
                        say(f"auto-resumed round-{r} state from {ck}")
        with timer.phase("evaluate"):
            results = evaluate(module, params, xt_d, yt)
        if profiling:
            jax.profiler.stop_trace()
            say(f"profiler trace written to {cfg.profile_dir}")
            # The trace-viewer dump is obs.trace food: profile_round.py's
            # --profile mode parses the same format into per-phase
            # device-time rows (trace_attribution).
            obs_events.emit("profiler_trace", round=r, dir=cfg.profile_dir)
        phases = timer.summary()
        record = {
            "round": r,
            **(
                {
                    "dp_epsilon": epsilon_spent(
                        r + 1, dp_cfg.noise_multiplier, dp_cfg.delta,
                        sample_rate=dp_sample_rate,
                    )
                }
                if cfg.dp is not None and cfg.encrypted
                else {}
            ),
            "phases": phases,
            # Per-phase roofline record (same schema as bench.py /
            # profile_round.py artifacts). The train numerator is TRAIN
            # math only — the fused phase also encrypts+aggregates, so its
            # MFU is a lower bound.
            "phase_roofline": {
                train_phase: roofline.phase_stats(
                    phases.get(train_phase), flops=train_flops, device=dev,
                    images=train_images,
                ),
                **(
                    {
                        "decrypt": roofline.phase_stats(
                            phases.get("decrypt"), device=dev
                        )
                    }
                    if cfg.encrypted
                    else {}
                ),
                "evaluate": roofline.phase_stats(
                    phases.get("evaluate"), device=dev, images=len(xt)
                ),
            },
            "val_loss": np.asarray(metrics)[:, -1, 0].tolist(),
            "val_acc": np.asarray(metrics)[:, -1, 1].tolist(),
            **{k: float(results[k]) for k in ("accuracy", "precision", "recall", "f1")},
        }
        if cfg.encrypted:
            # Encoder-saturation diagnostic: nonzero means trained weights
            # were clipped at the CKKS encode envelope (see fl.secure).
            record["encode_overflow"] = np.asarray(overflow).tolist()
            overflow_total = int(np.sum(overflow))
            if overflow_total > 0:
                # Under packing the same slot counts QUANTIZER saturation
                # (|update| > PackingConfig.clip) instead of encoder
                # saturation — the remedy is the clip, not the scale.
                envelope, remedy = (
                    ("quantizer clip", "raise packing.clip")
                    if pspec is not None
                    else ("CKKS encode envelope", "lower he.scale")
                )
                excluded_for_overflow = (
                    meta is not None and meta.excluded.get("overflow", 0) > 0
                )
                if train_cfg.on_overflow == "raise":
                    raise RuntimeError(
                        f"round {r}: {overflow_total} weights saturated the "
                        f"{envelope} and on_overflow='raise' — {remedy} or "
                        "switch to on_overflow='exclude'"
                    )
                if excluded_for_overflow:
                    say(f"round {r}: excluded "
                        f"{meta.excluded['overflow']} client(s) whose "
                        f"updates saturated the {envelope}")
                else:
                    say(f"WARNING: round {r} clipped {overflow_total} "
                        f"weights at the {envelope}; {remedy}")
        if robust and meta is not None:
            # Per-round robustness record: the participation mask the
            # program applied, surviving count (the decode denominator),
            # per-cause exclusion counts, retries, and the injected faults.
            # record_round_meta also publishes it to obs (exclusion
            # counters by cause + one round_robust event line).
            record_round_meta(meta, r)
            rob: dict[str, Any] = {**meta.record(), "round_retries": attempt}
            if smeta is not None:
                # The streaming round's arrival-level story (quorum,
                # commit time, dedup/retry/staleness accounting).
                record["stream"] = smeta.record()
            if sched is not None:
                rob["faults"] = {
                    "dropped": np.flatnonzero(sched.dropped).tolist(),
                    "nan": np.flatnonzero(
                        sched.poison == POISON_NAN
                    ).tolist(),
                    "huge": np.flatnonzero(
                        sched.poison == POISON_HUGE
                    ).tolist(),
                    "straggler_s": round(straggler_s, 4),
                    "device_loss": bool(sched.device_loss),
                }
            record["robust"] = rob
        history.append(record)
        _record_round_obs(r, phases, dev)
        obs_spans.stop(obs_spans.ROUND)   # before the stamp that closes it
        obs_events.emit(
            "round_end", round=r,
            accuracy=round(record["accuracy"], 6),
            f1=round(record["f1"], 6),
            **(
                {"surviving": meta.surviving}
                if robust and meta is not None
                else {}
            ),
        )
        say(
            f"round {r}: acc {record['accuracy']:.4f} f1 {record['f1']:.4f} "
            + (
                f"dp_eps {record['dp_epsilon']:.2f} "
                if "dp_epsilon" in record
                else ""
            )
            + (
                f"surviving {meta.surviving}/{meta.num_clients} "
                if robust and meta is not None
                else ""
            )
            + f"({timer})"
        )
        if cfg.checkpoint_path:
            save_checkpoint(
                cfg.checkpoint_path, params, r + 1, key,
                meta={"model": cfg.model, "dataset": cfg.dataset,
                      "num_clients": cfg.num_clients},
            )
            obs_events.emit(
                "checkpoint_save", round=r, path=cfg.checkpoint_path
            )
            if server is not None:
                # The checkpoint now covers everything before round r+1:
                # compact the journal down to the records recovery can
                # still need (round r's carries/close + open work).
                server.compact_to(r + 1)

    if cfg.save_model_path:
        # The aggregated-model artifact the reference always writes
        # (`agg_model.hdf5`, FLPyfhelin.py:280) — npz here.
        save_params(cfg.save_model_path, params)
        say(f"saved aggregated model to {cfg.save_model_path}")

    from hefl_tpu.ckks.backend import he_backend_report
    from hefl_tpu.data.augment import backend_report
    from hefl_tpu.fl.fusion import fusion_report

    if server is not None:
        server.close()
    span_trace = None
    if cfg.span_trace_path:
        # This call's host spans, with the streaming rounds' trees.
        span_trace = obs_spans.export_chrome_trace(
            cfg.span_trace_path, span_tracers,
            obs_spans.recorded(call=call_id),
        )
        say(
            f"span trace: this call's host spans and {len(span_tracers)} "
            f"streaming round(s) -> {span_trace} "
            "(Chrome trace-viewer / obs.trace loadable)"
        )
        obs_events.emit(
            "span_trace", path=span_trace, rounds=len(span_tracers)
        )
    obs_record = _finish_run_obs(metrics_base, rounds=len(history))
    obs_spans.end_call()
    return {
        "history": history,
        "final_metrics": history[-1] if history else None,
        "params": params,
        # Span export (obs.spans): the written trace path (None = not
        # requested).
        "span_trace": span_trace,
        # Durable-aggregation record (None = in-memory engine): journal
        # path, fsync policy, and what recovery found on startup.
        "journal": server.report() if server is not None else None,
        # Which augment row-shift backend the round programs traced with
        # (incl. auto-selection micro-timings when in "auto" mode).
        "augment_backend": backend_report(),
        # Which cross-client training backend the round programs traced
        # with (TrainConfig.client_fusion; fl.fusion auto-selection).
        "client_fusion": fusion_report(),
        # Which HE backend (fused Pallas kernels vs the XLA reference) the
        # encrypt/decrypt programs traced with (HEFL_HE; ckks.backend).
        "he_backend": he_backend_report(),
        # Quantized bit-interleaved packing geometry (None = the historical
        # float path): packed vs unpacked ciphertext counts and the
        # declared quantization-error budget.
        "packing": pspec.geometry_record() if pspec is not None else None,
        # Streaming quorum-aggregation knobs this run used (None = the
        # synchronous round loop).
        "stream": (
            dataclasses.asdict(cfg.stream) if cfg.stream is not None else None
        ),
        # Round-mesh topology (ISSUE 15): devices per axis — ct > 1 means
        # the in-round HE rows sharded on the 2-D ("clients", "ct") mesh.
        "mesh": {
            "axes": [str(a) for a in mesh.axis_names],
            "clients": client_mesh_size(mesh),
            "ct": ct_shard_count(mesh),
        },
        # Hybrid-HE uplink record (None = direct CKKS uploads): key seed +
        # the bytes_on_wire story — symmetric-upload bytes vs the plain
        # quantized baseline (expansion_hhe, the <= 1.1x gate currency)
        # and vs the packed CKKS ciphertext it replaces (reduction).
        "hhe": (
            {
                "key_seed": (cfg.hhe or HheConfig()).key_seed,
                **_hhe_wire_record(pspec, ctx),
            }
            if hhe_on and pspec is not None
            else None
        ),
        # Observability record: where this run's events.jsonl went (None =
        # disabled) + THIS RUN's metrics (counters as deltas against the
        # run-start baseline; exclusions by cause, retries, resumes,
        # compile count, memory high-water).
        "obs": obs_record,
    }
