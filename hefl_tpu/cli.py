"""Command-line entry: `python -m hefl_tpu.cli [flags]`.

The reference's "CLI" is running the notebook top-to-bottom with constants
edited in source (SURVEY.md §2.1, §2.11). Every knob the notebook hard-codes
is a flag here; defaults reproduce the reference experiment (2 clients,
1 round, 10 local epochs, medical dataset, encrypted aggregation).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from hefl_tpu.experiment import ExperimentConfig, HEConfig, run_experiment
from hefl_tpu.fl import (
    CrashConfig,
    DpConfig,
    FaultConfig,
    HheConfig,
    PackingConfig,
    StreamConfig,
    TrainConfig,
)
from hefl_tpu.models import MODEL_REGISTRY


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hefl_tpu",
        description="TPU-native homomorphic-encryption federated learning",
    )
    p.add_argument("--preset", default=None,
                   help="run a named BASELINE.json config (see "
                        "hefl_tpu.presets.PRESETS); other flags are ignored")
    p.add_argument("--model", default="medcnn", choices=sorted(MODEL_REGISTRY))
    p.add_argument("--dataset", default="medical",
                   choices=["medical", "mnist", "cifar10"])
    p.add_argument("--data-dir", default=None, metavar="DIR",
                   help="directory of class-subdir images (reference layout: "
                        "DIR/Train and DIR/Test, or one folder that gets an "
                        "80/20 split); overrides --dataset")
    p.add_argument("--image-size", type=int, default=256,
                   help="decode size for --data-dir images (HxH)")
    p.add_argument("--num-clients", type=int, default=2)
    p.add_argument("--rounds", type=int, default=1)
    p.add_argument("--epochs", type=int, default=10, help="local epochs per round")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="linear lr warmup steps (0 = reference behavior)")
    p.add_argument("--num-classes", type=int, default=None,
                   help="default: the model's registry default")
    p.add_argument("--plaintext", action="store_true",
                   help="plain FedAvg (no HE) — the cell-6 comparison path")
    p.add_argument("--partition", default="iid", choices=["iid", "label_skew"])
    p.add_argument("--skew-alpha", type=float, default=0.5)
    p.add_argument("--prox-mu", type=float, default=0.0, help="FedProx strength")
    p.add_argument("--no-augment", action="store_true")
    p.add_argument("--client-fusion", default="auto",
                   choices=["auto", "fused", "vmap"],
                   help="cross-client training backend: 'fused' folds the "
                        "client axis into every conv/dense GEMM batch "
                        "(fl.fusion), 'vmap' is the per-client reference, "
                        "'auto' is 'fused' for a model whose client-folded "
                        "forward is lane-packed (resnet20), else 'vmap'")
    p.add_argument("--he-n", type=int, default=4096, help="CKKS ring degree")
    p.add_argument("--he-primes", type=int, default=3, help="RNS limb count")
    # --- quantized bit-interleaved packing (ckks.quantize / README
    # "Packing & precision") ---
    p.add_argument("--pack-bits", type=int, default=0, metavar="B",
                   help="quantize client updates to B bits and bit-"
                        "interleave them k-to-a-CKKS-slot: every HE phase "
                        "and the uplink shrink by the packing factor "
                        "(0 = off, the bit-exact float path)")
    p.add_argument("--pack-interleave", type=int, default=0, metavar="K",
                   help="coefficients per slot (0 = auto: the carry-free "
                        "headroom maximum for the ring and client count)")
    p.add_argument("--pack-clip", type=float, default=None, metavar="C",
                   help="symmetric clip bound on a client's update for the "
                        "quantizer grid (default 0.5); |update| > C "
                        "saturates (counted in encode_overflow, same "
                        "on_overflow machinery)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--n-train", type=int, default=None)
    p.add_argument("--n-test", type=int, default=None)
    p.add_argument("--checkpoint", default=None, help="checkpoint path (.npz)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--save-model", default="agg_model.npz", metavar="PATH",
                   dest="save_model",
                   help="persist the final aggregated model (the reference's "
                        "agg_model.hdf5, always written); --no-save-model "
                        "to disable")
    p.add_argument("--no-save-model", action="store_const", const=None,
                   dest="save_model")
    p.add_argument("--centralized", action="store_true",
                   help="centralized (non-federated) baseline: train one "
                        "model on the whole dataset (train_server analog)")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the first round to DIR "
                        "and print its device seconds by scope, by kernel "
                        "and by program (obs.trace reads them from the "
                        ".xplane.pb of a TPU run)")
    p.add_argument("--events", default=None, metavar="PATH", dest="events",
                   help="structured run-event JSONL (obs.events). Default: "
                        "events.jsonl next to --checkpoint (else ./); "
                        "--no-events or HEFL_EVENTS=0 disables")
    p.add_argument("--no-events", action="store_const", const="",
                   dest="events")
    p.add_argument("--span-trace", default=None, metavar="PATH",
                   dest="span_trace",
                   help="write the run's host spans (obs.spans: set-up, rounds, "
                        "phases and their steps, on the profiler's clock) "
                        "and every streaming round's lifecycle span tree "
                        "(arrival/fold/ship/commit/recovery on the engine's "
                        "virtual clock) as Chrome trace-viewer JSON (.gz "
                        "honored)")
    p.add_argument("--json", action="store_true", help="emit history as JSON lines")
    p.add_argument("--dp-noise", type=float, default=0.0, metavar="SIGMA",
                   help="DP-FedAvg central noise multiplier (0 = off): clip "
                        "client deltas and add distributed Gaussian noise "
                        "inside the encrypted round (fl/dp.py); per-round "
                        "epsilon is reported in the history")
    p.add_argument("--dp-clip", type=float, default=1.0, metavar="C",
                   help="DP-FedAvg L2 clip bound on a client's model delta")
    p.add_argument("--dp-delta", type=float, default=1e-5,
                   help="target delta for the (epsilon, delta) accountant")
    # --- robustness / fault injection (fl/faults.py, README "Robustness") ---
    p.add_argument("--on-overflow", default="warn",
                   choices=["warn", "exclude", "raise"],
                   help="when a client's update saturates the CKKS encode "
                        "envelope: warn (reference behavior), exclude the "
                        "client from the round, or raise")
    p.add_argument("--max-update-norm", type=float, default=0.0, metavar="L2",
                   help="exclude clients whose update L2 norm (vs the "
                        "round's global weights) exceeds this bound "
                        "(0 = no bound)")
    p.add_argument("--drop-fraction", type=float, default=0.0,
                   help="fault injection: fraction of clients scheduled "
                        "out of each round (deterministic, --fault-seed)")
    p.add_argument("--nan-clients", type=int, default=0, metavar="K",
                   help="fault injection: clients per round whose update "
                        "is NaN-poisoned before aggregation")
    p.add_argument("--huge-clients", type=int, default=0, metavar="K",
                   help="fault injection: clients per round whose update "
                        "gets +1e15 on every weight")
    p.add_argument("--straggler-delay", type=float, default=0.0, metavar="S",
                   help="fault injection: max per-round straggler delay "
                        "in seconds (25%% of clients straggle)")
    p.add_argument("--fail-rounds", default="", metavar="R,R,...",
                   help="fault injection: comma-separated round indices "
                        "whose first attempt simulates a device loss "
                        "(exercises --max-round-retries)")
    p.add_argument("--arrival-delay", type=float, default=0.0, metavar="S",
                   help="fault injection: max base dispersion of upload "
                        "arrival times consumed by the streaming engine "
                        "(stragglers add their delay on top)")
    p.add_argument("--duplicate-clients", type=int, default=0, metavar="K",
                   help="fault injection: clients per round whose upload "
                        "is delivered twice (streaming dedups by nonce)")
    p.add_argument("--transient-clients", type=int, default=0, metavar="K",
                   help="fault injection: clients per round whose first "
                        "delivery is lost (recovered by streaming retries)")
    p.add_argument("--permanent-clients", type=int, default=0, metavar="K",
                   help="fault injection: clients per round for whom every "
                        "delivery fails (excluded as unreachable)")
    p.add_argument("--outage-hosts", type=int, default=0, metavar="K",
                   help="fault injection: host rows per round whose whole "
                        "contiguous client block is scheduled out (a "
                        "regional outage); requires --num-hosts H >= 2")
    p.add_argument("--link-loss", type=int, default=0, metavar="K",
                   help="fault injection: tier->root uplinks per round "
                        "whose first ship delivery is LOST (recovered by "
                        "ship retries); requires --num-hosts H >= 2")
    p.add_argument("--link-dark", type=int, default=0, metavar="K",
                   help="fault injection: tier->root uplinks per round "
                        "that lose EVERY ship delivery (the host misses "
                        "the round as host_unreachable); requires "
                        "--num-hosts H >= 2")
    p.add_argument("--link-delay", type=float, default=0.0, metavar="S",
                   help="fault injection: max per-uplink ship delivery "
                        "delay in simulated seconds (drawn per round; "
                        "gated by --ship-deadline); requires --num-hosts")
    p.add_argument("--link-dup", type=int, default=0, metavar="K",
                   help="fault injection: tier->root uplinks per round "
                        "whose ship is delivered TWICE (the root dedups "
                        "by (host, round, sha)); requires --num-hosts")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="PRNG seed of the fault schedule")
    # --- streaming quorum aggregation (fl/stream.py, README "Streaming
    # aggregation & quorum") ---
    p.add_argument("--stream", action="store_true",
                   help="streaming quorum aggregation: arriving encrypted "
                        "updates fold online into a running modular sum; "
                        "rounds commit at --quorum, stragglers carry under "
                        "--staleness instead of stalling the round")
    p.add_argument("--cohort-size", type=int, default=0, metavar="K",
                   help="clients sampled into each round's cohort "
                        "(0 = all; implies --stream semantics)")
    p.add_argument("--quorum", type=float, default=1.0, metavar="Q",
                   help="fraction of the cohort whose arrivals commit the "
                        "round; below it the round degrades gracefully "
                        "(model carried forward, loud event)")
    p.add_argument("--deadline", type=float, default=0.0, metavar="S",
                   help="per-client arrival deadline in simulated seconds "
                        "(0 = none)")
    p.add_argument("--staleness", type=int, default=0, metavar="T",
                   help="bounded-staleness budget: rounds a missed upload "
                        "may carry forward before exclusion as stale")
    p.add_argument("--stream-retries", type=int, default=0, metavar="N",
                   help="redelivery attempts for a lost upload "
                        "(exponential backoff + jitter)")
    p.add_argument("--stream-backoff", type=float, default=0.25, metavar="S",
                   help="base backoff between delivery retries")
    p.add_argument("--stream-seed", type=int, default=0,
                   help="PRNG seed of cohort sampling and retry jitter")
    p.add_argument("--full-cohort-train", action="store_true",
                   help="disable cohort-only training: every registered "
                        "client slot trains each round with unsampled "
                        "clients masked (the historical full-C producer; "
                        "the cohort-only default gathers just the sampled "
                        "cohort's slots, bitwise the same aggregate)")
    p.add_argument("--num-hosts", type=int, default=0, metavar="H",
                   help="hierarchical multi-host aggregation (>= 2): each "
                        "host folds its contiguous client block locally "
                        "and ships ONE partial ciphertext across the "
                        "simulated DCN per round — O(hosts) cross-host "
                        "bytes, bitwise the flat fold; 0 = flat "
                        "single-root aggregation; implies --stream")
    p.add_argument("--host-quorum", type=float, default=1.0, metavar="Q",
                   help="fraction of the round's nonempty host tiers "
                        "whose partials must land at the root to commit; "
                        "below it the round degrades like a missed client "
                        "quorum; requires --num-hosts H >= 2")
    p.add_argument("--ship-deadline", type=float, default=0.0, metavar="S",
                   help="per-round tier->root ship deadline in simulated "
                        "seconds from the client-quorum commit point "
                        "(0 = none; retried deliveries are exempt); "
                        "requires --num-hosts H >= 2")
    p.add_argument("--host-staleness", type=int, default=0, metavar="T",
                   help="tier staleness budget: rounds a host partial "
                        "that missed its ship may carry forward to fold "
                        "as a stale tier fold before its clients are "
                        "excluded as host_stale; requires --num-hosts")
    p.add_argument("--mesh-ct", type=int, default=0, metavar="K",
                   help="2-D (clients, ct) round mesh: give each client "
                        "block K devices that split its in-round "
                        "ciphertext rows (bitwise-identical rounds, HE "
                        "throughput x K); 0 = the 1-D client mesh")
    # --- hybrid-HE symmetric uplink (hefl_tpu/hhe, README "Hybrid HE
    # uplink") ---
    p.add_argument("--hhe", action="store_true",
                   help="hybrid-HE uplink: clients encrypt their packed "
                        "quantized update under a per-client symmetric "
                        "stream cipher (~1x wire bytes, no client-side "
                        "NTTs) and the server transciphers into CKKS "
                        "before the quorum fold; requires --pack-bits and "
                        "implies --stream")
    p.add_argument("--hhe-key-seed", type=int, default=0, metavar="S",
                   help="enrollment seed of the per-client symmetric "
                        "master-key derivation (hhe.derive_client_keys)")
    # --- durable aggregation service (fl/journal.py + fl/server.py,
    # README "Durable aggregation & crash recovery") ---
    p.add_argument("--serve", action="store_true",
                   help="recover-then-serve lifecycle: wrap the streaming "
                        "engine in a write-ahead round journal (default "
                        "path next to --checkpoint) and auto-resume from "
                        "an existing checkpoint — re-running the same "
                        "command after a crash recovers exactly")
    p.add_argument("--journal-path", default=None, metavar="PATH",
                   help="write-ahead round journal (fl.journal): every "
                        "engine transition is durable and a restarted "
                        "server replays it to the bitwise state of an "
                        "uninterrupted run; requires a streaming knob")
    p.add_argument("--fsync-policy", default=None,
                   choices=["always", "commit", "never"],
                   help="journal fsync policy: every append / transaction "
                        "boundaries (commit, degrade, round_close) / "
                        "OS-paced. Default: HEFL_JOURNAL_FSYNC, else "
                        "'commit'")
    p.add_argument("--crash-round", type=int, default=None, metavar="R",
                   help="crash injection: simulate a server process crash "
                        "during round R (requires the journal). Re-running "
                        "WITHOUT the crash flags always recovers; an armed "
                        "mid_append/pre_commit crash (whose record never "
                        "landed) fires again on every run")
    p.add_argument("--crash-at", default="post_fold",
                   choices=["mid_append", "post_fold", "pre_commit",
                            "post_commit", "post_close"],
                   help="crash injection boundary: mid-journal-append "
                        "(leaves a REAL torn record), after the Nth fold, "
                        "before/after the commit record, or after the "
                        "round seals (before its checkpoint)")
    p.add_argument("--crash-after-folds", type=int, default=1, metavar="N",
                   help="which fold (1-based) triggers "
                        "mid_append/post_fold crashes")
    p.add_argument("--dp-min-surviving", type=int, default=0, metavar="K",
                   help="dp noise floor: calibrate each client's noise "
                        "share to K surviving clients (conservative "
                        "over-noising for partial participation; 0 = "
                        "full-participation calibration, auto-derived "
                        "from the schedule/quorum under faults/streaming)")
    p.add_argument("--max-round-retries", type=int, default=0,
                   help="retry a failed round this many times with "
                        "exponential backoff, auto-resuming from the "
                        "--checkpoint when one matches the round")
    p.add_argument("--retry-backoff", type=float, default=0.5, metavar="S",
                   help="base backoff between round retries (doubles per "
                        "attempt)")
    return p


def _packing_from_args(args: argparse.Namespace) -> "PackingConfig | None":
    """--pack-bits gates the whole feature; the sibling knobs without it
    would be SILENTLY ignored (a run the user believes is packed but
    isn't), so that combination fails loudly instead."""
    if args.pack_bits <= 0:
        if args.pack_interleave or args.pack_clip is not None:
            raise SystemExit(
                "--pack-interleave/--pack-clip have no effect without "
                "--pack-bits; add --pack-bits B to enable packing"
            )
        return None
    return PackingConfig(
        bits=args.pack_bits,
        interleave=args.pack_interleave,
        clip=0.5 if args.pack_clip is None else args.pack_clip,
    )


def config_from_args(args: argparse.Namespace) -> ExperimentConfig:
    num_classes = (
        args.num_classes
        if args.num_classes is not None
        else MODEL_REGISTRY[args.model][1]
    )
    fail_rounds = tuple(
        int(r) for r in args.fail_rounds.split(",") if r.strip()
    )
    any_fault = (
        args.drop_fraction > 0
        or args.nan_clients > 0
        or args.huge_clients > 0
        or args.straggler_delay > 0
        or args.arrival_delay > 0
        or args.duplicate_clients > 0
        or args.transient_clients > 0
        or args.permanent_clients > 0
        or args.outage_hosts > 0
        or args.link_loss > 0
        or args.link_dark > 0
        or args.link_delay > 0
        or args.link_dup > 0
        or fail_rounds
    )
    if args.outage_hosts > 0 and args.num_hosts < 2:
        raise SystemExit(
            "--outage-hosts darkens host rows of the hierarchical "
            "topology; add --num-hosts H (>= 2) to define the rows"
        )
    link_faults = (
        args.link_loss > 0
        or args.link_dark > 0
        or args.link_delay > 0
        or args.link_dup > 0
    )
    if link_faults and args.num_hosts < 2:
        raise SystemExit(
            "--link-loss/--link-dark/--link-delay/--link-dup fault the "
            "tier->root uplinks of the hierarchical topology; add "
            "--num-hosts H (>= 2) to define the uplinks"
        )
    if (
        args.host_quorum != 1.0
        or args.ship_deadline > 0
        or args.host_staleness > 0
    ) and args.num_hosts < 2:
        raise SystemExit(
            "--host-quorum/--ship-deadline/--host-staleness govern the "
            "tier->root uplink of the hierarchical fold tree; add "
            "--num-hosts H (>= 2) to define the tiers"
        )
    faults = (
        FaultConfig(
            seed=args.fault_seed,
            drop_fraction=args.drop_fraction,
            nan_clients=args.nan_clients,
            huge_clients=args.huge_clients,
            straggler_fraction=0.25 if args.straggler_delay > 0 else 0.0,
            straggler_delay_s=args.straggler_delay,
            fail_rounds=fail_rounds,
            arrival_delay_s=args.arrival_delay,
            duplicate_clients=args.duplicate_clients,
            transient_fail_clients=args.transient_clients,
            permanent_fail_clients=args.permanent_clients,
            outage_hosts=args.outage_hosts,
            link_loss_hosts=args.link_loss,
            link_dark_hosts=args.link_dark,
            link_delay_s=args.link_delay,
            link_dup_hosts=args.link_dup,
            num_hosts=(
                args.num_hosts
                if (args.outage_hosts > 0 or link_faults)
                else 0
            ),
        )
        if any_fault
        else None
    )
    want_stream = (
        args.stream
        or args.hhe
        or args.cohort_size > 0
        or args.quorum < 1.0
        or args.deadline > 0
        or args.staleness > 0
        or args.stream_retries > 0
        or args.num_hosts > 0
    )
    if args.num_hosts == 1:
        raise SystemExit(
            "--num-hosts 1 is the flat single-root fold; use 0 (flat) or "
            ">= 2 (hierarchical multi-host aggregation)"
        )
    if args.hhe and args.pack_bits <= 0:
        # The symmetric cipher lives in the packed integer domain; without
        # packing there is nothing for the keystream to add to. Fail at
        # the flag layer (same pattern as the packing siblings) instead of
        # deep inside run_experiment.
        raise SystemExit(
            "--hhe ships the PACKED quantized update under the stream "
            "cipher; add --pack-bits B to enable packing"
        )
    if args.hhe_key_seed and not args.hhe:
        raise SystemExit(
            "--hhe-key-seed has no effect without --hhe; add --hhe to "
            "enable the hybrid-HE uplink"
        )
    arrival_faults = (
        args.arrival_delay > 0
        or args.duplicate_clients > 0
        or args.transient_clients > 0
        or args.permanent_clients > 0
    )
    if arrival_faults and not want_stream:
        # Arrival-level faults only exist on the streaming engine's
        # timeline; the synchronous driver would SILENTLY inject nothing —
        # a chaos run the user believes ran but didn't. Fail loudly (same
        # pattern as the packing flags).
        raise SystemExit(
            "--arrival-delay/--duplicate-clients/--transient-clients/"
            "--permanent-clients are consumed by the streaming engine; "
            "add --stream (or another streaming knob) to enable it"
        )
    if (args.journal_path or args.serve) and not want_stream:
        # The journal records streaming-engine transitions; without a
        # streaming knob it would SILENTLY provide no durability — the
        # worst failure mode for a flag named --serve.
        raise SystemExit(
            "--journal-path/--serve wrap the streaming engine; add "
            "--stream (or another streaming knob) to enable it"
        )
    if args.crash_round is not None and not (args.journal_path or args.serve):
        raise SystemExit(
            "--crash-round without a write-ahead journal is just data "
            "loss; add --journal-path PATH or --serve"
        )
    if args.crash_round is None and (
        args.crash_at != "post_fold" or args.crash_after_folds != 1
    ):
        raise SystemExit(
            "--crash-at/--crash-after-folds have no effect without "
            "--crash-round R; add it to arm the crash injection"
        )
    if args.dp_min_surviving > 0 and args.dp_noise <= 0:
        # Same silent-no-op guard: a declared noise floor without dp
        # enabled would be dropped without a word.
        raise SystemExit(
            "--dp-min-surviving has no effect without --dp-noise; add "
            "--dp-noise SIGMA to enable dp"
        )
    if args.full_cohort_train and not want_stream:
        raise SystemExit(
            "--full-cohort-train has no effect without a streaming knob; "
            "add --stream (or --cohort-size K) to enable the engine"
        )
    stream = (
        StreamConfig(
            cohort_size=args.cohort_size,
            cohort_only=not args.full_cohort_train,
            quorum=args.quorum,
            deadline_s=args.deadline,
            max_retries=args.stream_retries,
            retry_backoff_s=args.stream_backoff,
            staleness_rounds=args.staleness,
            seed=args.stream_seed,
            num_hosts=args.num_hosts,
            host_quorum=args.host_quorum,
            ship_deadline_s=args.ship_deadline,
            host_staleness_rounds=args.host_staleness,
            upload_kind="hhe" if args.hhe else "ckks",
        )
        if want_stream
        else None
    )
    return ExperimentConfig(
        model=args.model,
        dataset=args.dataset,
        data_dir=args.data_dir,
        image_size=(args.image_size, args.image_size),
        num_clients=args.num_clients,
        rounds=args.rounds,
        encrypted=not args.plaintext,
        partition=args.partition,
        skew_alpha=args.skew_alpha,
        train=TrainConfig(
            epochs=args.epochs,
            batch_size=args.batch_size,
            lr=args.lr,
            warmup_steps=args.warmup_steps,
            prox_mu=args.prox_mu,
            augment=not args.no_augment,
            client_fusion=args.client_fusion,
            num_classes=num_classes,
            on_overflow=args.on_overflow,
            max_update_norm=args.max_update_norm,
        ),
        he=HEConfig(n=args.he_n, num_primes=args.he_primes),
        packing=_packing_from_args(args),
        seed=args.seed,
        n_train=args.n_train,
        n_test=args.n_test,
        checkpoint_path=args.checkpoint,
        profile_dir=args.profile,
        save_model_path=args.save_model,
        centralized=args.centralized,
        dp=(
            DpConfig(
                clip_norm=args.dp_clip,
                noise_multiplier=args.dp_noise,
                delta=args.dp_delta,
                min_surviving=args.dp_min_surviving,
            )
            if args.dp_noise > 0
            else None
        ),
        faults=faults,
        stream=stream,
        hhe=HheConfig(key_seed=args.hhe_key_seed) if args.hhe else None,
        journal_path=args.journal_path,
        fsync_policy=args.fsync_policy,
        serve=args.serve,
        crash=(
            CrashConfig(
                round=args.crash_round,
                at=args.crash_at,
                after_folds=args.crash_after_folds,
            )
            if args.crash_round is not None
            else None
        ),
        max_round_retries=args.max_round_retries,
        retry_backoff_s=args.retry_backoff,
        events_path=args.events,
        span_trace_path=args.span_trace,
        mesh_ct=args.mesh_ct,
    )


def main(argv: list[str] | None = None) -> int:
    # Persistent XLA compilation cache: repeated CLI runs must not re-pay
    # the round program's compile.
    from hefl_tpu.utils.device import setup_compile_cache

    setup_compile_cache()
    args = build_parser().parse_args(argv)
    if args.preset is not None:
        from hefl_tpu.presets import PRESETS

        if args.preset not in PRESETS:
            raise SystemExit(
                f"unknown preset {args.preset!r}; available: {sorted(PRESETS)}"
            )
        cfg = PRESETS[args.preset]
    else:
        cfg = config_from_args(args)
    # Pre-flight static analysis (ISSUE 8): reject a statically-unsafe
    # config (packing headroom, aggregation bounds) BEFORE dataset and
    # compile work, with the offending op named. run_experiment re-checks
    # (cached certificates make that free) so programmatic callers get
    # the same guarantee.
    from hefl_tpu import analysis

    try:
        analysis.check_experiment(cfg)
    except analysis.AnalysisError as e:
        raise SystemExit(f"hefl-lint: {e}")
    out = run_experiment(cfg, resume=args.resume, verbose=not args.json)
    if args.json:
        for rec in out["history"]:
            print(json.dumps(rec))
    if cfg.profile_dir is not None:
        print_trace_attribution(cfg.profile_dir, as_json=args.json)
    return 0


def print_trace_attribution(profile_dir: str, as_json: bool = False) -> None:
    """The traced round's device seconds (`obs.trace.trace_attribution`), as
    a table or as one JSON line. A CPU run's trace has no device plane to
    read: that is said, and is no error of the run."""
    from hefl_tpu.obs import trace as obs_trace

    try:
        rec = obs_trace.trace_attribution(profile_dir)
    except (obs_trace.NoDevicePlane, obs_trace.NoScopeMetadata) as e:
        print(f"no device seconds by scope: {e}", file=sys.stderr)
        return
    print(json.dumps({"trace_attribution": rec}) if as_json
          else obs_trace.format_table(rec))


if __name__ == "__main__":
    raise SystemExit(main())
