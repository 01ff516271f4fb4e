"""Training hyperparameter config.

Defaults reproduce the reference exactly (SURVEY.md §2.1, §2.5):
Adam(lr=1e-3, decay=1e-4) + categorical CE (FLPyfhelin.py:140-141), 10
local epochs, batch 32, EarlyStopping(patience=5, restore_best_weights)
(:186), ReduceLROnPlateau(patience=2, factor=0.3, min_lr=1e-6) (:167,188),
best-checkpoint by accuracy (:169), validation_split=0.1 (:97).
`prox_mu > 0` enables the FedProx proximal term (BASELINE.json config 4).
"""

from __future__ import annotations

import dataclasses

# Re-exported here because this module is the FL-layer's config surface:
# PackingConfig (quantized bit-interleaved CKKS packing — bits, interleave
# factor, clip, guard, error budget) is DEFINED next to the quantizer it
# parameterizes (ckks.quantize) but threads through TrainConfig's siblings
# into fl.secure's encrypt/psum/decrypt paths and ExperimentConfig.
# HheConfig (the hybrid-HE symmetric-uplink key knobs, ISSUE 11) lives next
# to its cipher (hhe.cipher) for the same reason.
from hefl_tpu.ckks.quantize import PackingConfig  # noqa: F401
from hefl_tpu.hhe.cipher import HheConfig  # noqa: F401


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    epochs: int = 10
    batch_size: int = 32
    lr: float = 1e-3
    lr_decay: float = 1e-4          # Keras-style: lr_t = lr / (1 + decay*step)
    warmup_steps: int = 0           # linear lr ramp (0 = reference behavior)
    val_fraction: float = 0.1
    es_patience: int = 5            # early stopping on val loss
    plateau_patience: int = 2       # ReduceLROnPlateau on val loss
    plateau_factor: float = 0.3
    min_lr: float = 1e-6
    min_delta: float = 0.0
    prox_mu: float = 0.0            # FedProx; 0 = plain FedAvg
    augment: bool = True
    aug_shear: float = 0.2
    aug_zoom: float = 0.2
    aug_flip: bool = True
    # Row-shift backend for the augment affine ("gather" | "fft" | "dft");
    # None takes data.augment.resolve_shift_backend's rule: "dft" on a TPU,
    # "gather" elsewhere.
    aug_backend: str | None = None
    num_classes: int = 2
    # Micro-batch accumulation: each optimizer step runs ONE fused
    # forward/backward over `accum_steps` micro-batches of `batch_size`
    # (mean loss over the union == mean of per-micro-batch gradients), so
    # the MXU sees GEMMs `accum_steps`x larger. The Adam/decay update math
    # is untouched; the schedule just advances once per fused batch, so
    # >1 trades optimizer steps for arithmetic intensity (documented in
    # README "Perf knobs"). 1 reproduces the reference exactly.
    accum_steps: int = 1
    # Steps-major flattened local-training scan (one scan over E*S steps,
    # permutations/one-hot hoisted out of the step body) vs the historical
    # nested scan-of-scans. Same math, same RNG stream; the flag exists so
    # the equivalence stays testable (tests/test_perf.py).
    flat_scan: bool = True
    # Cross-client training backend (fl.fusion): "fused" reshapes the
    # client axis into the batch axis of every conv/dense (one GEMM stream
    # of effective batch C*B per layer, per-client weights via
    # batch-grouped convs / batched GEMMs), "vmap" is the per-client vmap
    # reference. "auto" (default) resolves to "fused" for a model whose
    # client-folded forward packs the clients into the lanes (ResNet20) and
    # to "vmap" otherwise (fl.fusion.resolve_fusion_backend). Same math,
    # same RNG streams, same callback semantics on both backends
    # (tests/test_perf.py pins it).
    # A token model over a frozen base (models/lm/) is trained one client
    # after another whatever this says ("auto" only; fl.fusion says why).
    client_fusion: str = "auto"
    # --- update sanitization (fl.faults / the participation-masked round
    # engine). Both knobs default OFF so the historical all-clients-present
    # round programs (and their seeds) are untouched; turning either on
    # forces the masked engine — which also applies the NaN/Inf filter —
    # on EVERY round. (With both off, a faulted run's clean-schedule
    # rounds take the bit-for-bit legacy fast path, which traces no
    # predicates; RoundMeta.sanitized records which route ran.)
    #
    # What to do when a client's trained weights saturate the CKKS encode
    # envelope (encode_overflow > 0): "warn" keeps the reference behavior
    # (aggregate + log), "exclude" drops the client from the round inside
    # the jitted program, "raise" aborts the experiment.
    on_overflow: str = "warn"
    # L2 bound on a client's update (delta vs the round's global weights):
    # a finite update with a larger norm is excluded from aggregation.
    # 0 disables the bound.
    max_update_norm: float = 0.0

    def __post_init__(self):
        if self.on_overflow not in ("warn", "exclude", "raise"):
            raise ValueError(
                f"on_overflow={self.on_overflow!r}: must be one of "
                "'warn' | 'exclude' | 'raise'"
            )
        if self.client_fusion not in ("auto", "fused", "vmap"):
            raise ValueError(
                f"client_fusion={self.client_fusion!r}: must be one of "
                "'auto' | 'fused' | 'vmap'"
            )


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Streaming quorum-aggregation knobs (fl.stream; frozen => hashable,
    rides in ExperimentConfig).

    Defined here — not next to the engine — because stream.py imports the
    FL layer's round machinery and the config surface must stay cycle-free
    (the same reason PackingConfig lives with its quantizer and is
    re-exported here).

    cohort_size:      clients sampled into each round's cohort
                      (deterministic PRNG; 0 = every client, i.e. full
                      participation remains available but is no longer
                      assumed).
    quorum:           fraction of the cohort whose arrivals COMMIT the
                      round (the round closes as soon as
                      ceil(quorum * cohort) fresh uploads have folded);
                      below quorum the round degrades gracefully — the
                      global model carries forward with a loud
                      round_robust/stream_round event.
    deadline_s:       per-client arrival deadline (0 = none): an upload
                      arriving after it cannot fold fresh this round — it
                      is carried under the staleness budget or dropped.
                      Server-solicited RETRIES may land after the deadline
                      and still fold (the server extended the round for
                      them).
    max_retries:      redelivery attempts for a LOST upload (exponential
                      backoff + jitter); 0 = lost means gone.
    retry_backoff_s:  base backoff between delivery retries (doubles per
                      attempt).
    retry_jitter:     +/- fraction of each backoff drawn from a
                      deterministic per-(round, client, attempt) PRNG —
                      de-synchronizes retry storms, reproducibly.
    staleness_rounds: bounded-staleness budget tau: how many rounds a
                      missed upload may carry forward before it is
                      excluded as "stale" (0 = synchronous semantics:
                      missed means dropped with cause "timeout").
    cohort_only:      train ONLY the sampled cohort's client slots
                      (ISSUE 15): the engine gathers the cohort's data/
                      key/mask rows before the fused GEMM stream, padded
                      up a small power-of-two bucket ladder
                      (fl.fedavg.cohort_bucket) so the no-new-compile
                      guarantee holds within a bucket, and scatters the
                      trained slots back — the committed aggregate is
                      BITWISE equal to the historical full-C masked path
                      at the same cohort, but compute scales with the
                      cohort instead of the registry. False restores the
                      full-C producer (every registered slot trains,
                      unsampled ones masked) — the reference the equality
                      gates run against.
                      Unsampled clients carry zero metrics rows under
                      cohort-only (they trained nothing).
    seed:             PRNG seed of cohort sampling and retry jitter
                      (independent of both the experiment seed and the
                      fault-schedule seed).
    time_scale:       real seconds slept per simulated second of arrival
                      waiting (under the hefl.quorum_wait host
                      TraceAnnotation). 0 = fully virtual clock: the
                      arrival timeline is simulated exactly but the driver
                      never sleeps — the CI/chaos default.
    num_hosts:        host rows of the simulated multi-host deployment
                      (ISSUE 16). 0 or 1 = the flat single-root fold
                      (the historical engine); >= 2 makes the engine
                      aggregate through `fl.hierarchy`'s two-tier fold
                      tree — each host folds its contiguous client block
                      locally and ships ONE partial ciphertext across the
                      simulated DCN, so cross-host traffic is O(hosts)
                      instead of O(cohort). The committed aggregate is
                      BITWISE equal to the flat fold (certified by
                      analysis.certify_fold_tree, measured by the
                      BENCH_DCN / chaos gates). Part of the journal's
                      config echo.
    host_quorum:      tier-level quorum H_Q (ISSUE 17): fraction of the
                      round's SHIPPING hosts (tiers that folded at least
                      one upload) whose partials must land at the root
                      for the round to commit — the hierarchical analog
                      of `quorum`. Below it the round degrades exactly
                      like a sub-quorum flat round (model carried,
                      encryption-of-zero, degraded_reason="host_quorum").
                      1.0 (default) = every shipping host must land, the
                      PR-16 lossless-DCN semantics. Requires
                      num_hosts >= 2.
    ship_deadline_s:  per-round tier->root ship deadline, measured from
                      the round's client-quorum commit point (0 = none):
                      a ship delivery landing after it cannot fold at
                      the root this round — the host is excluded
                      per-cause ("host_timeout") and its sealed partial
                      carries under `host_staleness_rounds` or is
                      dropped. Ship RETRIES (redeliveries of a LOST
                      ship) may land after the deadline and still fold,
                      mirroring the client-level retry contract.
                      Requires num_hosts >= 2.
    host_staleness_rounds:
                      tier-level bounded-staleness budget: how many
                      rounds a host's sealed partial that missed its
                      round's ship may carry forward as a STALE TIER
                      FOLD (one extra instance of the certified fold
                      loop at the root — analysis.certify_fold_tree's
                      carried-partial fact) before its clients are
                      excluded as "host_stale". 0 = synchronous DCN
                      semantics: a missed ship is dropped. Refused with
                      dp for the same reason as `staleness_rounds` (a
                      carried partial doubles its clients' accounted
                      per-round sensitivity). Requires num_hosts >= 2.
    upload_kind:      what the clients put on the wire (ISSUE 11):
                      "ckks" (the historical packed/float CKKS ciphertext)
                      or "hhe" — a symmetric stream-cipher encryption of
                      the PACKED quantized update (~1x wire expansion, no
                      client-side NTTs; requires a PackingConfig), which
                      the server transciphers into CKKS (hhe.transcipher)
                      before the quorum fold so everything downstream —
                      dedup, staleness, journal — is unchanged. Part of
                      the journal's config echo, so recovering an HHE
                      journal under a ckks config fails loudly.
    """

    cohort_size: int = 0
    cohort_only: bool = True
    quorum: float = 1.0
    deadline_s: float = 0.0
    max_retries: int = 0
    retry_backoff_s: float = 0.25
    retry_jitter: float = 0.25
    staleness_rounds: int = 0
    seed: int = 0
    time_scale: float = 0.0
    num_hosts: int = 0
    host_quorum: float = 1.0
    ship_deadline_s: float = 0.0
    host_staleness_rounds: int = 0
    upload_kind: str = "ckks"

    def __post_init__(self):
        if self.upload_kind not in ("ckks", "hhe"):
            raise ValueError(
                f"StreamConfig.upload_kind={self.upload_kind!r}: must be "
                "'ckks' or 'hhe'"
            )
        if not 0.0 < self.quorum <= 1.0:
            raise ValueError(
                f"StreamConfig.quorum={self.quorum}: must be in (0, 1]"
            )
        for name in ("cohort_size", "deadline_s", "max_retries",
                     "retry_backoff_s", "staleness_rounds", "time_scale",
                     "num_hosts", "ship_deadline_s", "host_staleness_rounds"):
            if getattr(self, name) < 0:
                raise ValueError(f"StreamConfig.{name} must be >= 0")
        if self.num_hosts == 1:
            raise ValueError(
                "StreamConfig.num_hosts=1: one host IS the flat fold — "
                "use 0 (flat) or >= 2 (hierarchical)"
            )
        if not 0.0 < self.host_quorum <= 1.0:
            raise ValueError(
                f"StreamConfig.host_quorum={self.host_quorum}: must be in "
                "(0, 1] (a fraction of the round's shipping hosts)"
            )
        if self.num_hosts < 2 and (
            self.host_quorum != 1.0
            or self.ship_deadline_s > 0
            or self.host_staleness_rounds > 0
        ):
            raise ValueError(
                "StreamConfig.host_quorum/ship_deadline_s/"
                "host_staleness_rounds describe the tier->root uplink of "
                "the hierarchical fold tree and would be silent no-ops on "
                "the flat engine — set num_hosts >= 2 to define the tiers"
            )
        if not 0.0 <= self.retry_jitter <= 1.0:
            raise ValueError(
                f"StreamConfig.retry_jitter={self.retry_jitter}: must be "
                "in [0, 1] (a fraction of the backoff)"
            )
