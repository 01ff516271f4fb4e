"""Streaming quorum aggregation: deadline-driven cohorts, bounded staleness.

The reference pipeline — and until this module, this repo's driver — is
one-round-everyone-arrives FedAvg: materialize every client's ciphertext,
psum, wait for the slowest straggler (`time.sleep` in experiment.py). That
synchronous assumption is the last blocker between "benchmark loop" and
the ROADMAP's million-client aggregation service: one slow client stalls
the whole round, and the full [C, n_ct, L, N] ciphertext block scales
memory linearly with the cohort.

CKKS addition is associative and commutative over exact residues mod p,
so neither assumption is load-bearing. This module replaces them:

  * `sample_cohort` — per-round cohorts drawn by a deterministic PRNG:
    partial participation is the DEFAULT regime, not a fault. With
    `StreamConfig.cohort_only` (the default, ISSUE 15) compute follows:
    only the sampled cohort's client slots are gathered and trained
    (power-of-two bucket ladder, no-new-compile within a bucket), and
    the committed aggregate stays BITWISE equal to the full-C masked
    producer at the same cohort.
  * `OnlineAccumulator` — each arriving encrypted update folds into a
    running modular sum: O(1) memory in cohort size, and — because every
    fold is exact arithmetic mod p — BITWISE equal to the batched
    psum-of-limbs whatever the arrival order (hash-gated in
    tests/test_stream.py and the chaos smoke). Duplicate deliveries dedup
    idempotently by (client, round) nonce.
  * `StreamEngine` — the round lifecycle: every cohort client carries a
    delivery deadline; a LOST upload is retried with exponential backoff
    and deterministic jitter; an upload that misses the round's commit is
    carried into the next round under a bounded-staleness budget tau
    (beyond tau it is excluded as "stale", attributed through the PR-2
    exclusion bitmask) or dropped; the round COMMITS as soon as a quorum
    Q of the cohort has arrived, and degrades gracefully below quorum
    (global model carried forward with a loud event — exactly the
    all-excluded-round semantics the driver already has).

The arrival timeline is SIMULATED on a virtual clock from the
deterministic fault schedule (fl.faults.schedule_arrivals): the engine
consumes per-client arrival times instead of the driver sleeping out the
max straggler delay, so chaos runs are both faster and richer
(duplicates, transient/permanent failures, cross-round arrivals).
`StreamConfig.time_scale` optionally maps simulated waiting onto real
wall-clock (slept under the hefl.quorum_wait host TraceAnnotation, the
same host_rows contract as hefl.straggler_wait).

Simulation vs service: the per-client uploads are produced here by ONE
batched SPMD program (`produce_uploads` — the same train/sanitize/encrypt
body as fl.secure's round, minus the psum), because the clients are
simulated in-process; a real deployment feeds network arrivals to the
same `OnlineAccumulator.fold` interface and the aggregation memory stays
O(1) either way.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from hefl_tpu.ckks.ops import Ciphertext
from hefl_tpu.fl.config import StreamConfig, TrainConfig
from hefl_tpu.fl.dp import calibration_clients
from hefl_tpu.fl.faults import (
    EXCLUDED_HOST_STALE,
    EXCLUDED_HOST_TIMEOUT,
    EXCLUDED_HOST_UNREACHABLE,
    EXCLUDED_NONFINITE,
    EXCLUDED_NORM,
    EXCLUDED_OVERFLOW,
    EXCLUDED_STALE,
    EXCLUDED_TIMEOUT,
    EXCLUDED_UNREACHABLE,
    EXCLUDED_UNSAMPLED,
    EXCLUSION_CAUSES,
    RoundMeta,
    schedule_arrivals,
    schedule_for_round,
    schedule_links,
)
from hefl_tpu.fl.fedavg import (
    _mask_inputs,
    _round_geometry,
    cohort_bucket,
    cohort_gather_index,
    replicate_on,
)
from hefl_tpu.obs import events as obs_events
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import scopes as obs_scopes
from hefl_tpu.obs import spans as obs_spans
from hefl_tpu.parallel import (
    client_axes,
    client_mesh_size,
    ct_shard_count,
    host_of_clients,
    shard_map,
)

# In-program sanitization causes: an upload whose bits carry any of these
# ARRIVES but is rejected at the accumulator (the sanitizer's verdict is
# part of the upload's validity, not of its delivery).
_REJECT_MASK = EXCLUDED_NONFINITE | EXCLUDED_NORM | EXCLUDED_OVERFLOW

# The staleness histogram ("rounds late" per folded upload) uses the
# registry's default bucket bounds — one source, obs.metrics.

# First-class latency distributions (ISSUE 20): commit latency is the
# virtual seconds from round open to the quorum-th fresh fold;
# arrival-to-fold is each folded upload's position on the same axis (how
# long into the round it landed — retries and stale carries push the
# tail). Both are virtual-clock seconds, so the bounds track the fault
# schedules' arrival spreads, not process wall time.
_COMMIT_LATENCY_BUCKETS = (
    0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0
)
_ARRIVAL_TO_FOLD_BUCKETS = _COMMIT_LATENCY_BUCKETS


# ---------------------------------------------------------------------------
# Cohort scheduler
# ---------------------------------------------------------------------------


def sample_cohort(
    stream: StreamConfig, round_index: int, num_clients: int
) -> np.ndarray:
    """The round's cohort: sorted client indices, drawn without replacement
    by a PRNG keyed on (stream.seed, round_index, 2) — deterministic,
    independent of call order and of the fault schedule's streams."""
    size = int(stream.cohort_size)
    if size <= 0 or size >= num_clients:
        return np.arange(num_clients)
    rng = np.random.default_rng([int(stream.seed), int(round_index), 2])
    return np.sort(rng.choice(num_clients, size, replace=False))


def quorum_count(stream: StreamConfig, cohort_size: int) -> int:
    """Fresh arrivals needed to commit: ceil(quorum * cohort), floor 1."""
    return max(1, int(math.ceil(stream.quorum * cohort_size)))


# ---------------------------------------------------------------------------
# Online accumulator: the O(1)-memory streaming half of the aggregation.
# ---------------------------------------------------------------------------


class OnlineAccumulator:
    """Running modular sum of ciphertext uploads, folded one arrival at a
    time.

    Each fold is an exact canonical addition mod p of uint32 RNS residues
    (int64 intermediate, so no wraparound at any prime size), which makes
    the running sum BITWISE equal to fl.secure's batched lazy-sum/psum
    over the same uploads in any arrival order — modular addition is
    associative and commutative, and every representation here is the
    canonical residue. Duplicate deliveries are rejected idempotently by
    nonce. Memory is O(1) in the number of uploads: one [n_ct, L, N]
    residue pair, however many clients fold.
    """

    def __init__(self, p: np.ndarray):
        self.p = np.asarray(p, dtype=np.int64)
        self._c0: np.ndarray | None = None
        self._c1: np.ndarray | None = None
        self._nonces: set = set()
        self.folded = 0
        self.duplicates = 0

    def _add(self, acc, row):
        return (
            (acc.astype(np.int64) + np.asarray(row, dtype=np.int64)) % self.p
        ).astype(np.uint32)

    def fold(self, nonce, c0, c1) -> bool:
        """Fold one upload; False (and count a duplicate) if its nonce was
        already folded — redelivery must be idempotent."""
        if nonce in self._nonces:
            self.duplicates += 1
            return False
        self._nonces.add(nonce)
        if self._c0 is None:
            # Canonicalize the first upload too (producer rows already are;
            # this keeps the invariant independent of the caller).
            z = np.zeros_like(np.asarray(c0, dtype=np.uint32))
            self._c0, self._c1 = self._add(z, c0), self._add(z, c1)
        else:
            self._c0 = self._add(self._c0, c0)
            self._c1 = self._add(self._c1, c1)
        self.folded += 1
        return True

    def fold_batch(self, nonces, c0_batch, c1_batch) -> int:
        """Fold a BATCH of arrivals in one vectorized dispatch (ISSUE 19,
        the server hot path at load): sum the batch's uint32 rows in int64
        and take ONE modular reduction, then fold the batch sum into the
        running accumulator.

        BITWISE-equal to folding the same uploads one at a time in any
        order: every row is a canonical residue < p < 2**32, so the int64
        batch sum is exact for any realistic batch (< 2**31 rows) and
        `((a % p) + (b % p)) % p == (a + b) % p` — associativity of
        modular addition is the same fact the one-at-a-time fold's
        equality with the batched psum already rests on (pinned by
        tests/test_stream.py). Duplicate nonces — against the window AND
        within the batch — are rejected idempotently exactly like
        `fold`'s, first occurrence wins. -> number of uploads folded.
        """
        fresh_rows = []
        for i, nonce in enumerate(nonces):
            if nonce in self._nonces:
                self.duplicates += 1
                continue
            self._nonces.add(nonce)
            fresh_rows.append(i)
        if not fresh_rows:
            return 0
        idx = np.asarray(fresh_rows, dtype=np.int64)
        b0 = np.asarray(c0_batch, dtype=np.int64)[idx]
        b1 = np.asarray(c1_batch, dtype=np.int64)[idx]
        s0 = (b0.sum(axis=0) % self.p).astype(np.uint32)
        s1 = (b1.sum(axis=0) % self.p).astype(np.uint32)
        if self._c0 is None:
            z = np.zeros_like(s0)
            self._c0, self._c1 = self._add(z, s0), self._add(z, s1)
        else:
            self._c0 = self._add(self._c0, s0)
            self._c1 = self._add(self._c1, s1)
        self.folded += len(fresh_rows)
        return len(fresh_rows)

    def value(self, like_shape=None) -> tuple[np.ndarray, np.ndarray]:
        """The running sum (canonical residues); zeros of `like_shape` when
        nothing folded (the encryption-of-zero an empty round yields)."""
        if self._c0 is None:
            if like_shape is None:
                raise ValueError(
                    "OnlineAccumulator.value: nothing folded and no shape"
                )
            z = np.zeros(like_shape, np.uint32)
            return z, z.copy()
        return self._c0, self._c1


def exact_int_probes() -> dict:
    """Shaped jaxpr probes of the online fold's declared exact-integer
    regions (ISSUE 8/12, analysis.lint). `OnlineAccumulator._add` runs
    host-side in numpy; these jax mirrors trace the same arithmetic (the
    `%` is the allowlisted host-side modulo — see analysis.lint.ALLOWLIST)
    so the no-float / no-stray-div rules still watch the fold's math. The
    int32 carrier is sound here for the same reason the fold is exact:
    two canonical residues < 2**27 sum below 2**28. The `fold_loop`
    region is the ARRIVAL-LOOP form (fold_loop_probe at a representative
    prime): the declared exact-int region now contains the real loop, so
    its carried state is lint- and range-watched, not just one step."""
    p = jnp.asarray([[2**27 - 39]], jnp.int32)

    def probe(acc, row):
        t = (acc.astype(jnp.int32) + row.astype(jnp.int32)) % p
        return t.astype(jnp.uint32)

    z = jnp.zeros((1, 8), jnp.uint32)
    loop_fn, loop_args = fold_loop_probe(2**27 - 39)
    return {
        "fl.stream.accumulator_fold": (probe, (z, z)),
        "fl.stream.fold_loop": (loop_fn, loop_args),
    }


def fold_loop_probe(prime: int):
    """The online fold as an UNBOUNDED arrival loop (ISSUE 12): a
    `lax.while_loop` folding one canonical row per arrival, with the
    arrival count an abstract input — the shape
    `analysis.ranges.certify_fold_inductive` needs to prove the
    accumulator invariant [0, p-1] INDUCTIVELY (base: the canonical first
    upload; step: this body) for ANY arrival count, where the old
    one-step trace only covered a single fold. The count-down counter
    makes the loop's post-fixpoint immediate for the analyzer; the `%`
    mirrors `OnlineAccumulator._add`'s host-side numpy modulo. Trace
    under `jax.enable_x64(True)` (int64 carrier)."""
    p = np.asarray([[int(prime)]], np.int64)

    def probe(count, acc, row):
        def cond(state):
            return state[0] > 0

        def body(state):
            remaining, a = state
            return remaining - 1, (a + row) % p

        _, out = jax.lax.while_loop(cond, body, (count, acc))
        return out

    z = np.zeros((1, 8), np.int64)
    return probe, (np.int64(0), z, z)


def ct_hash(c0, c1) -> str:
    """Pipeline hash of a ciphertext's residues — the bitwise-equality
    currency of the streaming-vs-batched gates."""
    import hashlib

    h = hashlib.sha256()
    h.update(np.ascontiguousarray(np.asarray(c0, dtype=np.uint32)))
    h.update(np.ascontiguousarray(np.asarray(c1, dtype=np.uint32)))
    return h.hexdigest()


class DedupWindow:
    """Bounded dedup nonce window: the engine's idempotence memory.

    A (client, round) nonce must stay live exactly as long as a duplicate
    delivery of it could still arrive: its upload can trail at most tau
    rounds behind its origin (the bounded-staleness budget) plus the
    commit round itself, so `advanced(r, tau)` keeps a nonce iff
    `r - origin_round <= tau + 1` and drops the rest. Size is therefore
    bounded by (tau + 2) x cohort uploads however long the service runs —
    the unbounded-set growth a multi-day run must not have — and the
    conservation property (no LIVE nonce is ever evicted early) is pinned
    by tests/test_stream.py::test_dedup_window_conservation.

    `advanced` returns a NEW window (the engine's transactional
    cross-round state: a failed round must leave the previous window
    untouched for the retry). Serialization for the journal's round_close
    record is plain iteration (sorted nonce pairs).

    `peak_entries` (ISSUE 19) is the high-water mark of live nonces over
    the window's whole lineage — `advanced` carries it forward, so a
    multi-day run's peak survives every round boundary. The documented
    bound is (tau + 2) x cohort: tau + 2 distinct origin rounds can be
    live at once (the commit round plus tau + 1 trailing), each
    contributing at most one nonce per cohort client. The engine surfaces
    it through the `stream.dedup_window_peak` gauge; the load harness
    (fl.load) asserts the bound at 10^5-client scale.
    """

    __slots__ = ("_nonces", "_peak")

    def __init__(self, nonces=(), peak: int = 0):
        self._nonces = {tuple(n) for n in nonces}
        self._peak = max(int(peak), len(self._nonces))

    def advanced(self, round_index: int, tau: int) -> "DedupWindow":
        """The window as round `round_index` sees it: expired nonces
        (older than the duplicate-reachability horizon tau + 1) evicted,
        live ones all kept. A new instance — transactional; the lineage
        peak carries forward."""
        return DedupWindow(
            (
                n for n in self._nonces
                if int(round_index) - int(n[1]) <= int(tau) + 1
            ),
            peak=self._peak,
        )

    @property
    def peak_entries(self) -> int:
        """High-water mark of live nonces over this window's lineage."""
        return self._peak

    def add(self, nonce) -> None:
        self._nonces.add(tuple(nonce))
        if len(self._nonces) > self._peak:
            self._peak = len(self._nonces)

    def __contains__(self, nonce) -> bool:
        return tuple(nonce) in self._nonces

    def __iter__(self):
        return iter(self._nonces)

    def __len__(self) -> int:
        return len(self._nonces)

    def __eq__(self, other) -> bool:
        if isinstance(other, DedupWindow):
            return self._nonces == other._nonces
        if isinstance(other, (set, frozenset)):
            return self._nonces == {tuple(n) for n in other}
        return NotImplemented


# ---------------------------------------------------------------------------
# Upload producer: one SPMD program -> per-client encrypted uploads + bits.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=16)
def _build_upload_fn(
    module,
    cfg: TrainConfig,
    mesh,
    ctx,
    dp=None,
    num_clients: int = 0,
    packing=None,
    hhe: bool = False,
):
    """Compile-once factory for the streaming upload program: EXACTLY the
    per-client body of fl.secure's masked round (`client_upload_body` —
    one shared function, so the streaming-vs-batched bitwise gates cannot
    drift), WITHOUT the mask-and-psum tail — the per-client ciphertexts
    leave the program (P(axes)-sharded) so the host-side engine can fold
    them as they "arrive". dp shares are calibrated to the declared
    surviving floor (fl.dp.calibration_clients), like the batched path.

    `hhe=True` (ISSUE 11) appends two traced inputs — per-client symmetric
    master keys uint32[C, 4] and the round counter — and swaps the CKKS
    encrypt for the hybrid-HE stream cipher (`fl.secure.hhe_encrypt_stack`):
    the program then emits (w_hi, w_lo) symmetric-ciphertext word pairs for
    the server-side transcipher instead of ciphertext residues. The round
    counter is TRACED, so every round of an experiment shares this one
    executable (the no-new-compile guarantee, pinned in tests/test_hhe.py).

    An error-feedback spec (`packing.error_feedback`, ISSUE 19) appends
    ONE more traced input — the per-client residual rows f32[C, total],
    sharded with the client axis — and one more output, the new residual
    rows. The engine owns the rows across rounds and donates the input
    buffer (the residual is pure carry state, like the optimizer's).
    """
    from hefl_tpu.fl.fusion import resolve_fusion_backend
    from hefl_tpu.fl.secure import client_upload_body

    axes = client_axes(mesh)
    # 2-D ("clients", "ct") mesh (ISSUE 15): the per-client encrypt core
    # shards its ciphertext rows over the ct axis, bitwise-identical.
    ct_shards = ct_shard_count(mesh)
    backend = resolve_fusion_backend(cfg.client_fusion, module)
    dp_k = calibration_clients(dp, num_clients) if dp is not None else 0
    ef = packing is not None and getattr(packing, "error_feedback", False)
    # Hoisted shuffle streams (ISSUE 15): the permutation sort must lower
    # OUTSIDE the manual-sharding region — see client.epoch_index_streams.
    from hefl_tpu.fl.client import hoist_streams, hoisted_streams_jit

    hoist = hoist_streams(cfg, backend)

    def body(gp, pk, x_blk, y_blk, kt_blk, ke_blk, *rest):
        i = 0
        streams_blk = None
        if hoist:
            streams_blk, i = (rest[0], rest[1]), 2
        kd_blk = None
        if dp is not None:
            kd_blk, i = rest[i], i + 1
        m_blk, po_blk = rest[i], rest[i + 1]
        hk_blk = hhe_round = None
        if hhe:
            hk_blk, hhe_round = rest[i + 2], rest[i + 3]
        ef_blk = rest[-1] if ef else None
        cts, mets, overflow, bits, _, ef_out = client_upload_body(
            module, cfg, backend, ctx, dp, dp_k, packing, True,
            gp, pk, x_blk, y_blk, kt_blk, ke_blk,
            kd_blk=kd_blk, m_blk=m_blk, po_blk=po_blk,
            hhe_keys_blk=hk_blk, hhe_round=hhe_round, ct_shards=ct_shards,
            streams_blk=streams_blk, ef_blk=ef_blk,
        )
        if ef:
            return cts, mets, overflow, bits, ef_out
        return cts, mets, overflow, bits

    in_specs = (P(), P(), P(axes), P(axes), P(axes), P(axes))
    if hoist:
        in_specs = in_specs + (P(axes), P(axes))  # hoisted shuffle streams
    if dp is not None:
        in_specs = in_specs + (P(axes),)
    in_specs = in_specs + (P(axes), P(axes))
    if hhe:
        # Per-client keys shard with the client axis; the round counter is
        # a replicated scalar.
        in_specs = in_specs + (P(axes), P())
    if ef:
        in_specs = in_specs + (P(axes),)   # EF residual rows (LAST arg)
    out_specs = (P(axes), P(axes), P(axes), P(axes))
    if ef:
        out_specs = out_specs + (P(axes),)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    if not hoist:
        # The EF residual is pure carry state: donate its buffer like the
        # optimizer state's (it is consumed and replaced every round).
        # It is the LAST positional argument by construction.
        return jax.jit(
            fn, donate_argnums=(len(in_specs) - 1,) if ef else ()
        )
    # Streams derive from the train keys (arg 4) and insert after the
    # enc keys (arg 5) — one shared wrapper, see client.hoisted_streams_jit.
    # The hoist wrapper inserts the two stream arrays mid-signature; the
    # EF residual stays the OUTER signature's last argument (the hoist
    # wrapper passes it through), so its donation index is outer-arg
    # count - 1: len(in_specs) - 2 before the streams are inserted.
    return hoisted_streams_jit(
        fn, cfg, x_index=2, key_index=4, insert_after=5,
        donate_argnums=(len(in_specs) - 3,) if ef else (),
    )


def produce_uploads(
    module,
    cfg: TrainConfig,
    mesh,
    ctx,
    pk,
    global_params,
    xs,
    ys,
    key,
    participation=None,
    poison=None,
    dp=None,
    num_real_clients: int | None = None,
    packing=None,
    hhe=None,
    round_index: int = 0,
    cohort=None,
    ef_residual=None,
):
    """Train every client and return its ENCRYPTED upload, per client.

    -> (Ciphertext [C, n_ct, L, N], metrics [C, E, 4], overflow int32[C],
    bits int32[C]): the streaming engine's arrival payloads plus the
    in-program sanitization verdicts. Key-split convention is IDENTICAL to
    secure_fedavg_round's (train/enc[/dp] streams), so a cohort's
    trainings match what the batched round would have computed for the
    same key.

    `cohort` (sorted REAL client indices, ISSUE 15) switches to
    COHORT-ONLY production: the sampled clients' data/key/mask rows are
    gathered BEFORE the fused GEMM stream and padded up the power-of-two
    bucket ladder (`fedavg.cohort_bucket` — masked-out client-0 dummies,
    the `pad_index` idiom, so bucket padding can never fold or count as
    surviving), and only that bucket trains/encrypts. Per-client keys are
    still split at the FULL registry count and gathered per client, so
    every cohort client's training, dp noise, and ciphertext are BITWISE
    what the full-C producer computes for it — the cohort-vs-full
    equality gates hold by construction. Outputs are then COHORT-ROWED
    ([len(cohort), ...], cohort order); the engine scatters. A cohort
    covering every client falls back to the historical full-C path (same
    shapes, same executables, bit-for-bit).

    `hhe` (an `fl.config.HheConfig`, ISSUE 11) switches the wire format to
    upload_kind=hhe: each client's packed quantized update is encrypted
    under its symmetric stream cipher instead of CKKS (requires `packing`),
    and the first return value becomes the `(w_hi, w_lo)` uint32[C, n_ct,
    N] word-pair tuple the server-side transcipher (hhe.transcipher)
    consumes. Training/dp/poison/sanitization trace identically, which is
    what makes the HHE-vs-direct parity gate hold by construction.
    `round_index` keys the keystream counter (traced — no recompile per
    round).

    `ef_residual` (f32[num_clients, total], ISSUE 19) is REQUIRED when
    `packing.error_feedback` is set: the per-client quantization residual
    rows the engine carries across rounds. Each client's residual is
    added to its update before quantizing at the low-bit grid and the
    new residual is RETURNED as a fifth output (cohort-rowed in cohort
    mode), to be scattered back into the engine's full-registry carry.
    """
    n_dev = client_mesh_size(mesh)
    num_clients, pad_idx, prepadded = _round_geometry(
        xs, n_dev, num_real_clients
    )
    if packing is not None and packing.clients < num_clients:
        raise ValueError(
            f"packing spec sized for {packing.clients} clients cannot hold "
            f"a carry-free sum over {num_clients} — rebuild "
            "PackedSpec.for_params with the experiment's count"
        )
    if hhe is not None and packing is None:
        raise ValueError(
            "upload_kind=hhe ships the PACKED quantized update under the "
            "stream cipher; add a PackingConfig (the symmetric cipher "
            "lives in the packed integer domain)"
        )
    ef = packing is not None and getattr(packing, "error_feedback", False)
    if ef and ef_residual is None:
        raise ValueError(
            "PackingConfig.error_feedback needs the per-client residual "
            "rows (ef_residual) the StreamEngine carries across rounds — "
            "pass f32[num_clients, total] (zeros on round 0; see "
            "fl.client.init_ef_residuals)"
        )
    if ef:
        ef_residual = jnp.asarray(ef_residual, jnp.float32)
    if dp is None:
        k_train, k_enc = jax.random.split(key)
        dp_keys = None
    else:
        k_train, k_enc, k_dp = jax.random.split(key, 3)
        dp_keys = jax.random.split(k_dp, num_clients)
    # Per-client key streams ALWAYS derive at the full registry count —
    # a cohort gather below picks rows out of this split, so client c's
    # streams are independent of who else was sampled (the bitwise
    # cohort-vs-full-C contract).
    train_keys = jax.random.split(k_train, num_clients)
    enc_keys = jax.random.split(k_enc, num_clients)
    gp = replicate_on(mesh, global_params)
    hhe_keys = None
    if hhe is not None:
        from hefl_tpu.hhe.cipher import derive_client_keys

        hhe_keys = jnp.asarray(
            derive_client_keys(hhe.key_seed, num_clients)
        )
    if cohort is not None:
        cohort = np.asarray(cohort, dtype=np.int64)
        if len(cohort) > num_clients or (
            len(cohort)
            and (int(cohort.min()) < 0 or int(cohort.max()) >= num_clients)
        ):
            # An oversized or out-of-range cohort cannot have come from
            # the sampler — training phantom client slots would silently
            # corrupt the aggregate's denominator; fail loudly instead.
            raise ValueError(
                f"produce_uploads: cohort of {len(cohort)} with indices in "
                f"[{cohort.min() if len(cohort) else 0}, "
                f"{cohort.max() if len(cohort) else 0}] does not fit the "
                f"{num_clients} registered clients"
            )
    if cohort is not None and len(cohort) < num_clients:
        # Cohort-only training (ISSUE 15): gather the sampled slots, pad
        # to the bucket, train ONLY those. `gidx` indexes REAL client
        # rows (< num_clients), so it is valid on pre-padded federated
        # arrays too — the dummy-padding rows at the tail are never
        # touched and the two padding schemes cannot interact.
        from hefl_tpu.fl.client import hoist_streams
        from hefl_tpu.fl.fusion import resolve_fusion_backend

        if not hoist_streams(
            cfg, resolve_fusion_backend(cfg.client_fusion, module)
        ):
            # The nested flat_scan=False layout derives its shuffle sort
            # INSIDE the sharded region, where XLA can couple it across
            # devices (see client.epoch_index_streams) — a cohort gather
            # changes client placement, so the committed aggregate could
            # silently diverge bitwise from the full-C reference. Refuse
            # rather than diverge.
            raise ValueError(
                "cohort-only training requires the hoisted shuffle "
                "streams (TrainConfig.flat_scan=True — the default — or "
                "the fused backend): the nested scan layout's in-body "
                "shuffle sort is placement-coupled under sharding, so a "
                "cohort gather could silently diverge bitwise. Either "
                "set flat_scan=True (keeps cohort-only training) or "
                "train the full registry with the un-hoisted layout via "
                "StreamConfig.cohort_only=False — the CLI escape hatch "
                "is --full-cohort-train"
            )
        n_c = len(cohort)
        bucket = cohort_bucket(n_c, num_clients, n_dev)
        gidx = cohort_gather_index(cohort, bucket)
        part_full = (
            np.ones(num_clients, np.int32)
            if participation is None
            else np.asarray(participation).astype(np.int32).reshape(
                num_clients
            )
        )
        pois_full = (
            np.zeros(num_clients, np.int32)
            if poison is None
            else np.asarray(poison).astype(np.int32).reshape(num_clients)
        )
        part_g = part_full[gidx].copy()
        pois_g = pois_full[gidx].copy()
        part_g[n_c:] = 0    # bucket padding: scheduled out, never ships
        pois_g[n_c:] = 0
        train_keys, enc_keys = train_keys[gidx], enc_keys[gidx]
        if dp_keys is not None:
            dp_keys = dp_keys[gidx]
        if hhe_keys is not None:
            hhe_keys = hhe_keys[gidx]
        xs, ys = xs[gidx], ys[gidx]
        fn = _build_upload_fn(
            module, cfg, mesh, ctx, dp, num_clients, packing, hhe is not None
        )
        args = (gp, pk, xs, ys, train_keys, enc_keys)
        if dp is not None:
            args = args + (dp_keys,)
        args = args + (jnp.asarray(part_g), jnp.asarray(pois_g))
        if hhe is not None:
            args = args + (hhe_keys, jnp.uint32(round_index))
        if ef:
            args = args + (ef_residual[gidx],)
        out = fn(*args)
        cts, mets, overflow, bits = out[:4]
        ef_tail = (out[4][:n_c],) if ef else ()
        if hhe is not None:
            w_hi, w_lo = cts
            return (
                (w_hi[:n_c], w_lo[:n_c]),
                mets[:n_c],
                overflow[:n_c],
                bits[:n_c],
            ) + ef_tail
        return (
            Ciphertext(c0=cts.c0[:n_c], c1=cts.c1[:n_c], scale=cts.scale),
            mets[:n_c],
            overflow[:n_c],
            bits[:n_c],
        ) + ef_tail
    part, pois = _mask_inputs(num_clients, participation, poison, pad_idx)
    if pad_idx is not None:
        train_keys, enc_keys = train_keys[pad_idx], enc_keys[pad_idx]
        if dp_keys is not None:
            dp_keys = dp_keys[pad_idx]
        if hhe_keys is not None:
            hhe_keys = hhe_keys[pad_idx]
        if not prepadded:
            xs, ys = xs[pad_idx], ys[pad_idx]
        if ef:
            ef_residual = ef_residual[pad_idx]
    fn = _build_upload_fn(
        module, cfg, mesh, ctx, dp, num_clients, packing, hhe is not None
    )
    args = (gp, pk, xs, ys, train_keys, enc_keys)
    if dp is not None:
        args = args + (dp_keys,)
    args = args + (part, pois)
    if hhe is not None:
        args = args + (hhe_keys, jnp.uint32(round_index))
    if ef:
        args = args + (ef_residual,)
    out = fn(*args)
    cts, mets, overflow, bits = out[:4]
    ef_tail = (out[4][:num_clients],) if ef else ()
    if hhe is not None:
        w_hi, w_lo = cts
        return (
            (w_hi[:num_clients], w_lo[:num_clients]),
            mets[:num_clients],
            overflow[:num_clients],
            bits[:num_clients],
        ) + ef_tail
    return (
        Ciphertext(
            c0=cts.c0[:num_clients], c1=cts.c1[:num_clients], scale=cts.scale
        ),
        mets[:num_clients],
        overflow[:num_clients],
        bits[:num_clients],
    ) + ef_tail


def cohort_compare_record(
    module,
    cfg: TrainConfig,
    mesh,
    ctx,
    pk,
    global_params,
    xs,
    ys,
    key,
    num_clients: int,
    cohort_size: int,
    seed: int = 0,
) -> dict:
    """Timed full-C-vs-cohort-only producer comparison (ISSUE 15) — the
    `cohort_compare` record bench.py / profile_round.py artifacts embed
    and run_perf_smoke.sh schema-gates.

    Both runs produce the SAME sampled cohort's uploads: the full-C run
    trains every registered slot with unsampled clients masked (the
    historical path), the cohort run gathers the cohort bucket first.
    Speedup is warm steady-state wall clock; `bitwise_equal` folds the
    cohort's uploads from both producers into `OnlineAccumulator`s and
    hash-compares the sums — the committed-aggregate equality shipped as
    artifact evidence, not just a test assertion.
    """
    from hefl_tpu.fl.fedavg import cohort_bucket as _bucket
    from hefl_tpu.utils.roofline import steady_seconds

    s = StreamConfig(cohort_size=cohort_size, seed=seed)
    cohort = sample_cohort(s, 0, num_clients)
    in_cohort = np.zeros(num_clients, dtype=bool)
    in_cohort[cohort] = True
    part = in_cohort.astype(np.int32)

    last: dict = {}   # the timed closures' final outputs, kept for the
                      # hash gate below — no extra producer executions

    def run(tag, cohort_arg):
        cts = produce_uploads(
            module, cfg, mesh, ctx, pk, global_params, xs, ys, key,
            participation=part, cohort=cohort_arg,
        )[0]
        last[tag] = cts
        return cts.c0

    t_full = steady_seconds(lambda: run("full", None))
    t_cohort = steady_seconds(lambda: run("cohort", cohort))
    cts_full = last["full"]
    cts_coh = last["cohort"]
    acc_full = OnlineAccumulator(ctx.ntt.p)
    acc_coh = OnlineAccumulator(ctx.ntt.p)
    f0, f1 = np.asarray(cts_full.c0), np.asarray(cts_full.c1)
    g0, g1 = np.asarray(cts_coh.c0), np.asarray(cts_coh.c1)
    for i, c in enumerate(cohort):
        acc_full.fold((int(c), 0), f0[c], f1[c])
        acc_coh.fold((int(c), 0), g0[i], g1[i])
    bitwise_equal = ct_hash(*acc_full.value()) == ct_hash(*acc_coh.value())
    n_dev = client_mesh_size(mesh)
    return {
        "num_clients": int(num_clients),
        "cohort_size": int(len(cohort)),
        "bucket": int(_bucket(len(cohort), num_clients, n_dev)),
        "full_c_train_s": round(t_full, 6),
        "cohort_train_s": round(t_cohort, 6),
        "speedup": round(t_full / t_cohort, 3),
        "devices_per_axis": {
            "clients": int(n_dev),
            "ct": int(ct_shard_count(mesh)),
        },
        "bitwise_equal": bool(bitwise_equal),
    }


def cohort_compare_smoke_record() -> dict:
    """The FIXED cohort_compare geometry bench.py and profile_round.py
    both embed and run_perf_smoke.sh stage (n) gates: 16 registered
    clients, cohort of 2, mnist/smallcnn on a tiny ring (the record
    measures TRAIN scaling, not HE ring cost). Single-sourced here so
    the two drivers cannot silently measure different configurations."""
    import jax

    from hefl_tpu.ckks.keys import CkksContext, keygen
    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.models import create_model
    from hefl_tpu.parallel import make_mesh

    module, params = create_model("smallcnn", rng=jax.random.key(7))
    (x, y), _, _ = make_dataset("mnist", seed=0, n_train=64, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), 16))
    ctx = CkksContext.create(n=256)
    _, pk = keygen(ctx, jax.random.key(77))
    cfg = TrainConfig(epochs=1, batch_size=8, num_classes=10,
                      augment=False, val_fraction=0.25)
    return cohort_compare_record(
        module, cfg, make_mesh(16), ctx, pk, params,
        jnp.asarray(xs), jnp.asarray(ys), jax.random.key(78),
        num_clients=16, cohort_size=2,
    )


# ---------------------------------------------------------------------------
# Round metadata + cross-round carry state
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PendingUpload:
    """An upload carried across rounds under the staleness budget."""

    client: int
    origin_round: int
    nonce: tuple
    c0: np.ndarray
    c1: np.ndarray
    lands_at: float      # arrival offset within its landing round
    lateness: int        # rounds behind its origin when it lands


@dataclasses.dataclass
class PendingTierPartial:
    """A sealed HOST partial carried across rounds under the tier
    staleness budget (ISSUE 17): host `host`'s tier folded `clients`'
    uploads in `origin_round` but its ship missed that round's commit
    (deadline / dark uplink). The partial folds at the NEXT round's root
    as a stale tier fold (`HierarchicalAggregator.fold_carried`, deduped
    by (host, origin_round)) or keeps carrying until `lateness` exceeds
    host_staleness_rounds, when its clients are excluded as
    "host_stale"."""

    host: int
    origin_round: int
    sha: str
    c0: np.ndarray
    c1: np.ndarray
    clients: tuple[int, ...]   # the client folds the partial contains
    lateness: int              # rounds behind its origin when it folds


@dataclasses.dataclass
class _HheRound:
    """Server-side hybrid-HE state of one round (ISSUE 11): the arrived
    symmetric ciphertexts, their transciphered CKKS residues (what the
    accumulator folds), and the provisioned keystream pads — kept so
    journal REPLAY can re-transcipher persisted symmetric bytes against
    the re-derived pads and land on bitwise the live fold's residues."""

    w_hi: np.ndarray      # uint32[C, n_ct, N] symmetric ciphertext words
    w_lo: np.ndarray
    pad_c0: np.ndarray    # uint32[C, n_ct, L, N] provisioned pad residues
    pad_c1: np.ndarray
    ctx: Any

    def retranscipher(self, c: int, w_hi, w_lo):
        """Transcipher one (journal-sourced) symmetric upload against
        client c's pad — the replay half of `fold`'s HHE leg."""
        from hefl_tpu.hhe.transcipher import retranscipher_decode

        return retranscipher_decode(
            self.ctx, w_hi, w_lo, self.pad_c0[c], self.pad_c1[c]
        )


@dataclasses.dataclass(frozen=True)
class StreamRoundMeta:
    """One streaming round's public outcome: the RoundMeta the decoder
    needs (surviving = uploads in the released sum) plus the arrival-level
    story — quorum, commit time, dedup/retry/staleness accounting."""

    meta: RoundMeta
    round_index: int
    cohort: tuple[int, ...]
    quorum: int
    committed: bool          # round released (False = degraded: model
                             # carried forward, nothing released)
    degraded_reason: str | None  # None|"quorum"|"host_quorum"|"dp_floor"
    fresh: int               # this round's cohort arrivals folded
    stale_folded: int        # carried uploads folded this round
    carried: int             # uploads carried into the NEXT round
    stale_excluded: int      # late uploads dropped past the budget
    unreachable: int         # deliveries lost with retries exhausted
    arrivals: int            # deliveries received (incl. duplicates)
    duplicates: int          # deduped redeliveries
    rejected: int            # arrivals the in-program sanitizer rejected
    retries: int             # redelivery attempts made
    commit_s: float          # simulated time at which the round closed
    hosts: dict | None = None  # hierarchical uplink story (ISSUE 17):
                             # landed/missed tiers, host quorum, ship
                             # retry/dedup and stale-tier-carry counts.
                             # None on the flat engine — flat-vs-hier twin
                             # comparisons strip this key.

    def record(self) -> dict:
        """JSON-ready summary for history[r] / the stream_round event."""
        out = {
            "cohort": list(self.cohort),
            "quorum": self.quorum,
            "committed": self.committed,
            "degraded_reason": self.degraded_reason,
            "fresh": self.fresh,
            "stale_folded": self.stale_folded,
            "carried": self.carried,
            "stale_excluded": self.stale_excluded,
            "unreachable": self.unreachable,
            "arrivals": self.arrivals,
            "duplicates": self.duplicates,
            "rejected": self.rejected,
            "retries": self.retries,
            "commit_s": round(self.commit_s, 6),
        }
        if self.hosts is not None:
            out["hosts"] = dict(self.hosts)
        return out


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Delivery:
    """One simulated delivery event."""

    t: float
    seq: int
    kind: str            # "fresh" | "stale"
    client: int
    nonce: tuple
    retried: bool = False
    pending: Any = None  # PendingUpload for kind == "stale"


class StreamEngine:
    """Round lifecycle driver for streaming quorum aggregation.

    One instance per experiment: it owns the cross-round state (uploads
    carried under the staleness budget, the dedup nonce window) and runs
    each round's arrival simulation against the deterministic fault
    schedule. All waiting is on a virtual clock unless
    StreamConfig.time_scale > 0 maps it onto real sleeping (under the
    hefl.quorum_wait host TraceAnnotation).
    """

    def __init__(self, stream: StreamConfig, faults=None):
        self.stream = stream
        self.faults = faults
        self._pending: list[PendingUpload] = []   # land next round
        # Sealed host partials that missed their round's commit, carried
        # under host_staleness_rounds to fold as stale tier folds.
        self._pending_tiers: list[PendingTierPartial] = []
        # Dedup nonce window, bounded to the duplicate-reachability
        # horizon (tau + 1 rounds past a nonce's origin) — see DedupWindow.
        self._seen: DedupWindow = DedupWindow()
        # Error-feedback residual rows (ISSUE 19): f32[num_clients, total]
        # per-client quantization error carried across rounds when
        # PackedSpec.error_feedback is set. Lazily zero-initialized on the
        # first EF round (the engine does not know the parameter count
        # until it sees global_params); committed transactionally with
        # _pending/_seen — a round that dies mid-execution leaves the
        # previous residuals intact for the retry.
        self._ef_residual: np.ndarray | None = None
        # The most recent round's span tree (ISSUE 20): run_round installs
        # one SpanTracer per round; drivers collect these for the Chrome
        # trace export. Not cross-round state — purely observational.
        self.last_spans: obs_spans.SpanTracer | None = None

    # -- deterministic retry timeline --------------------------------------

    def _retry_times(self, round_index: int, client: int, t0: float) -> list:
        """Redelivery times for a lost upload: exponential backoff with
        deterministic +/- jitter, starting from the server's miss point
        (the deadline when one is set, else the original send)."""
        s = self.stream
        rng = np.random.default_rng(
            [int(s.seed), int(round_index), int(client), 3]
        )
        t = max(s.deadline_s, t0) if s.deadline_s > 0 else t0
        out = []
        for i in range(s.max_retries):
            back = s.retry_backoff_s * (2.0**i)
            t += back * (1.0 + s.retry_jitter * float(rng.uniform(-1.0, 1.0)))
            out.append(t)
        return out

    # -- hybrid-HE transciphering (ISSUE 11) -------------------------------

    def _transcipher_round(
        self, ctx, pk, packing, uploads, key, round_index, num_clients,
        dp, hhe, journaled: bool, client_ids=None,
    ):
        """Provision pads + transcipher the round's symmetric uploads.

        -> (_HheRound | None, Ciphertext [C, n_ct, L, N]). The pad-
        encryption randomness derives from the round key with the SAME
        split convention `produce_uploads` uses (train/enc[/dp]) so a
        replayed round re-derives identical pads — the property that makes
        journaled symmetric bodies re-transcipher to bitwise the live
        residues. The _HheRound host copies (symmetric words + pad
        residues, a full round-sized transfer) exist only for the journal;
        `journaled=False` skips them and returns None. `client_ids`
        (cohort-only rounds, ISSUE 15) maps each upload row to its REAL
        client index: per-client master keys and pad randomness are
        derived at the full registry count and gathered, so a cohort
        row's pad is bitwise the full-C round's — the transcipher parity
        holds under cohort gathering too. Runs under the public key only:
        the authority wraps client master keys, the server sees
        ciphertexts of keystreams, and nobody outside the client holds
        its key in the clear (README "Hybrid HE uplink")."""
        from hefl_tpu.hhe import cipher as hhe_cipher
        from hefl_tpu.hhe import transcipher as hhe_transcipher

        w_hi_dev, w_lo_dev = uploads
        keys = hhe_cipher.derive_client_keys(hhe.key_seed, num_clients)
        if dp is None:
            _, k_enc = jax.random.split(key)
        else:
            _, k_enc, _ = jax.random.split(key, 3)
        enc_keys = jax.random.split(k_enc, num_clients)
        if client_ids is not None:
            ids = np.asarray(client_ids, dtype=np.int64)
            keys = np.asarray(keys)[ids]
            enc_keys = enc_keys[jnp.asarray(ids)]
        tracer = obs_spans.current()
        with (
            tracer.measure(
                "transcipher", uploads=int(np.asarray(w_hi_dev).shape[0])
            )
            if tracer is not None
            else contextlib.nullcontext()
        ):
            tc, pad = hhe_transcipher.transcipher_batch(
                ctx, packing, pk, jnp.asarray(w_hi_dev),
                jnp.asarray(w_lo_dev), keys, round_index, enc_keys,
            )
        rd = None
        if journaled:
            rd = _HheRound(
                w_hi=np.asarray(w_hi_dev), w_lo=np.asarray(w_lo_dev),
                pad_c0=np.asarray(pad.c0), pad_c1=np.asarray(pad.c1),
                ctx=ctx,
            )
        obs_metrics.counter("hhe.uploads_transciphered").inc(
            int(np.asarray(w_hi_dev).shape[0])
        )
        obs_metrics.gauge("hhe.upload_bytes").set(
            hhe_cipher.sym_wire_bytes(packing)
        )
        return rd, tc

    # -- one round ---------------------------------------------------------

    def run_round(
        self,
        module,
        cfg: TrainConfig,
        mesh,
        ctx,
        pk,
        global_params,
        xs,
        ys,
        key,
        round_index: int,
        dp=None,
        packing=None,
        num_real_clients: int | None = None,
        session=None,
        hhe=None,
    ):
        """Traced entry point: installs one `obs.spans.SpanTracer` for the
        round (kept as `self.last_spans` for exporters), then runs
        `_run_round_body` — see its docstring for the full contract."""
        tracer = obs_spans.SpanTracer(int(round_index))
        self.last_spans = tracer
        with obs_spans.activate(tracer):
            return self._run_round_body(
                module, cfg, mesh, ctx, pk, global_params, xs, ys, key,
                round_index, dp=dp, packing=packing,
                num_real_clients=num_real_clients, session=session, hhe=hhe,
            )

    def _run_round_body(
        self,
        module,
        cfg: TrainConfig,
        mesh,
        ctx,
        pk,
        global_params,
        xs,
        ys,
        key,
        round_index: int,
        dp=None,
        packing=None,
        num_real_clients: int | None = None,
        session=None,
        hhe=None,
    ):
        """-> (Ciphertext sum, metrics [C, E, 4], overflow [C],
        StreamRoundMeta). meta.meta.surviving is the decode denominator;
        0 (or committed=False) means nothing was released this round and
        the driver keeps the global model. Under cohort-only training
        (StreamConfig.cohort_only, the default) metrics/overflow rows of
        unsampled clients are zeros — those clients trained nothing.

        `session` (fl.journal.RoundSession, optional) is the durability
        hook: every engine transition is journaled through it (live mode)
        or VERIFIED against the journal and — for folds — re-fed the
        persisted upload bytes (replay mode, the server's crash
        recovery). None keeps the historical in-memory-only engine.

        With `StreamConfig.upload_kind == "hhe"` (ISSUE 11) the cohort
        uploads symmetric-cipher word pairs (~1x wire) and the server
        TRANSCIPHERS them into CKKS — one batched dispatch against pads
        the key authority provisioned under the public key — before the
        fold; everything from the fold on (dedup, staleness, journal,
        commit hash) carries the transciphered ciphertexts unchanged,
        except that journaled FRESH-fold bodies persist the symmetric
        ciphertext bytes (the wire artifact) and replay re-transciphers
        them. `hhe` (fl.config.HheConfig) supplies the key-derivation
        knobs; omitted = defaults."""
        tracer = obs_spans.current()
        s = self.stream
        hhe_mode = s.upload_kind == "hhe"
        if hhe_mode and packing is None:
            raise ValueError(
                "upload_kind=hhe ships the PACKED quantized update under "
                "the stream cipher; add a PackingConfig (the symmetric "
                "cipher lives in the packed integer domain)"
            )
        if hhe_mode and hhe is None:
            from hefl_tpu.fl.config import HheConfig

            hhe = HheConfig()
        if hhe_mode:
            # Round-setup range proof (ISSUE 8 gate, extended to HHE):
            # the keystream subtract must stay carry-free inside the
            # packed guard band, the transciphered total inside the q/2
            # wall, and the mod-2**62 recovery window exact — certified
            # for ALL inputs (lru_cached: one proof per geometry), or the
            # round refuses to run, naming the overflowing op.
            from hefl_tpu.analysis.ranges import certify_transciphering

            guard_bits = packing.guard - max(
                packing.clients - 1, 0
            ).bit_length()
            cert = certify_transciphering(
                int(ctx.modulus), packing.bits, packing.k,
                packing.clients, guard_bits,
            )
            if not cert.ok:
                raise ValueError(
                    "upload_kind=hhe rejected by static range analysis — "
                    f"{cert.summary()}"
                )
        # Inductive fold certificate (ISSUE 12): the OnlineAccumulator
        # invariant this round's folds rely on, proven for ANY arrival
        # count (lru_cached — one proof per (prime, spec) geometry); a
        # packed round also re-derives its headroom-capped C-client sum
        # through the same loop machinery. An uncertified fold refuses to
        # run, naming the offending op.
        from hefl_tpu.analysis.ranges import certify_fold_inductive

        max_prime = int(np.asarray(ctx.ntt.p).max())
        fold_cert = (
            certify_fold_inductive(max_prime, packing, int(ctx.modulus))
            if packing is not None
            else certify_fold_inductive(max_prime)
        )
        if not fold_cert.ok:
            raise ValueError(
                "streaming fold rejected by static range analysis — "
                f"{fold_cert.summary()}"
            )
        if dp is not None and s.staleness_rounds > 0:
            # A carried upload lets one client contribute to a release
            # TWICE (its stale + fresh uploads: sensitivity 2C while
            # epsilon_spent accounts C per round) and makes a release
            # depend on a client outside the round's cohort (voiding the
            # subsampling amplification). Until a staleness-aware
            # accountant exists, the combination is rejected loudly — the
            # silently-weakened-guarantee failure mode fl.dp must never
            # allow.
            raise ValueError(
                "dp cannot be combined with a staleness budget "
                f"(staleness_rounds={s.staleness_rounds}): a carried "
                "upload gives one client 2x the accounted per-round "
                "sensitivity and breaks cohort-subsampling amplification "
                "— set staleness_rounds=0 for dp runs"
            )
        if dp is not None and s.host_staleness_rounds > 0:
            # Same hazard one tier up: a carried HOST partial re-releases
            # every client fold it contains in a later round, doubling
            # their accounted sensitivity and crossing cohort boundaries.
            raise ValueError(
                "dp cannot be combined with a tier staleness budget "
                f"(host_staleness_rounds={s.host_staleness_rounds}): a "
                "carried host partial re-releases its client folds in a "
                "later round, giving each 2x the accounted per-round "
                "sensitivity and breaking cohort-subsampling amplification "
                "— set host_staleness_rounds=0 for dp runs"
            )
        ef_on = packing is not None and getattr(
            packing, "error_feedback", False
        )
        if dp is not None and ef_on:
            # Same hazard class as the staleness carries above, one layer
            # down: the EF residual carries round r's clipped-and-noised
            # signal INTO round r+1's upload, so a client's round-(r+1)
            # contribution is no longer a function of only its round-(r+1)
            # data — per-round sensitivity accounting and the
            # cohort-subsampling amplification both break. Until an
            # EF-aware accountant exists, refuse loudly.
            raise ValueError(
                "dp cannot be combined with error-feedback packing "
                "(PackedSpec.error_feedback): the residual carries round "
                "r's signal into round r+1's upload, giving a client "
                "cross-round influence the per-round sensitivity "
                "accounting does not cover and breaking cohort-subsampling "
                "amplification — drop error_feedback for dp runs"
            )
        n_dev = client_mesh_size(mesh)
        num_clients, _, _ = _round_geometry(xs, n_dev, num_real_clients)
        cohort = sample_cohort(s, round_index, num_clients)
        in_cohort = np.zeros(num_clients, dtype=bool)
        in_cohort[cohort] = True
        qcount = quorum_count(s, len(cohort))
        tau = int(s.staleness_rounds)
        if session is not None:
            # WAL discipline: the round's identity (index, PRNG key,
            # cohort, quorum geometry) is durable before any work — a
            # recovering process re-derives the identical round and the
            # session verifies it against this record.
            session.round_open(
                round_index,
                np.asarray(jax.random.key_data(key)).reshape(-1).tolist(),
                cohort, qcount, tau, num_clients,
                int(packing.clients) if packing is not None else None,
            )

        if self.faults is not None:
            sched = schedule_for_round(self.faults, round_index, num_clients)
            arr = schedule_arrivals(self.faults, round_index, num_clients)
        else:
            sched = arr = None
        dropped = (
            sched.dropped if sched is not None else np.zeros(num_clients, bool)
        )
        part = (in_cohort & ~dropped).astype(np.int32)
        pois = (
            np.where(in_cohort, sched.poison, 0).astype(np.int32)
            if sched is not None
            else None
        )

        # Cohort-only training (ISSUE 15, StreamConfig.cohort_only): when
        # the cohort is a strict subset of the registry, only its client
        # slots are gathered and trained (bucket-padded — see
        # produce_uploads); outputs come back COHORT-ROWED and `row_of`
        # maps client index -> upload row. A full cohort (cohort_size=0 /
        # >= C) keeps the historical full-C shapes bit-for-bit.
        use_cohort = bool(s.cohort_only) and len(cohort) < num_clients
        ef_full = None
        if ef_on:
            # Lazy zero-init of the cross-round residual carry — sized by
            # the model's raveled parameter count, rows for the FULL
            # registry (a cohort round gathers/scatters its rows).
            from jax.flatten_util import ravel_pytree

            total = int(ravel_pytree(global_params)[0].size)
            if (
                self._ef_residual is None
                or self._ef_residual.shape != (num_clients, total)
            ):
                self._ef_residual = np.zeros(
                    (num_clients, total), np.float32
                )
            ef_full = self._ef_residual
        out = produce_uploads(
            module, cfg, mesh, ctx, pk, global_params, xs, ys, key,
            participation=part, poison=pois, dp=dp,
            num_real_clients=num_real_clients, packing=packing,
            hhe=hhe if hhe_mode else None, round_index=round_index,
            cohort=cohort if use_cohort else None,
            ef_residual=ef_full,
        )
        cts, mets_dev, overflow_dev, bits_dev = out[:4]
        ef_new = out[4] if ef_on else None
        rows = cohort if use_cohort else np.arange(num_clients)
        row_of = np.full(num_clients, -1, dtype=np.int64)
        row_of[rows] = np.arange(len(rows))
        ef_next = None
        if ef_on:
            # Residuals update at PRODUCTION time, not on the fold/commit
            # verdict: the client quantized its upload carrying the old
            # residual, so the new residual is what its next upload must
            # carry regardless of whether this one survived delivery —
            # re-adding a dropped upload's error would double-count it if
            # the carried upload later folds. Staged here, committed with
            # the other cross-round state at the end of the round.
            ef_next = ef_full.copy()
            ef_next[rows] = np.asarray(ef_new, np.float32)
        hhe_rd = None
        if hhe_mode:
            # Server-side transciphering (hhe.transcipher): the arrived
            # symmetric word pairs become REAL CKKS ciphertexts in one
            # batched dispatch, and the rest of the round never knows the
            # clients skipped their NTTs.
            hhe_rd, cts = self._transcipher_round(
                ctx, pk, packing, cts, key, round_index, num_clients, dp,
                hhe, journaled=session is not None,
                client_ids=rows if use_cohort else None,
            )
        if use_cohort:
            # Scatter the cohort rows back to registry-indexed metadata:
            # metrics/overflow/bits for unsampled clients are zeros (they
            # trained nothing — that is the point), and `surviving` can
            # only ever count folded cohort rows, so cohort padding and
            # mesh dummy padding cannot double-count.
            m_rows = np.asarray(mets_dev)
            mets = np.zeros(
                (num_clients,) + m_rows.shape[1:], m_rows.dtype
            )
            mets[rows] = m_rows
            ov_rows = np.asarray(overflow_dev)
            overflow = np.zeros(
                (num_clients,) + ov_rows.shape[1:], ov_rows.dtype
            )
            overflow[rows] = ov_rows
            bits = np.zeros(num_clients, np.int64)
            bits[rows] = np.asarray(bits_dev).astype(np.int64)
        else:
            mets, overflow = mets_dev, overflow_dev
            bits = np.asarray(bits_dev).astype(np.int64).copy()
        # The program's sanitizer verdict, immutable: the arrival-time
        # reject predicate must read THIS, not the attribution copy below
        # (a stale fold clears a client's attribution, and that must never
        # un-reject the same client's poisoned fresh upload).
        prog_bits = bits.copy()
        # Host-side attribution fix-up: the program marks every mask-0
        # client "scheduled"; a client that simply was not sampled this
        # round is attributed "unsampled" instead (not a fault).
        bits[~in_cohort] = EXCLUDED_UNSAMPLED
        c0 = np.asarray(cts.c0)     # cohort-rowed when use_cohort
        c1 = np.asarray(cts.c1)
        row_shape = c0.shape[1:]

        # Cross-round state is COMMITTED only at the end of a successful
        # round (transactional): a round that dies mid-execution — the
        # exact case the driver's retry envelope exists for — must leave
        # the carried uploads and the dedup window untouched for the
        # retry, not half-consumed.
        # Dedup window: nonces stay live while a duplicate could still
        # arrive (the staleness budget bounds how far one can trail).
        seen = self._seen.advanced(round_index, tau)
        pending_next: list[PendingUpload] = []

        # ---- build this round's delivery timeline ------------------------
        events: list[_Delivery] = []
        seq = 0
        retries_made = 0
        unreachable = 0
        for up in self._pending:
            events.append(_Delivery(
                t=float(up.lands_at), seq=seq, kind="stale",
                client=up.client, nonce=up.nonce, pending=up,
            ))
            seq += 1
        for c in cohort:
            if part[c] == 0:
                continue   # scheduled out: never uploads
            nonce = (int(c), int(round_index))
            t0 = float(arr.arrival_s[c]) if arr is not None else 0.0
            permanent = bool(arr is not None and arr.permanent[c])
            transient = bool(arr is not None and arr.transient[c])
            if permanent:
                # Every delivery fails; the engine still pays the retries.
                times = self._retry_times(round_index, c, t0)
                retries_made += len(times)
                if session is not None:
                    for i, rt in enumerate(times):
                        session.retry(round_index, c, nonce, i + 1, rt)
                if tracer is not None:
                    for i, rt in enumerate(times):
                        tracer.add(
                            "retry", float(rt), client=int(c),
                            attempt=i + 1, delivered=False,
                        )
                bits[c] |= EXCLUDED_UNREACHABLE
                unreachable += 1
                continue
            if transient:
                retry_at = self._retry_times(round_index, c, t0)
                if not retry_at:
                    bits[c] |= EXCLUDED_UNREACHABLE
                    unreachable += 1
                    continue
                retries_made += 1
                if session is not None:
                    session.retry(round_index, c, nonce, 1, retry_at[0])
                if tracer is not None:
                    tracer.add(
                        "retry", float(retry_at[0]), client=int(c),
                        attempt=1, delivered=True,
                    )
                events.append(_Delivery(
                    t=float(retry_at[0]), seq=seq, kind="fresh", client=int(c),
                    nonce=nonce, retried=True,
                ))
                seq += 1
                continue
            events.append(_Delivery(
                t=t0, seq=seq, kind="fresh", client=int(c), nonce=nonce,
            ))
            seq += 1
            if arr is not None and arr.duplicate[c]:
                events.append(_Delivery(
                    t=t0 + max(s.retry_backoff_s * 0.5, 1e-6), seq=seq,
                    kind="fresh", client=int(c), nonce=nonce,
                ))
                seq += 1

        # ---- process arrivals in time order ------------------------------
        deadline = s.deadline_s if s.deadline_s > 0 else float("inf")
        hier = s.num_hosts >= 2
        if hier:
            # Hierarchical multi-host fold (ISSUE 16): each host's tier
            # folds its contiguous client block locally and ships ONE
            # partial ciphertext across the simulated DCN at commit time
            # — O(hosts) cross-host bytes, bitwise the flat fold (lazy
            # import: hierarchy pulls this module). ISSUE 17 makes the
            # tier->root uplink faulty: the link-fault schedule and the
            # ship retry policy ride into the aggregator.
            from hefl_tpu.fl.hierarchy import HierarchicalAggregator, ShipPolicy

            link = None
            if self.faults is not None and self.faults._any_link_fault():
                if int(self.faults.num_hosts) != int(s.num_hosts):
                    raise ValueError(
                        f"FaultConfig.num_hosts={self.faults.num_hosts} does "
                        f"not match StreamConfig.num_hosts={s.num_hosts}: "
                        "the link-fault schedule would fault the uplinks of "
                        "a different fold-tree topology"
                    )
                link = schedule_links(self.faults, round_index)
            acc = HierarchicalAggregator(
                ctx.ntt.p, s.num_hosts, num_clients,
                round_index=round_index, link=link,
                ship=ShipPolicy(
                    deadline_s=float(s.ship_deadline_s),
                    max_retries=int(s.max_retries),
                    backoff_s=float(s.retry_backoff_s),
                    jitter=float(s.retry_jitter),
                    seed=int(s.seed),
                ),
            )
            host_of = host_of_clients(num_clients, s.num_hosts)
        else:
            acc = OnlineAccumulator(ctx.ntt.p)
            host_of = None
        # ---- stale tier folds (ISSUE 17) ---------------------------------
        # Host partials that missed an earlier round's commit fold at THIS
        # round's root before any arrival: each is one sealed mod-p sum,
        # deduped by (host, origin_round), and its clients re-enter the
        # released set without re-uploading. acc.folded counts their client
        # folds, so quorum/headroom/DP accounting see them automatically.
        tier_stale_folded = 0
        tier_stale_clients: list[int] = []
        if hier:
            for tp in self._pending_tiers:
                if session is not None:
                    session.tier_fold(
                        round_index, tp.host, tp.origin_round, tp.sha,
                        len(tp.clients), tp.lateness,
                    )
                if acc.fold_carried(
                    tp.host, tp.origin_round, tp.c0, tp.c1, tp.sha,
                    len(tp.clients),
                ):
                    tier_stale_folded += 1
                    tier_stale_clients.extend(int(c) for c in tp.clients)
                    for tc in tp.clients:
                        bits[int(tc)] &= ~EXCLUDED_UNSAMPLED
                    if tracer is not None:
                        # Carried partials fold before any arrival — a
                        # point span at the round's virtual origin.
                        tracer.add(
                            "tier_fold", 0.0, host=int(tp.host),
                            origin_round=int(tp.origin_round),
                            clients=len(tp.clients),
                            lateness=int(tp.lateness),
                        )
        staleness_hist = obs_metrics.histogram("stream.staleness_rounds")
        committed_at: float | None = None
        fresh = stale_folded = arrivals = rejected = 0
        stale_excluded = 0
        headroom_blocked = 0
        folded_clients: list[int] = []
        fresh_used: list[tuple] = []   # (client, t) folded fresh this round
        stale_used: list[tuple] = []   # (PendingUpload, t) folded stale
        missed: list[tuple] = []   # (kind, client, t, lateness, c0, c1, nonce)
        # Packed uploads share carry-free headroom sized for `clients`
        # field summands; EVERY fold — fresh or stale — must respect it or
        # the quantized lanes silently overflow into their neighbors. A
        # fresh upload blocked by headroom takes the missed path
        # (carry/timeout); worst case the round degrades, never corrupts.
        max_folds = int(packing.clients) if packing is not None else None
        last_t = 0.0
        for ev in sorted(events, key=lambda e: (e.t, e.seq)):
            last_t = max(last_t, ev.t)
            headroom_ok = max_folds is None or acc.folded < max_folds
            if ev.kind == "stale":
                up = ev.pending
                if committed_at is None and headroom_ok:
                    if session is not None:
                        # Content-hash only: the bytes are already durable
                        # in the origin round's carry record.
                        session.fold(
                            round_index, ev.seq, "stale", up.client,
                            up.nonce, up.lateness, ev.t, up.c0, up.c1,
                            persist=False,
                        )
                    acc.fold(("stale",) + up.nonce, up.c0, up.c1)
                    stale_folded += 1
                    folded_clients.append(up.client)
                    stale_used.append((up, ev.t))
                    if tracer is not None:
                        tracer.add(
                            "fold", ev.t, client=int(up.client),
                            src="stale", lateness=int(up.lateness),
                        )
                    obs_metrics.histogram(
                        "stream.arrival_to_fold_s",
                        bounds=_ARRIVAL_TO_FOLD_BUCKETS,
                    ).observe(round(max(0.0, float(ev.t)), 9))
                    # The client participates via its late upload; clear
                    # ONLY the not-in-this-cohort attribution — same-round
                    # fresh-upload causes (nonfinite, unreachable, ...)
                    # must survive for the exclusion accounting.
                    bits[up.client] &= ~EXCLUDED_UNSAMPLED
                    staleness_hist.observe(up.lateness)
                else:
                    if committed_at is None and not headroom_ok:
                        headroom_blocked += 1
                    if session is not None:
                        session.miss(
                            round_index, ev.seq, "stale", up.client,
                            up.nonce, ev.t, up.lateness,
                        )
                    missed.append((
                        "stale", up.client, ev.t, up.lateness,
                        up.c0, up.c1, up.nonce,
                    ))
                continue
            arrivals += 1
            if ev.nonce in seen:
                if session is not None:
                    session.dedup(round_index, ev.seq, ev.client, ev.nonce)
                acc.duplicates += 1
                if tracer is not None:
                    tracer.add(
                        "arrival", ev.t, client=int(ev.client),
                        outcome="duplicate", retried=bool(ev.retried),
                    )
                continue
            seen.add(ev.nonce)
            c = ev.client
            if prog_bits[c] & _REJECT_MASK:
                if session is not None:
                    session.reject(round_index, ev.seq, c, ev.nonce)
                rejected += 1
                if tracer is not None:
                    tracer.add(
                        "arrival", ev.t, client=int(c),
                        outcome="rejected", retried=bool(ev.retried),
                    )
                continue
            row = int(row_of[c])    # upload row (== c on the full-C path)
            if (
                committed_at is None
                and (ev.t <= deadline or ev.retried)
                and headroom_ok
            ):
                fc0, fc1 = c0[row], c1[row]
                if session is not None:
                    # Persist the arrived upload; on replay the session
                    # hands back the JOURNAL's bytes (content-hash
                    # verified against this re-derived upload) and the
                    # accumulator re-folds exactly what was journaled.
                    if hhe_rd is not None:
                        # HHE uploads persist the SYMMETRIC ciphertext
                        # bytes — the actual ~1x wire artifact, its sha256
                        # the upload's content hash. Replay hands the
                        # journal's words back and they re-transcipher
                        # against the re-derived pad: bitwise the live
                        # fold's residues (deterministic pads + the
                        # backend parity gate).
                        wh, wl = hhe_rd.w_hi[row], hhe_rd.w_lo[row]
                        rh, rl = session.fold(
                            round_index, ev.seq, "fresh", c, ev.nonce, 0,
                            ev.t, wh, wl, persist=True,
                        )
                        if rh is not wh:
                            fc0, fc1 = hhe_rd.retranscipher(row, rh, rl)
                    else:
                        fc0, fc1 = session.fold(
                            round_index, ev.seq, "fresh", c, ev.nonce, 0,
                            ev.t, c0[row], c1[row], persist=True,
                        )
                acc.fold(ev.nonce, fc0, fc1)
                fresh += 1
                folded_clients.append(c)
                fresh_used.append((c, ev.t))
                staleness_hist.observe(0)
                if tracer is not None:
                    arr_sp = tracer.add(
                        "arrival", ev.t, client=int(c),
                        outcome="folded", retried=bool(ev.retried),
                    )
                    tracer.add(
                        "fold", ev.t, parent=arr_sp, client=int(c),
                        src="fresh",
                    )
                obs_metrics.histogram(
                    "stream.arrival_to_fold_s",
                    bounds=_ARRIVAL_TO_FOLD_BUCKETS,
                ).observe(round(max(0.0, float(ev.t)), 9))
                if fresh >= qcount:
                    committed_at = ev.t
            else:
                if committed_at is None and not headroom_ok:
                    headroom_blocked += 1
                if session is not None:
                    session.miss(
                        round_index, ev.seq, "fresh", c, ev.nonce, ev.t, 0
                    )
                missed.append((
                    "fresh", c, ev.t, 0, c0[row], c1[row], ev.nonce,
                ))
                if tracer is not None:
                    tracer.add(
                        "arrival", ev.t, client=int(c),
                        outcome="missed", retried=bool(ev.retried),
                    )
        committed = committed_at is not None
        commit_s = (
            committed_at
            if committed
            else min(max(last_t, 0.0), deadline)
            if events
            else 0.0
        )
        # DP surviving-cohort floor (fl.dp.calibration_clients): a round
        # whose released sum would hold fewer uploads than the declared
        # noise-calibration floor must NOT be released — the aggregate
        # would carry less noise than epsilon_spent accounts, the exact
        # failure the batched path fail-louds on (fl.secure). Streaming
        # degrades instead of raising: the model carries forward, loudly.
        degraded_reason = None if committed else "quorum"

        # ---- hierarchical ship phase (ISSUE 17) --------------------------
        # The client-quorum commit point launches every nonempty tier's
        # ship onto the faulty DCN uplink: delay, transient loss with
        # journaled retries (exempt from the ship deadline once launched),
        # dark links, and duplicate deliveries (deduped at the root) all
        # run on the same virtual clock. The round then re-takes its
        # verdict at the TIER level: fewer than host_quorum landed tiers
        # (or an empty released sum) degrades the round exactly like a
        # missed client quorum. The client quorum itself was enforced at
        # arrival time over the FULL fold set; host_quorum < 1 is the
        # operator's explicit consent to release with missed tiers
        # excluded per-cause — the released sum then holds at least
        # qcount - (folds of the missed tiers) uploads, and dp runs keep
        # the hard calibration floor on the RELEASED count below.
        host_tau = int(s.host_staleness_rounds)
        pending_tiers_next: list[PendingTierPartial] = []
        tier_carried = 0
        tier_stale_excluded = 0
        missed_hosts: set[int] = set()
        hq = 0
        released: int | None = None
        if hier and committed:
            acc.ship_all(t0=float(committed_at))
            if session is not None:
                for sh_h, sh_att, sh_t, sh_lost in acc.ship_log:
                    if sh_att > 1:
                        session.ship_retry(
                            round_index, sh_h, sh_att, sh_t, sh_lost
                        )
            nonempty = int(acc.nonempty_tiers)
            hq = max(1, math.ceil(s.host_quorum * nonempty)) if nonempty else 0
            missed_hosts = {h for h, _cz in acc.missed_ships}
            # Per-cause attribution for every client whose tier missed the
            # ship — set regardless of the round's eventual verdict so the
            # exclusions.host_* counters track the link-fault schedule.
            for mh, cause in acc.missed_ships:
                cbit = (
                    EXCLUDED_HOST_TIMEOUT if cause == "timeout"
                    else EXCLUDED_HOST_UNREACHABLE
                )
                for c in folded_clients:
                    if int(host_of[c]) == int(mh):
                        bits[int(c)] |= cbit
            released = (
                sum(
                    1 for c in folded_clients
                    if int(host_of[c]) not in missed_hosts
                )
                + len(tier_stale_clients)
            )
            if len(acc.landed_hosts) < hq:
                committed = False
                degraded_reason = "host_quorum"
                obs_metrics.counter("stream.host_quorum_degraded").inc()
            elif released <= 0:
                # Every landed fold was in a missed tier: nothing to
                # release — same verdict as a missed client quorum.
                committed = False
                degraded_reason = "quorum"
        if dp is not None and committed:
            dp_floor = calibration_clients(dp, num_clients)
            n_rel = released if released is not None else acc.folded
            if n_rel < dp_floor:
                committed = False
                degraded_reason = "dp_floor"
                obs_metrics.counter("stream.dp_floor_degraded").inc()
        if committed and missed_hosts:
            # The round commits WITHOUT the missed tiers: their clients are
            # excluded per-cause and each sealed partial carries under the
            # tier staleness budget to fold at a later round's root.
            for mh, _cause in acc.missed_ships:
                pc0, pc1, psha, _nf = acc.take_late_partial(mh)
                t_clients = tuple(
                    int(c) for c in folded_clients
                    if int(host_of[c]) == int(mh)
                )
                if host_tau >= 1 and t_clients:
                    pending_tiers_next.append(PendingTierPartial(
                        host=int(mh), origin_round=int(round_index),
                        sha=psha, c0=pc0, c1=pc1, clients=t_clients,
                        lateness=1,
                    ))
                    tier_carried += 1
        surviving = 0
        if committed:
            surviving = int(released if released is not None else acc.folded)
        if tracer is not None:
            # The round verdict as a point span at the commit time — after
            # every re-take (host quorum, dp floor), so args carry the
            # FINAL outcome the session journals below.
            tracer.add(
                "commit", float(commit_s), committed=bool(committed),
                degraded_reason=degraded_reason, surviving=int(surviving),
                fresh=int(fresh), quorum=int(qcount),
            )
        if committed:
            obs_metrics.histogram(
                "stream.commit_latency_s", bounds=_COMMIT_LATENCY_BUCKETS
            ).observe(round(float(commit_s), 9))
        if session is not None:
            # The transaction's verdict record. On replay the re-derived
            # canonical-sum sha256 must MATCH the journaled one — the
            # recovered-equals-uninterrupted bitwise gate, enforced at
            # every recovery, not just in tests.
            if committed:
                sc0, sc1 = acc.value(like_shape=row_shape)
                session.commit(
                    round_index, ct_hash(sc0, sc1), surviving, fresh,
                    stale_folded, commit_s,
                )
            else:
                session.degrade(round_index, degraded_reason, fresh, qcount)

        # ---- misses: carry under the staleness budget, or drop -----------
        carried = 0
        for kind, c, t, lateness, mc0, mc1, nonce in missed:
            next_late = lateness + 1
            if next_late <= tau:
                pending_next.append(PendingUpload(
                    client=int(c), origin_round=int(nonce[-1]), nonce=nonce,
                    c0=np.array(mc0), c1=np.array(mc1),
                    lands_at=max(0.0, float(t) - float(commit_s)),
                    lateness=next_late,
                ))
                carried += 1
                if kind == "fresh":
                    bits[c] |= EXCLUDED_TIMEOUT
            else:
                if kind == "fresh":
                    bits[c] |= EXCLUDED_TIMEOUT
                else:
                    bits[c] |= EXCLUDED_STALE
                    stale_excluded += 1
        if not committed:
            # Degraded round: the accumulator is discarded, but an upload
            # that FOLDED into it was delivered in good faith — re-carry
            # it under the staleness budget (a stale upload one round
            # deeper; a fresh one at lateness 1) instead of destroying it
            # mid-budget, and attribute what cannot carry.
            for up, t in stale_used:
                next_late = up.lateness + 1
                if next_late <= tau:
                    pending_next.append(PendingUpload(
                        client=up.client, origin_round=up.origin_round,
                        nonce=up.nonce, c0=up.c0, c1=up.c1,
                        lands_at=max(0.0, float(t) - float(commit_s)),
                        lateness=next_late,
                    ))
                    carried += 1
                    # The fold was undone: restore attribution (the fold
                    # had cleared it), or the client would read as neither
                    # surviving nor excluded this round.
                    bits[up.client] |= EXCLUDED_TIMEOUT
                else:
                    bits[up.client] |= EXCLUDED_STALE
                    stale_excluded += 1
            for c, t in fresh_used:
                bits[c] |= EXCLUDED_TIMEOUT
                if tau >= 1:
                    r_c = int(row_of[c])
                    pending_next.append(PendingUpload(
                        client=int(c), origin_round=int(round_index),
                        nonce=(int(c), int(round_index)),
                        c0=np.array(c0[r_c]), c1=np.array(c1[r_c]),
                        lands_at=max(0.0, float(t) - float(commit_s)),
                        lateness=1,
                    ))
                    carried += 1
            # Carried tier partials folded into the discarded accumulator
            # (or still pending): re-carry each one round deeper under the
            # tier budget, restoring its clients' attribution — past the
            # budget its clients are excluded as host_stale.
            for tp in self._pending_tiers:
                next_late = tp.lateness + 1
                if next_late <= host_tau:
                    pending_tiers_next.append(
                        dataclasses.replace(tp, lateness=next_late)
                    )
                    tier_carried += 1
                    for tc in tp.clients:
                        bits[int(tc)] |= EXCLUDED_HOST_TIMEOUT
                else:
                    for tc in tp.clients:
                        bits[int(tc)] |= EXCLUDED_HOST_STALE
                    tier_stale_excluded += 1

        # ---- public metadata + observability -----------------------------
        hosts_rec = None
        if hier:
            hosts_rec = {
                "nonempty": int(acc.nonempty_tiers),
                "landed": [int(h) for h in acc.landed_hosts],
                "missed": [
                    [int(h), str(cz)] for h, cz in acc.missed_ships
                ],
                "host_quorum": int(hq),
                "ship_retries": int(acc.ship_retries),
                "ship_lost": int(acc.ship_lost),
                "ship_deduped": int(acc.ship_deduped),
                "tier_carried": int(tier_carried),
                "tier_stale_folded": int(tier_stale_folded),
                "tier_stale_excluded": int(tier_stale_excluded),
                "ships_done_s": round(float(acc.ships_done_s), 6),
            }
            obs_metrics.counter("dcn.tier.carried").inc(tier_carried)
            obs_metrics.counter("dcn.tier.stale_folded").inc(
                tier_stale_folded
            )
            obs_metrics.counter("dcn.tier.stale_excluded").inc(
                tier_stale_excluded
            )
        participation = np.zeros(num_clients, np.int32)
        if committed:
            rel_clients = [
                c for c in folded_clients
                if host_of is None or int(host_of[c]) not in missed_hosts
            ] + tier_stale_clients
            if rel_clients:
                participation[np.asarray(rel_clients, dtype=int)] = 1
        meta = RoundMeta(
            num_clients=num_clients,
            bits=tuple(int(v) for v in bits),
            participation=tuple(int(v) for v in participation),
            surviving=int(surviving),
            excluded={
                name: int(np.count_nonzero(bits & flag))
                for name, flag in EXCLUSION_CAUSES.items()
            },
            sanitized=True,
        )
        smeta = StreamRoundMeta(
            meta=meta,
            round_index=int(round_index),
            cohort=tuple(int(c) for c in cohort),
            quorum=qcount,
            committed=committed,
            degraded_reason=degraded_reason,
            fresh=fresh,
            stale_folded=stale_folded,
            carried=carried,
            stale_excluded=stale_excluded,
            unreachable=unreachable,
            arrivals=arrivals,
            duplicates=acc.duplicates,
            rejected=rejected,
            retries=retries_made,
            commit_s=float(commit_s),
            hosts=hosts_rec,
        )
        obs_metrics.counter("stream.arrivals").inc(arrivals)
        obs_metrics.counter("stream.duplicates").inc(acc.duplicates)
        obs_metrics.counter("stream.rejected").inc(rejected)
        obs_metrics.counter("stream.folds").inc(fresh + stale_folded)
        obs_metrics.counter("stream.retries").inc(retries_made)
        obs_metrics.counter("stream.late_carried").inc(carried)
        obs_metrics.counter("stream.stale_excluded").inc(stale_excluded)
        obs_metrics.counter("stream.headroom_blocked").inc(headroom_blocked)
        if not committed:
            obs_metrics.counter("stream.degraded_rounds").inc()
        obs_events.emit(
            "stream_round", round=round_index, **smeta.record()
        )
        if hier and committed:
            # One DCN-traffic summary per committed hierarchical round:
            # per-uplink bytes, the flat-topology model for the same
            # folds, their ratio, and the faulty-uplink outcome. The ship
            # phase above already ran the delivery timelines and sealed
            # the tree, so the counters are final here.
            obs_events.emit("dcn_round", round=round_index, **acc.report())
        # Quorum-wait span: how long (simulated) the round held open before
        # committing — the streaming analog of the straggler wait.
        obs_events.emit(
            "quorum_wait", round=round_index, seconds=round(float(commit_s), 6),
            quorum=qcount, fresh=fresh, committed=committed,
        )
        if s.time_scale > 0 and commit_s > 0:
            # Map simulated waiting onto wall-clock so the wait is a real,
            # attributable host span (obs.trace host_rows), like the
            # synchronous driver's straggler sleep.
            with obs_spans.span(obs_scopes.QUORUM_WAIT):
                time.sleep(float(commit_s) * s.time_scale)

        if session is not None:
            # Stale carries (payload-bearing: a carried upload must
            # survive a crash even though its origin round's producer key
            # is gone) and the round_close seal — the durable half of the
            # transactional state commit below. The close record carries
            # the post-round dedup window so a compacted journal can
            # rebuild it without the dropped rounds' fold records.
            for up in pending_next:
                session.carry(
                    round_index, up.client, up.origin_round, up.nonce,
                    up.lands_at, up.lateness, up.c0, up.c1,
                )
            for tp in pending_tiers_next:
                # Payload-bearing like `carry`: a carried HOST partial must
                # survive a crash even though its origin round's tier
                # journals are gone by the time it folds.
                session.tier_carry(
                    round_index, tp.host, tp.origin_round, tp.clients,
                    tp.lateness, tp.c0, tp.c1,
                )
            session.close(
                round_index, committed, surviving, meta.excluded, seen
            )

        # Commit the transactional cross-round state — only a round that
        # ran to completion updates it; a raise anywhere above leaves the
        # previous round's carried uploads and dedup window intact for
        # the driver's retry.
        self._pending = pending_next
        self._pending_tiers = pending_tiers_next
        self._seen = seen
        if ef_on:
            self._ef_residual = ef_next
        # Peak dedup-window occupancy (ISSUE 19): gauged every round so a
        # duplicate storm's memory high-water mark is observable against
        # the (tau + 2) x cohort bound DedupWindow documents.
        obs_metrics.gauge("stream.dedup_window_peak").set(
            seen.peak_entries
        )

        if committed:
            sum_c0, sum_c1 = acc.value(like_shape=row_shape)
        else:
            # Below quorum nothing is released: hand back an encryption of
            # zero, NOT the partial sum — a sub-quorum aggregate is both
            # semantically void (the driver carries the model) and more
            # privacy-sensitive than a full one (fewer contributors).
            sum_c0 = np.zeros(row_shape, np.uint32)
            sum_c1 = np.zeros(row_shape, np.uint32)
        ct_sum = Ciphertext(
            c0=jnp.asarray(sum_c0), c1=jnp.asarray(sum_c1), scale=cts.scale
        )
        if tracer is not None:
            # Seal the root over everything on the virtual clock: the last
            # arrival, the commit point, and (hierarchical rounds) the
            # ship phase's landing horizon.
            tracer.finish(max(
                float(commit_s), float(last_t),
                float(getattr(acc, "ships_done_s", 0.0) or 0.0),
            ))
        return ct_sum, mets, overflow, smeta
