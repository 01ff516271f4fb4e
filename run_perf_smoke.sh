#!/bin/bash
# CPU perf smoke: proves the MFU/roofline + attribution machinery
# end-to-end on the driver box before any TPU window is spent on it.
# Runs the MFU_SMOKE train-step ladder and the PROFILE_SMOKE attribution
# harness, then gates on the artifact SCHEMA:
#   (a) every mfu_probe row carries mfu / images_per_s / xla_flops;
#   (b) the attribution JSON carries phase_roofline records for every
#       phase and the augment backend choice;
#   (c) no clamped attribution row is negative, and any negative RAW delta
#       is flagged attribution_unreliable (the -17.7% validation row class
#       of bug fails here, on CPU, instead of poisoning TPU evidence);
#   (d) the client_fusion backend record and the fused-vs-vmap comparison
#       rows (seconds/mfu/images_per_s per backend + speedup) are present
#       — the ISSUE-3 schema every bench artifact now carries;
#   (e) the he_backend record and the he_roofline rows (ISSUE 4): every HE
#       phase (encrypt/aggregate/decrypt) must carry non-null int_ops /
#       int_ops_per_s / bytes / bytes_per_s, and the decrypt/evaluate
#       phase_roofline rows must no longer ship flops/mfu nulls;
#   (f) trace-native attribution (ISSUE 5): profile_round runs with
#       --profile, and the resulting trace_attribution record must carry
#       attribution_source: "trace", per-phase device-time rows from ONE
#       program's profiler trace, and a round-program sum-vs-wall
#       agreement within 15%;
#   (g) no utilization row anywhere in the artifact exceeds 1.0 without a
#       timing_floor_suspect flag (the impossible 6.19x aggregate row
#       class of bug);
#   (h) structured run events (ISSUE 5): a tiny CLI experiment writes
#       events.jsonl, which must parse strictly (obs.events.read_events)
#       and carry the experiment_start/round_phase/round_end/
#       experiment_end schema;
#   (i) packed quantized aggregation (ISSUE 6): the packing record and the
#       bytes_on_wire rows must be present and non-null, the packed
#       uplink/ciphertext count must shrink ~k-fold, and the measured
#       speedups must clear the floors — standalone encrypt and decrypt
#       core >= 1.5x at k=4, he_in_round speedup >= 1.5x;
#   (j) static analysis (ISSUE 8): the fast hefl-lint gate exits clean,
#       and the CLI run's experiment_end metrics embed
#       analysis.violations = 0 plus an analysis_check event (proof the
#       pre-flight range/lint certification ran on this tree);
#   (k) hybrid-HE uplink (ISSUE 11): --hhe must map to
#       StreamConfig(upload_kind='hhe') and refuse to run unpacked; a
#       tiny streaming run under HHE must carry the hhe wire record with
#       measured expansion_hhe <= 1.1x over the plain quantized bytes and
#       an hhe.uploads_transciphered counter equal to cohort x rounds;
#       and its final params must be BITWISE equal to the direct
#       packed-CKKS twin's — the transcipher-vs-direct parity gate at
#       the whole-experiment level;
#   (l) encrypted-inference certification (ISSUE 12): the smoke serving
#       bench runs with the certify_inference pre-flight — both serving
#       rings' rotate-and-sum ladders certify (canonical carries at any
#       ladder depth, gadget products inside the 2**62 wall) and the
#       bench's analysis_check row must report violations = 0, the same
#       analysis.violations evidence training artifacts embed;
#   (m) serving throughput (ISSUE 13): the BENCH_INFER artifact must
#       carry the QPS + latency-percentile schema (p50/p95/p99) on every
#       row, the certify_keyswitch gadget certificates alongside the
#       ladder ones, the he_backend record, and a batched-vs-single
#       serving speedup (slot-packed + ct-batched BSGS vs single-query)
#       clearing the >= 1.3x floor on the CPU smoke; additionally
#       (ISSUE 18) the hoisted-rotation gates — hoisted/unhoisted BSGS
#       parity shas bitwise-equal, strictly fewer forward NTTs per score
#       hoisted, >= 1.3x hoisted QPS over the per-step twin — and the
#       composed mlp_bsgs gates (parity shas equal, fewer key-switches
#       than the per-class hidden ladders);
#   (n) cohort-only training (ISSUE 15): the cohort_compare record
#       (full-C vs cohort-only producer seconds, bucket chosen, devices
#       per mesh axis) must be present with bitwise_equal true — the
#       committed aggregate of the cohort-gathered producer hash-equal to
#       the full-C masked path — and the cohort-only speedup at
#       cohort 2-of-16 must clear the >= 2x floor on the CPU smoke;
#   (o) hierarchical aggregation (ISSUE 16): the standalone BENCH_DCN
#       smoke record — flat O(cohort) vs two-tier O(hosts) cross-host
#       bytes at cohort 8-of-16 over 4 hosts — must clear the
#       cohort/hosts*0.8 bytes-ratio floor with the committed aggregates
#       bitwise-equal in every tested arrival order;
#   (p) server hot path at load (ISSUE 19): the standalone BENCH_LOAD
#       smoke trace (10**4 simulated clients, synthetic bodies, REAL
#       journal/dedup/fold machinery) — group-commit journal sha-equal
#       to the unbatched twin with fsyncs/round <= 1/10 of
#       fsync_policy=always, vectorized fold ingest sha-equal to the
#       sequential fold, dedup-window peak within the (tau+2)*cohort
#       bound, and the folds/s + appends-per-fsync throughput floors;
#   (q) round-lifecycle spans + latency percentiles (ISSUE 20): a faulty
#       streaming round must export a Chrome-trace-viewer-loadable span
#       timeline (hefl.span.* names) whose per-kind span counts equal
#       the stream.*/dcn.*/journal.* counter deltas EXACTLY
#       (obs.spans.conservation_errors == []), and the BENCH_LOAD smoke
#       artifact (now run with --sweep) must carry the commit-latency-
#       percentiles-vs-(cohort, quorum) family: >= 3 points, every point
#       committed with p50 <= p95 <= p99.
# Needs no chip: both harnesses pin themselves to CPU in smoke mode.
set -euo pipefail
cd "$(dirname "$0")"

workdir=$(mktemp -d)
# mfu_probe.json is TPU evidence when produced WITHOUT MFU_SMOKE;
# shelter any committed copy from the smoke run's overwrite. The restore
# lives in the EXIT trap so a failure or Ctrl-C between the overwrite and
# the restore cannot clobber committed evidence (the backup would
# otherwise vanish with $workdir).
trap '[ -f "$workdir/mfu_probe.json.orig" ] && mv "$workdir/mfu_probe.json.orig" mfu_probe.json; rm -rf "$workdir"' EXIT
[ -f mfu_probe.json ] && cp mfu_probe.json "$workdir/mfu_probe.json.orig"

MFU_SMOKE=1 python mfu_probe.py > "$workdir/mfu_smoke.md"
mv mfu_probe.json "$workdir/mfu_probe.json"
if [ -f "$workdir/mfu_probe.json.orig" ]; then
  mv "$workdir/mfu_probe.json.orig" mfu_probe.json
fi

PROFILE_SMOKE=1 python profile_round.py --profile "$workdir/trace" \
  > "$workdir/profile_smoke.out"

# (h) events.jsonl end-to-end: one tiny CPU experiment through the CLI
# with the event writer pointed into the workdir.
JAX_PLATFORMS=cpu HEFL_EVENTS=1 python -m hefl_tpu.cli \
  --dataset mnist --model smallcnn --num-clients 2 --rounds 1 --epochs 1 \
  --batch-size 8 --n-train 64 --n-test 32 --he-n 256 --no-save-model \
  --events "$workdir/events.jsonl" --json > "$workdir/events_run.out"

# (j) static analysis (ISSUE 8): the fast hefl-lint gate must come back
# clean — source sweep, exact-integer region lint, range certification of
# the packing grid, hot-path rem/div/f64/callback lint, donation check.
# Any violation fails the smoke here, before TPU evidence is spent on a
# tree that breaks its own invariants.
JAX_PLATFORMS=cpu python -m hefl_tpu.analysis --fast --json \
  > "$workdir/hefl_lint.jsonl" || {
  echo "PERF SMOKE FAILED: hefl-lint violations:"
  cat "$workdir/hefl_lint.jsonl"
  exit 1
}

# (l)+(m) encrypted-inference certification + serving throughput
# (ISSUE 12/13): the serving bench at smoke geometry with the
# certify_inference + certify_keyswitch pre-flight; the BENCH_INFER
# artifact must carry the QPS/percentile schema, 0 violations, the
# keyswitch gadget certificates, and the >= 1.3x batched-vs-single floor.
INFERENCE_SMOKE=1 INFERENCE_REPS=3 JAX_PLATFORMS=cpu \
BENCH_INFER_PATH="$workdir/BENCH_INFER.json" \
python bench_inference.py > "$workdir/inference_smoke.out" || {
  echo "PERF SMOKE FAILED: bench_inference (serving pre-flight):"
  tail -20 "$workdir/inference_smoke.out"
  exit 1
}
python - "$workdir/BENCH_INFER.json" <<'PY'
import json
import sys

fail = []
try:
    art = json.load(open(sys.argv[1]))
except (OSError, ValueError) as e:
    print(f"PERF SMOKE FAILED: BENCH_INFER artifact unreadable: {e}")
    sys.exit(1)

rows = art.get("rows") or []
if len(rows) < 5:
    fail.append(f"BENCH_INFER: expected >= 5 serving rows, got {len(rows)}")
for r in rows:
    for field in ("plan", "batch", "keyswitches_per_score", "p50_ms",
                  "p95_ms", "p99_ms", "qps", "max_abs_err", "argmax_ok"):
        if r.get(field) is None:
            fail.append(f"BENCH_INFER row {r.get('row')}: missing {field}")
    if r.get("argmax_ok") is not True:
        fail.append(f"BENCH_INFER row {r.get('row')}: argmax_ok false")
plans = {r.get("plan") for r in rows}
if not {"ladder", "bsgs", "mlp", "bsgs_hoisted", "bsgs_unhoisted",
        "mlp_bsgs"} <= plans:
    fail.append(
        f"BENCH_INFER: plans {plans} missing "
        "ladder/bsgs/mlp/bsgs_hoisted/bsgs_unhoisted/mlp_bsgs rows"
    )

# Hoisted-rotation gates (ISSUE 18): the hoisted and unhoisted runs of
# the SAME plan must be bitwise-equal (shared uncentered decomposition —
# identical digits, exact modular arithmetic), the hoisted run must pay
# strictly fewer forward NTTs per score, and the saved NTTs must show up
# as QPS: >= 1.3x over the per-step twin even on the CPU smoke geometry.
hoist = art.get("hoisted") or {}
if hoist.get("parity") is not True or not hoist.get("parity_sha_hoisted"):
    fail.append(
        "BENCH_INFER: hoisted/unhoisted BSGS parity shas differ — the "
        "shared decomposition changed the ciphertext bits"
    )
hn, un = hoist.get("hoisted_ntts_per_score"), hoist.get(
    "unhoisted_ntts_per_score")
if not (isinstance(hn, int) and isinstance(un, int) and hn < un):
    fail.append(
        f"BENCH_INFER: hoisted forward NTTs/score ({hn}) must be strictly "
        f"below unhoisted ({un})"
    )
hs = hoist.get("speedup")
if not isinstance(hs, (int, float)):
    fail.append("BENCH_INFER: missing hoisted.speedup")
elif hs < 1.3:
    fail.append(
        f"BENCH_INFER: hoisted-vs-unhoisted QPS speedup {hs}x is below "
        "the 1.3x floor (sharing the gadget decomposition across the "
        "baby sweep should save far more than this)"
    )

# Composed MLP gates (ISSUE 18): the two-layer BSGS program's hoisted and
# unhoisted runs must also be bitwise-equal, and it must beat the
# per-class hidden ladders on key-switches per score.
mcmp = art.get("mlp_compare") or {}
if mcmp.get("parity") is not True or not mcmp.get("parity_sha_hoisted"):
    fail.append(
        "BENCH_INFER: mlp_bsgs hoisted/unhoisted parity shas differ"
    )
lks = mcmp.get("ladder_keyswitches_per_score")
bks = mcmp.get("mlp_bsgs_keyswitches_per_score")
if not (isinstance(lks, (int, float)) and isinstance(bks, (int, float))
        and bks < lks):
    fail.append(
        f"BENCH_INFER: mlp_bsgs keyswitches/score ({bks}) must be below "
        f"the ladder MLP's ({lks})"
    )

check = art.get("analysis_check") or {}
if check.get("violations") != 0:
    fail.append(
        f"BENCH_INFER: analysis.violations = {check.get('violations')} "
        "on the smoke serving rings"
    )
certs = check.get("certified") or []
if len(certs) < 4 or not all("CERTIFIED" in c for c in certs):
    fail.append(
        f"BENCH_INFER: expected 4 CERTIFIED summaries (ladder + keyswitch "
        f"gadget per serving ring), got {len(certs)}"
    )
if not any("keyswitch gadget" in c for c in certs):
    fail.append("BENCH_INFER: no certify_keyswitch gadget certificate")

if not isinstance(art.get("he_backend"), dict):
    fail.append("BENCH_INFER: missing he_backend record")

bvs = art.get("batched_vs_single") or {}
speedup = bvs.get("speedup")
if not isinstance(speedup, (int, float)):
    fail.append("BENCH_INFER: missing batched_vs_single.speedup")
elif speedup < 1.3:
    fail.append(
        f"BENCH_INFER: batched-vs-single serving speedup {speedup}x is "
        "below the 1.3x floor (slot packing + ct batching should amortize "
        "far more than this)"
    )

if fail:
    print("PERF SMOKE FAILED (inference stage):")
    for f in fail:
        print(" -", f)
    sys.exit(1)
print(
    f"inference smoke OK: {len(rows)} serving rows with QPS/p50/p95/p99, "
    f"{len(certs)} certificates (ladder + keyswitch gadget per ring), "
    f"analysis.violations=0, batched-vs-single {speedup}x (>= 1.3x), "
    f"hoisted-vs-unhoisted {hs}x (>= 1.3x, parity shas equal, "
    f"{hn} < {un} forward NTTs/score), mlp_bsgs {bks} < {lks} "
    "keyswitches/score (parity shas equal)"
)
PY

# (o) hierarchical aggregation (ISSUE 16): the standalone BENCH_DCN
# producer at the cohort-8-of-16 / 4-host smoke geometry. Flat-vs-
# hierarchical cross-host bytes must clear the cohort/hosts*0.8 ratio
# floor and the committed aggregates must be bitwise-equal in EVERY
# tested arrival order (identity/reversed/shuffled, each with duplicate
# redeliveries) — the module itself exits nonzero on either gate, and
# the schema gate below keeps the artifact honest.
JAX_PLATFORMS=cpu python -m hefl_tpu.fl.hierarchy \
  --out "$workdir/BENCH_DCN_SMOKE.json" > "$workdir/dcn_smoke.out" || {
  echo "PERF SMOKE FAILED: BENCH_DCN gates (bytes ratio / bitwise equality):"
  tail -20 "$workdir/dcn_smoke.out"
  exit 1
}
python - "$workdir/BENCH_DCN_SMOKE.json" <<'PY'
import json
import sys

fail = []
art = json.load(open(sys.argv[1]))
rec = art.get("dcn_compare")
if not isinstance(rec, dict):
    fail.append("BENCH_DCN: missing dcn_compare record")
    rec = {}
for field in ("num_clients", "cohort_size", "num_hosts", "ct_bytes",
              "flat_dcn_bytes", "hier_dcn_bytes", "per_link",
              "shipping_hosts", "bytes_ratio", "ratio_floor",
              "arrival_orders", "bitwise_equal",
              # faulty-uplink schema (ISSUE 17): every row carries the
              # retry/quorum fields (zero on clean links) so dashboards
              # can rely on them unconditionally
              "ship_retries", "ship_lost", "ship_deduped",
              "missed_hosts", "released"):
    if rec.get(field) is None:
        fail.append(f"BENCH_DCN: dcn_compare.{field} missing/null")
if rec.get("missed_hosts"):
    fail.append(
        f"BENCH_DCN: clean-link geometry missed hosts "
        f"{rec.get('missed_hosts')} — the quorum fields must be zero here"
    )
if rec.get("bitwise_equal") is not True:
    fail.append(
        "BENCH_DCN: hierarchical aggregate is NOT bitwise-equal to the "
        "flat fold across the tested arrival orders"
    )
ratio, floor = rec.get("bytes_ratio"), rec.get("ratio_floor")
if (
    isinstance(ratio, (int, float)) and isinstance(floor, (int, float))
    and ratio < floor
):
    fail.append(
        f"BENCH_DCN: flat/hier bytes ratio {ratio}x is below the "
        f"cohort/hosts floor {floor}x — the hierarchy is not O(hosts)"
    )
links = rec.get("per_link")
if isinstance(links, dict) and len(links) != rec.get("num_hosts"):
    fail.append(
        f"BENCH_DCN: per_link has {len(links)} uplinks for "
        f"{rec.get('num_hosts')} hosts"
    )
if fail:
    print("PERF SMOKE FAILED (DCN stage):")
    for f in fail:
        print(" -", f)
    sys.exit(1)
print(
    f"dcn smoke OK: flat {rec['flat_dcn_bytes']}B vs hier "
    f"{rec['hier_dcn_bytes']}B = {ratio}x (floor {floor}x), "
    f"bitwise-equal across {len(rec['arrival_orders'])} arrival orders"
)
PY

# (p) server hot path at load (ISSUE 19): the BENCH_LOAD smoke trace.
# The module itself exits nonzero when any of its gates fail (group-
# commit sha-equality, fsync ratio, batched-fold sha-equality, dedup
# bound, EF geometry); the schema gate below adds the CI throughput
# floors so a silent order-of-magnitude regression in the hot path
# cannot ship with a green artifact.
JAX_PLATFORMS=cpu python -m hefl_tpu.fl.load --smoke --sweep \
  --out "$workdir/BENCH_LOAD_SMOKE.json" > "$workdir/load_smoke.out" || {
  echo "PERF SMOKE FAILED: BENCH_LOAD gates (sha equality / fsync ratio):"
  tail -20 "$workdir/load_smoke.out"
  exit 1
}
python - "$workdir/BENCH_LOAD_SMOKE.json" <<'PY'
import json
import sys

fail = []
art = json.load(open(sys.argv[1]))
rec = art.get("bench_load")
if not isinstance(rec, dict):
    fail.append("BENCH_LOAD: missing bench_load record")
    rec = {}
for field in ("config", "runs", "group_commit", "batched_fold", "dedup",
              "fold_throughput", "recovery", "gather", "ef_packing", "ok"):
    if rec.get(field) is None:
        fail.append(f"BENCH_LOAD: bench_load.{field} missing/null")
if rec.get("ok") is not True:
    fail.append("BENCH_LOAD: harness gates not ok")
g = rec.get("group_commit") or {}
if g.get("sha_equal") is not True:
    fail.append(
        "BENCH_LOAD: group-commit journal NOT sha-equal to the "
        "unbatched twin"
    )
ratio = g.get("fsync_ratio")
if not (isinstance(ratio, (int, float)) and ratio <= 0.1):
    fail.append(
        f"BENCH_LOAD: grouped fsyncs/round ratio {ratio} exceeds the "
        "1/10-of-always budget"
    )
runs = rec.get("runs") or {}
grouped = runs.get("commit_grouped") or {}
for name, run in runs.items():
    for field in ("appends", "fsyncs", "fsyncs_per_round", "appends_per_s",
                  "folds_per_s", "commit_latency_s", "dedup_window_peak",
                  "sum_sha", "journal_bytes_sha"):
        if run.get(field) is None:
            fail.append(f"BENCH_LOAD: runs.{name}.{field} missing/null")
# CI throughput floors (CPU smoke, deliberately conservative: the
# observed hot path runs orders of magnitude above both).
folds_s = grouped.get("folds_per_s") or 0
if folds_s < 2000:
    fail.append(
        f"BENCH_LOAD: commit_grouped folds/s = {folds_s} below the 2000 "
        "CPU floor — the vectorized ingest hot path regressed"
    )
appends = grouped.get("appends") or 0
fsyncs = max(grouped.get("fsyncs") or 0, 1)
if appends / fsyncs < 10:
    fail.append(
        f"BENCH_LOAD: {appends} appends over {fsyncs} fsyncs < 10 "
        "appends/fsync — group commit is not actually batching"
    )
bf = rec.get("batched_fold") or {}
if bf.get("sha_equal") is not True:
    fail.append(
        "BENCH_LOAD: batched fold ingest NOT sha-equal to sequential"
    )
dd = rec.get("dedup") or {}
if not (isinstance(dd.get("peak"), int) and dd.get("ok") is True):
    fail.append(
        f"BENCH_LOAD: dedup window peak {dd.get('peak')} outside the "
        f"(tau+2)*cohort bound {dd.get('bound')}"
    )
ef = rec.get("ef_packing") or {}
if ef.get("bytes_ratio_ok") is not True or ef.get("certified") is not True:
    fail.append(
        "BENCH_LOAD: EF b=4 deeper-k geometry missing its bytes-ratio "
        "<= 0.55 budget or its carry-free certification"
    )
if fail:
    print("PERF SMOKE FAILED (LOAD stage):")
    for f in fail:
        print(" -", f)
    sys.exit(1)
print(
    f"load smoke OK: {rec['config']['num_clients']} clients, "
    f"folds/s={folds_s}, fsync_ratio={ratio} (budget 0.1), "
    f"{appends} appends / {fsyncs} fsyncs, "
    f"ef_bytes={ef.get('bytes_ratio_b4_vs_b8')} (budget 0.55)"
)
PY

# (q) round-lifecycle spans (ISSUE 20): drive one faulty streaming round
# with span tracing on, export the Chrome trace, and gate BOTH halves of
# the contract — the exported timeline loads through the repo's own
# trace parser with hefl.span.* names, and the per-kind span counts
# equal the counter deltas exactly. Then schema-gate the sweep family
# stage (p) just wrote into BENCH_LOAD_SMOKE.json.
JAX_PLATFORMS=cpu python - "$workdir" <<'PY'
import sys

import jax
import jax.numpy as jnp

from hefl_tpu.ckks.keys import CkksContext, keygen
from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
from hefl_tpu.fl import FaultConfig, StreamConfig, StreamEngine, TrainConfig
from hefl_tpu.models import SmallCNN
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import spans as obs_spans
from hefl_tpu.obs import trace as obs_trace
from hefl_tpu.parallel import make_mesh

workdir = sys.argv[1]
fail = []
num_clients = 8
n = num_clients * 8
(x, y), _, _ = make_dataset("mnist", seed=0, n_train=n, n_test=8)
xs, ys = stack_federated(x, y, iid_contiguous(n, num_clients))
model = SmallCNN(num_classes=10)
params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
mesh = make_mesh(num_clients)
ctx = CkksContext.create(n=256)
_, pk = keygen(ctx, jax.random.key(1))
cfg = TrainConfig(epochs=1, batch_size=4, num_classes=10, augment=False,
                  val_fraction=0.25)
eng = StreamEngine(
    StreamConfig(quorum=0.75, staleness_rounds=1, seed=3, deadline_s=20.0),
    FaultConfig(seed=5, straggler_fraction=0.3, straggler_delay_s=30.0,
                duplicate_clients=1, transient_fail_clients=1),
)
tracers = []
for r in range(2):
    base = obs_metrics.snapshot()
    _, _, _, sm = eng.run_round(
        model, cfg, mesh, ctx, pk, params, jnp.asarray(xs), jnp.asarray(ys),
        jax.random.key(100 + r), r,
    )
    delta = obs_metrics.snapshot_delta(base)
    tracer = eng.last_spans
    tracers.append(tracer)
    errs = obs_spans.conservation_errors(tracer.counts(), delta)
    for e in errs:
        fail.append(f"SPANS round {r}: {e}")
    if tracer.counts().get("fold", 0) != sm.fresh + sm.stale_folded:
        fail.append(
            f"SPANS round {r}: fold spans "
            f"{tracer.counts().get('fold', 0)} != fresh+stale "
            f"{sm.fresh + sm.stale_folded}"
        )
out = f"{workdir}/spans.trace.json.gz"
obs_spans.export_chrome_trace(out, tracers)
events = obs_trace.load_trace_events(out)
want = sum(len(t.spans()) for t in tracers)
if len(events) != want:
    fail.append(f"SPANS export: {len(events)} trace events != {want} spans")
names = {e.get("name") for e in events}
legal = {f"hefl.span.{k}" for k in obs_spans.SPAN_KINDS}
if not names <= legal:
    fail.append(f"SPANS export: illegal names {sorted(names - legal)}")
for must in ("hefl.span.round", "hefl.span.arrival", "hefl.span.fold",
             "hefl.span.commit"):
    if must not in names:
        fail.append(f"SPANS export: {must} missing from the timeline")
for e in events:
    if e.get("ph") != "X" or not isinstance(e.get("ts"), (int, float)) \
            or not isinstance(e.get("dur"), (int, float)) or e["dur"] < 0:
        fail.append(f"SPANS export: malformed event {e.get('name')}")
        break
if fail:
    print("PERF SMOKE FAILED (SPANS stage):")
    for f in fail:
        print(" -", f)
    sys.exit(1)
print(
    f"spans smoke OK: 2 faulty rounds conserved "
    f"({want} spans == counter deltas), export loadable "
    f"({len(names)} kinds)"
)
PY

python - "$workdir/BENCH_LOAD_SMOKE.json" <<'PY'
import json
import sys

fail = []
art = json.load(open(sys.argv[1]))
sw = (art.get("bench_load") or {}).get("commit_latency_sweep")
if not isinstance(sw, dict):
    fail.append("SWEEP: bench_load.commit_latency_sweep missing")
    sw = {}
pts = sw.get("points") or []
if len(pts) < 3:
    fail.append(f"SWEEP: {len(pts)} points < 3 — not a family")
if sw.get("ok") is not True:
    fail.append("SWEEP: family gates not ok")
combos = set()
for p in pts:
    combos.add((p.get("cohort_size"), p.get("quorum")))
    lat = p.get("commit_latency_s") or {}
    p50, p95, p99 = lat.get("p50"), lat.get("p95"), lat.get("p99")
    if not all(isinstance(v, (int, float)) for v in (p50, p95, p99)):
        fail.append(f"SWEEP: point {p.get('cohort_size')}x"
                    f"{p.get('quorum')} missing p50/p95/p99")
    elif not (p50 <= p95 <= p99):
        fail.append(f"SWEEP: point {p.get('cohort_size')}x"
                    f"{p.get('quorum')}: p50 {p50} <= p95 {p95} <= "
                    f"p99 {p99} violated")
    if not p.get("committed_rounds"):
        fail.append(f"SWEEP: point {p.get('cohort_size')}x"
                    f"{p.get('quorum')} committed no rounds")
if len(combos) != len(pts):
    fail.append("SWEEP: duplicate (cohort_size, quorum) points")
if fail:
    print("PERF SMOKE FAILED (SWEEP stage):")
    for f in fail:
        print(" -", f)
    sys.exit(1)
print(f"sweep smoke OK: {len(pts)} (cohort, quorum) points, "
      "p50<=p95<=p99 everywhere")
PY

# (k) hybrid-HE uplink (ISSUE 11): wire expansion <= 1.1x + the
# transcipher-vs-direct bitwise parity gate, at experiment level. The
# streaming engine shards clients over the virtual device mesh (same
# emulation the test suite uses).
JAX_PLATFORMS=cpu \
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2" \
python - <<'PY'
import dataclasses
import hashlib
import sys

import numpy as np
import jax

from hefl_tpu.cli import build_parser, config_from_args
from hefl_tpu.experiment import ExperimentConfig, HEConfig, run_experiment
from hefl_tpu.fl import HheConfig, PackingConfig, StreamConfig, TrainConfig

fail = []

# The CLI flag path: --hhe maps to upload_kind=hhe + an HheConfig, and
# refuses to run without packing (the cipher lives in the packed domain).
argv = ["--dataset", "mnist", "--model", "smallcnn", "--num-clients", "2",
        "--rounds", "1", "--pack-bits", "8", "--hhe", "--hhe-key-seed", "5"]
cfg_cli = config_from_args(build_parser().parse_args(argv))
if cfg_cli.stream is None or cfg_cli.stream.upload_kind != "hhe":
    fail.append("cli: --hhe did not map to StreamConfig(upload_kind='hhe')")
if cfg_cli.hhe is None or cfg_cli.hhe.key_seed != 5:
    fail.append("cli: --hhe-key-seed did not reach the HheConfig")
try:
    config_from_args(build_parser().parse_args(["--dataset", "mnist", "--hhe"]))
    fail.append("cli: --hhe without --pack-bits was not rejected")
except SystemExit:
    pass

base = ExperimentConfig(
    model="smallcnn", dataset="mnist", num_clients=2, rounds=1,
    encrypted=True, he=HEConfig(n=256), seed=0, n_train=64, n_test=32,
    train=TrainConfig(num_classes=10, epochs=1, batch_size=8,
                      augment=False, val_fraction=0.25),
    packing=PackingConfig(bits=8, interleave=2, clip=0.5),
    stream=StreamConfig(quorum=1.0),
)
print("hhe smoke: direct packed-CKKS twin ...", flush=True)
direct = run_experiment(base, verbose=False)
hcfg = dataclasses.replace(
    base,
    stream=dataclasses.replace(base.stream, upload_kind="hhe"),
    hhe=HheConfig(key_seed=0),
)
print("hhe smoke: hybrid-HE twin (upload_kind=hhe) ...", flush=True)
hrun = run_experiment(hcfg, verbose=False)

rec = hrun.get("hhe")
if not isinstance(rec, dict):
    fail.append("hhe run: result carries no hhe wire record")
else:
    for field in ("hhe_upload", "plain_quantized", "ciphertext_packed",
                  "expansion_hhe", "reduction_vs_ckks"):
        if rec.get(field) is None:
            fail.append(f"hhe record: {field} missing/null")
    exp = rec.get("expansion_hhe")
    if not isinstance(exp, (int, float)) or exp > 1.1:
        fail.append(
            f"hhe record: measured wire expansion {exp} > the 1.1x gate "
            "over the plain quantized bytes"
        )
    red = rec.get("reduction_vs_ckks")
    if isinstance(red, (int, float)) and red < 1.2:
        fail.append(
            f"hhe record: uplink only {red}x smaller than the packed CKKS "
            "ciphertext it replaces"
        )

metrics = (hrun.get("obs") or {}).get("metrics") or {}
want = base.num_clients * base.rounds
got = metrics.get("hhe.uploads_transciphered", 0)
if got != want:
    fail.append(
        f"hhe counters: uploads_transciphered {got} != cohort x rounds "
        f"{want}"
    )

def _sha(tree):
    return hashlib.sha256(b"".join(
        np.ascontiguousarray(np.asarray(leaf)).tobytes()
        for leaf in jax.tree_util.tree_leaves(tree)
    )).hexdigest()

sha_d, sha_h = _sha(direct["params"]), _sha(hrun["params"])
if sha_d != sha_h:
    fail.append(
        "hhe parity: final params under HHE transciphering differ bitwise "
        f"from the direct packed-CKKS twin ({sha_h[:16]} != {sha_d[:16]})"
    )

if fail:
    print("PERF SMOKE FAILED (hhe stage):")
    for f in fail:
        print(" -", f)
    sys.exit(1)
print(
    f"hhe smoke OK: expansion_hhe {rec['expansion_hhe']}x (<= 1.1x), "
    f"{rec['reduction_vs_ckks']}x below the packed CKKS uplink, "
    f"{got} uploads transciphered, final params sha256-equal to the "
    f"direct twin ({sha_d[:16]})"
)
PY

python - "$workdir/mfu_probe.json" "$workdir/profile_smoke.out" \
  "$workdir/events.jsonl" <<'PY'
import json
import sys

mfu_path, prof_path, events_path = sys.argv[1:4]
fail = []

probe = json.load(open(mfu_path))
if "peak_flops" not in probe or not probe.get("rows"):
    fail.append("mfu_probe.json: missing peak_flops/rows")
for row in probe.get("rows", []):
    for field in ("images_per_s", "xla_flops"):
        if row.get(field) is None:
            fail.append(
                f"mfu_probe.json row batch={row.get('batch')}: missing {field}"
            )
    # A CPU has no peak: the smoke's mfu is present and null.
    if "mfu" not in row or row["mfu"] is not None:
        fail.append(
            f"mfu_probe.json row batch={row.get('batch')}: a CPU smoke row "
            "must carry mfu: null"
        )
if "augment_backend" not in probe:
    fail.append("mfu_probe.json: missing augment_backend")

rec = None
for line in open(prof_path):
    line = line.strip()
    if not line.startswith("{"):
        continue
    try:
        cand = json.loads(line)
    except ValueError:
        continue
    if cand.get("metric") == "phase_attribution":
        rec = cand
if rec is None:
    fail.append("profile output: no phase_attribution JSON line")
else:
    roofline = rec.get("phase_roofline") or {}
    for phase in ("fused_round", "train_only", "decrypt", "evaluate"):
        stats = roofline.get(phase)
        if not isinstance(stats, dict) or not {
            "seconds", "mfu", "images_per_s"
        } <= set(stats):
            fail.append(
                f"profile: phase_roofline[{phase!r}] missing the "
                "seconds/mfu/images_per_s schema"
            )
    unreliable = rec.get("attribution_unreliable")
    if unreliable is None:
        fail.append("profile: missing attribution_unreliable flag")
    neg_raw = [
        k for k, v in rec.items()
        if k.endswith("_raw") and isinstance(v, (int, float)) and v < 0
    ]
    if neg_raw and unreliable is not True:
        fail.append(
            f"profile: negative raw deltas {neg_raw} not flagged "
            "attribution_unreliable"
        )
    for k in ("he_in_round_s", "augment_s", "per_epoch_val_s", "sgd_core_s"):
        if isinstance(rec.get(k), (int, float)) and rec[k] < 0:
            fail.append(f"profile: clamped attribution row {k} is negative")
    if "augment_backend" not in rec:
        fail.append("profile: missing augment_backend record")
    # Client-fusion schema gate (ISSUE 3): every profile artifact must
    # record the cross-client backend and the fused-vs-vmap comparison.
    cf = rec.get("client_fusion")
    if not isinstance(cf, dict) or "backend" not in cf:
        fail.append("profile: missing client_fusion backend record")
    cmp_rows = rec.get("client_fusion_compare")
    if not isinstance(cmp_rows, dict):
        fail.append("profile: missing client_fusion_compare rows")
    else:
        if "fused_speedup_vs_vmap" not in cmp_rows:
            fail.append("profile: client_fusion_compare missing "
                        "fused_speedup_vs_vmap")
        for bk in ("vmap", "fused"):
            row = cmp_rows.get(bk)
            if not isinstance(row, dict) or not {
                "seconds", "mfu", "images_per_s"
            } <= set(row):
                fail.append(
                    f"profile: client_fusion_compare[{bk!r}] missing the "
                    "seconds/mfu/images_per_s schema"
                )
        speedup = cmp_rows.get("fused_speedup_vs_vmap")
        if isinstance(speedup, (int, float)) and speedup < 1.0:
            print(
                f"WARNING: fused train round is {speedup}x vmap on this "
                "device — auto mode will keep picking vmap here"
            )
    # HE backend + roofline schema gate (ISSUE 4).
    hb = rec.get("he_backend")
    if not isinstance(hb, dict) or not hb.get("backend"):
        fail.append("profile: missing he_backend record")
    he = rec.get("he_roofline")
    if not isinstance(he, dict):
        fail.append("profile: missing he_roofline rows")
    else:
        for phase in ("encrypt", "aggregate", "decrypt"):
            row = he.get(phase)
            need = ("seconds", "int_ops", "int_ops_per_s", "bytes", "bytes_per_s")
            if not isinstance(row, dict) or not set(need) <= set(row):
                fail.append(
                    f"profile: he_roofline[{phase!r}] missing the "
                    "int-op/bandwidth schema"
                )
            else:
                nulls = [k for k in need if row.get(k) is None]
                if nulls:
                    fail.append(
                        f"profile: he_roofline[{phase!r}] null fields {nulls}"
                    )
    for phase in ("decrypt", "evaluate"):
        row = (rec.get("phase_roofline") or {}).get(phase) or {}
        if row.get("flops") is None:
            fail.append(
                f"profile: phase_roofline[{phase!r}].flops is still "
                "null — the HE roofline must fill it"
            )
        # A CPU has no peak: the smoke's utilization is present and null.
        if "mfu" not in row or row["mfu"] is not None:
            fail.append(
                f"profile: phase_roofline[{phase!r}].mfu must be null on "
                "the CPU smoke"
            )
    # (f) trace-native attribution: per-phase device time from ONE
    # program's trace, agreeing with the traced wall clock.
    if rec.get("attribution_source") != "trace":
        fail.append(
            "profile: attribution_source is "
            f"{rec.get('attribution_source')!r}, expected 'trace' "
            "(--profile ran)"
        )
    ta = rec.get("trace_attribution")
    if not isinstance(ta, dict) or not ta.get("rows"):
        fail.append("profile: missing trace_attribution rows")
    else:
        for ph in ("hefl.sgd_core", "hefl.encrypt", "hefl.psum_aggregate",
                   "hefl.decrypt", "hefl.evaluate"):
            row = ta["rows"].get(ph)
            if not isinstance(row, dict) or not row.get("device_seconds"):
                fail.append(
                    f"profile: trace_attribution missing/empty row {ph!r}"
                )
        agree = ta.get("round_wall_agreement")
        if not isinstance(agree, (int, float)) or not 0.85 <= agree <= 1.15:
            fail.append(
                "profile: trace rows do not sum to within 15% of the "
                f"traced round's wall clock (agreement {agree})"
            )
        if ta.get("suspected_truncated"):
            fail.append(
                "profile: trace hit the event-converter cap — attribution "
                "undercounts; shrink the traced geometry"
            )

    # (i) packed quantized aggregation schema + speedup floors (ISSUE 6).
    pk = rec.get("packing")
    if not isinstance(pk, dict):
        fail.append("profile: missing packing record")
    else:
        for field in ("bits", "interleave", "n_ct", "n_ct_unpacked",
                      "error_budget", "standalone_encrypt_packed_s",
                      "encrypt_speedup", "decrypt_core_packed_s",
                      "decrypt_speedup", "he_in_round_packed_s",
                      "he_roofline_packed"):
            if pk.get(field) is None:
                fail.append(f"profile: packing.{field} missing/null")
        # he_in_round_speedup is ablation-subtracted and null when the raw
        # delta went non-positive (documented fast-round noise) — the
        # single-program standalone floors below stay the hard gate.
        if pk.get("he_in_round_speedup") is None:
            print(
                "WARNING: packing.he_in_round_speedup null (ablation "
                "noise); relying on the standalone speedup floors"
            )
        k = pk.get("interleave") or 0
        if k and pk.get("n_ct") and pk.get("n_ct_unpacked"):
            if pk["n_ct"] > -(-pk["n_ct_unpacked"] // k):
                fail.append(
                    f"profile: packed n_ct {pk['n_ct']} is not the "
                    f"{k}-fold reduction of {pk['n_ct_unpacked']}"
                )
        for field, floor in (("encrypt_speedup", 1.5),
                             ("decrypt_speedup", 1.5),
                             ("he_in_round_speedup", 1.5)):
            v = pk.get(field)
            if isinstance(v, (int, float)) and v < floor:
                fail.append(
                    f"profile: packing.{field} = {v} below the {floor}x "
                    f"floor at k={k}"
                )
        hep = pk.get("he_roofline_packed") or {}
        for phase in ("encrypt", "decrypt"):
            row = hep.get(phase) or {}
            if row.get("bytes_per_s") is None:
                fail.append(
                    f"profile: he_roofline_packed[{phase!r}].bytes_per_s "
                    "is null"
                )
    bw = rec.get("bytes_on_wire")
    if not isinstance(bw, dict):
        fail.append("profile: missing bytes_on_wire record")
    else:
        for field in ("plain_update", "ciphertext_unpacked",
                      "ciphertext_packed", "packed_reduction"):
            if bw.get(field) is None:
                fail.append(f"profile: bytes_on_wire.{field} missing/null")
        k = (rec.get("packing") or {}).get("interleave") or 0
        red = bw.get("packed_reduction")
        if k and isinstance(red, (int, float)) and red < 0.9 * k:
            fail.append(
                f"profile: bytes_on_wire reduction {red} is not the ~{k}x "
                "the interleave factor promises"
            )

    # (n) cohort-only training (ISSUE 15): schema + bitwise equality +
    # the >= 2x cohort 2-of-16 speedup floor.
    cc = rec.get("cohort_compare")
    if not isinstance(cc, dict):
        fail.append("profile: missing cohort_compare record")
    else:
        for field in ("num_clients", "cohort_size", "bucket",
                      "full_c_train_s", "cohort_train_s", "speedup",
                      "devices_per_axis", "bitwise_equal"):
            if cc.get(field) is None:
                fail.append(f"profile: cohort_compare.{field} missing/null")
        if cc.get("bitwise_equal") is not True:
            fail.append(
                "profile: cohort-only committed aggregate is NOT hash-equal "
                "to the full-C masked path (cohort_compare.bitwise_equal)"
            )
        sp_c = cc.get("speedup")
        if isinstance(sp_c, (int, float)) and sp_c < 2.0:
            fail.append(
                f"profile: cohort-only speedup {sp_c}x at cohort 2-of-16 is "
                "below the 2x floor (training 2 slots instead of 16 should "
                "amortize far more than this)"
            )
        dpa = cc.get("devices_per_axis")
        if not isinstance(dpa, dict) or not {"clients", "ct"} <= set(dpa):
            fail.append(
                "profile: cohort_compare.devices_per_axis missing the "
                "clients/ct axes"
            )

    # (g) no unflagged utilization > 1.0 anywhere in the artifact.
    def scan_utils(node, path="rec"):
        if isinstance(node, dict):
            for field in ("mfu", "util_vs_peak_int_ops"):
                v = node.get(field)
                if isinstance(v, (int, float)) and v > 1.0:
                    fail.append(
                        f"{path}.{field} = {v} > 1.0 shipped without "
                        "clamping (timing_floor_suspect)"
                    )
            for k, v in node.items():
                scan_utils(v, f"{path}.{k}")

    scan_utils(rec)
    scan_utils(probe, "mfu_probe")

# (h) events.jsonl schema gate: strict parse + required event kinds.
sys.path.insert(0, ".")
from hefl_tpu.obs import events as obs_events  # noqa: E402

try:
    evs = obs_events.read_events(events_path)  # strict: malformed line fails
except (OSError, ValueError) as e:
    evs = []
    fail.append(f"events.jsonl unusable: {e}")
if evs:
    kinds = {e["event"] for e in evs}
    for needed in ("experiment_start", "round_phase", "round_end",
                   "experiment_end"):
        if needed not in kinds:
            fail.append(f"events.jsonl: missing {needed!r} event")
    phases_seen = {e["phase"] for e in evs if e["event"] == "round_phase"}
    if "train+encrypt+aggregate" not in phases_seen:
        fail.append(
            "events.jsonl: no round_phase for the fused train phase "
            f"(saw {sorted(phases_seen)})"
        )
    end = [e for e in evs if e["event"] == "experiment_end"]
    if end and not isinstance(end[-1].get("metrics"), dict):
        fail.append("events.jsonl: experiment_end carries no metrics snapshot")
    # (j) the analysis.violations counter must be EMBEDDED in the run's
    # metrics snapshot (proof the pre-flight static analysis ran) and be 0.
    if end and isinstance(end[-1].get("metrics"), dict):
        av = end[-1]["metrics"].get("analysis.violations")
        if av is None:
            fail.append(
                "events.jsonl: experiment_end metrics missing "
                "analysis.violations (pre-flight static analysis not run?)"
            )
        elif av != 0:
            fail.append(
                f"events.jsonl: analysis.violations = {av} (static "
                "invariant violations on the smoke config)"
            )
    if "analysis_check" not in kinds:
        fail.append("events.jsonl: missing 'analysis_check' event")

if fail:
    print("PERF SMOKE FAILED:")
    for f in fail:
        print(" -", f)
    sys.exit(1)
print(
    "perf smoke OK: MFU + roofline schema present on both artifacts, "
    "he_roofline rows non-null, no unflagged negative attribution rows, "
    "trace_attribution from one program agrees with the traced wall "
    "clock, no unflagged utilization > 1, events.jsonl schema valid, "
    "packing + bytes_on_wire rows present with the k-fold reduction and "
    ">=1.5x HE speedups, cohort_compare bitwise-equal with the >=2x "
    "cohort-only floor, BENCH_DCN flat-vs-hier ratio over the "
    "cohort/hosts floor with arrival-order bitwise equality, BENCH_LOAD "
    "group-commit sha-equal under the fsync + throughput floors with the "
    "commit-latency sweep family, span timelines conserved against the "
    "stream counters and trace-viewer loadable, hefl-lint clean with "
    "analysis.violations=0 embedded in the run metrics"
)
PY
