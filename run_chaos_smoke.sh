#!/bin/bash
# CPU chaos smoke: proves the fault-tolerant round engine end-to-end on the
# driver box — the robustness analog of run_perf_smoke.sh. Runs the
# `chaos-smoke` preset (25% scheduled dropout + one NaN-poisoned client per
# round + one simulated device loss, all deterministic via fl/faults.py)
# against its clean twin, then gates on:
#   (a) every round excluded EXACTLY the scheduled/poisoned clients
#       (asserted via the round metadata the masked engine returns);
#   (b) zero unflagged NaNs in the artifact: any non-finite per-client
#       metric must belong to a client the round metadata excluded, and
#       the final aggregated params must be finite;
#   (c) the faulted run's final accuracy is within tolerance of the clean
#       run's (a NaN client that leaks into the aggregate fails this hard);
#   (d) the simulated device-loss round really exercised the retry path;
#   (e) the structured run-event log (ISSUE 5): the faulted run writes
#       events.jsonl, whose per-round round_robust exclusion records and
#       round_retry events must match the deterministic fault schedule
#       EXACTLY, and whose experiment_end metrics counters must equal the
#       schedule's totals;
#   (f) packed quantized aggregation (ISSUE 6): the SAME faulted schedule
#       re-run with the b=8/k=2 packed upload must exclude the identical
#       clients, keep all params finite, and land within the accuracy
#       tolerance of the unpacked faulted run — quantization at the
#       declared budget must not change robustness behavior.
#   (g) streaming quorum aggregation (ISSUE 7): the faulted schedule plus
#       arrival-level faults (stragglers past the deadline, duplicate and
#       transiently-lost deliveries) run through the streaming engine:
#       every round must COMMIT at quorum, the per-round stream_round
#       events' arrival/dedup/retry counters and the cross-round staleness
#       bookkeeping must match the deterministic schedule EXACTLY, the
#       experiment_end stream.* counters must equal the per-round sums,
#       and the final accuracy must land within tolerance of the
#       synchronous faulted twin.
#   (h) durable aggregation / crash recovery (ISSUE 9): the streaming
#       schedule re-run under the write-ahead journal with a deterministic
#       mid-journal-append process crash (a REAL torn record on disk).
#       Re-running the config must recover — torn tail truncated, sealed
#       round replayed, persisted uploads re-folded — and the recovered
#       run's per-round canonical-sum sha256 chain must be BITWISE equal
#       to an uninterrupted journaled twin's, its final params bitwise
#       equal, and its recovery.* counters equal to the injected schedule
#       exactly.
#   (i) hybrid-HE uplink twin (ISSUE 11): the SAME streaming fault
#       schedule re-run with upload_kind=hhe — clients ship symmetric
#       stream-cipher word pairs and the server transciphers into CKKS
#       before the fold. Every round must still commit at quorum, the
#       stream.* counters must equal the direct streaming twin's schedule
#       totals exactly (the arrival machinery is cipher-agnostic), the
#       hhe wire record must show <= 1.1x expansion, final params must be
#       finite and the accuracy within tolerance of the synchronous
#       faulted run.
#   (j) cohort-only training twin (ISSUE 15): the streaming fault
#       schedule with a sampled cohort of 6-of-8, run through the
#       cohort-only producer (just the sampled slots gathered + trained)
#       AND the full-C producer. Every round must commit in both, the
#       unsampled exclusions must equal C - cohort each round, and the
#       two runs' final params must be BITWISE equal — the cohort gather
#       cannot change a single committed bit under the full chaos
#       schedule.
#   (k) hierarchical aggregation twin (ISSUE 16): the streaming schedule
#       re-run flat (num_hosts=0) AND through the two-tier fold tree
#       (num_hosts=4), under a duplicate storm and under a regional
#       outage (1 of 4 hosts dark — the --outage-hosts schedule, seen
#       identically by both twins). Every round must commit in both with
#       identical stream records, and the final params must be BITWISE
#       equal — the fold tree commits exactly the flat aggregate.
#   (l) lossy-DCN twin (ISSUE 17): the streaming schedule with the
#       tier->root uplinks faulted — transient ship loss (recovered by
#       the ship retry), duplicated delivery (root dedup), per-uplink
#       delay — vs the flat twin at the identical client schedule.
#       Committed rounds must stay BITWISE equal to flat, and the
#       retry/dedup/exclusion counters must equal the injected link
#       schedule exactly.
# Artifact: CHAOS_SMOKE.json (accuracy curves + per-round exclusions
# + the events.jsonl cross-checks, streaming + crash-recovery + HHE +
# cohort-only + hierarchical twins included).
# CPU-only: needs no chip.
set -euo pipefail
cd "$(dirname "$0")"

export JAX_PLATFORMS=cpu
# The preset's 8 clients need the virtual 8-device mesh (same emulation the
# test suite uses; harmless if XLA_FLAGS already pins a device count).
if [[ "${XLA_FLAGS:-}" != *xla_force_host_platform_device_count* ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
fi

# The faulted run's structured events land here; the clean twin runs with
# the writer disabled so the log is exactly one run's evidence. The
# streaming twin gets its OWN log so the two runs' counters never mix.
workdir=$(mktemp -d)
trap 'rm -rf "$workdir"' EXIT
export HEFL_EVENTS=1
export CHAOS_EVENTS_PATH="$workdir/events.jsonl"
export CHAOS_STREAM_EVENTS_PATH="$workdir/stream_events.jsonl"

python - <<'PY'
import dataclasses
import json
import math
import os
import sys

import numpy as np

from hefl_tpu.experiment import run_experiment
from hefl_tpu.fl import schedule_for_round
from hefl_tpu.obs import events as obs_events
from hefl_tpu.presets import PRESETS

ACC_TOL = 0.20   # tiny-run noise floor; a leaked NaN fails by orders more

events_path = os.environ["CHAOS_EVENTS_PATH"]
cfg = dataclasses.replace(PRESETS["chaos-smoke"], events_path=events_path)
clean_cfg = dataclasses.replace(
    cfg, faults=None, events_path="",
    train=dataclasses.replace(cfg.train, on_overflow="warn"),
)

print("chaos smoke: clean twin ...", flush=True)
clean = run_experiment(clean_cfg, verbose=False)
print("chaos smoke: faulted run ...", flush=True)
chaos = run_experiment(cfg, verbose=False)

# (f) packed twin of the faulted run (ISSUE 6): identical schedule, b=8
# quantized k=2-interleaved upload. The event log belongs to the unpacked
# run, so the packed twin runs with the writer off.
from hefl_tpu.fl import PackingConfig

packed_cfg = dataclasses.replace(
    cfg, events_path="",
    packing=PackingConfig(bits=8, interleave=2, clip=0.5),
)
print("chaos smoke: packed faulted twin (b=8 k=2) ...", flush=True)
packed = run_experiment(packed_cfg, verbose=False)

# (g) streaming twin (ISSUE 7): the same dropout/NaN schedule PLUS
# arrival-level faults — two stragglers whose uploads can miss the 2 s
# deadline (carried under tau=1), one duplicated delivery, one transient
# loss recovered by a single retry — through the streaming quorum engine.
# quorum=0.375 (3 of the 8-cohort) keeps every round committable even in
# the schedule's worst case.
from hefl_tpu.fl import StreamConfig, schedule_arrivals

stream_faults = dataclasses.replace(
    cfg.faults, straggler_fraction=0.25, straggler_delay_s=6.0,
    arrival_delay_s=0.5, duplicate_clients=1, transient_fail_clients=1,
)
stream_cfg = dataclasses.replace(
    cfg, faults=stream_faults,
    stream=StreamConfig(quorum=0.375, deadline_s=2.0, max_retries=1,
                        staleness_rounds=1, seed=0),
    events_path=os.environ["CHAOS_STREAM_EVENTS_PATH"],
)
print("chaos smoke: streaming twin (quorum 3/8, deadline 2s, tau 1) ...",
      flush=True)
streamed = run_experiment(stream_cfg, verbose=False)

fail = []
rounds = []
saw_retry = False
for r, rec in enumerate(chaos["history"]):
    rob = rec.get("robust")
    if rob is None:
        fail.append(f"round {r}: no robustness metadata in history")
        continue
    sched = schedule_for_round(cfg.faults, r, cfg.num_clients)
    expect = set(np.flatnonzero(sched.dropped).tolist()) | set(
        np.flatnonzero(sched.poison).tolist()
    )
    got = {i for i, p in enumerate(rob["participation"]) if not p}
    if got != expect:
        fail.append(
            f"round {r}: excluded {sorted(got)} but schedule says "
            f"{sorted(expect)}"
        )
    saw_retry = saw_retry or rob["round_retries"] > 0
    # (b) unflagged-NaN gate: every non-finite per-client metric must be an
    # excluded client's.
    for name in ("val_loss", "val_acc"):
        for i, v in enumerate(rec[name]):
            if not math.isfinite(v) and i not in got:
                fail.append(
                    f"round {r}: client {i} has non-finite {name} but was "
                    "NOT excluded"
                )
    rounds.append(
        {"round": r, "accuracy": rec["accuracy"], "surviving": rob["surviving"],
         "excluded": rob["excluded"], "retries": rob["round_retries"]}
    )
if not saw_retry:
    fail.append("device-loss round never exercised the retry path")
import jax

for leaf in jax.tree_util.tree_leaves(chaos["params"]):
    if not np.all(np.isfinite(np.asarray(leaf))):
        fail.append("final aggregated params contain non-finite values")
        break

acc_clean = clean["history"][-1]["accuracy"]
acc_chaos = chaos["history"][-1]["accuracy"]
if abs(acc_clean - acc_chaos) > ACC_TOL:
    fail.append(
        f"final accuracy diverged: clean {acc_clean:.4f} vs chaos "
        f"{acc_chaos:.4f} (tol {ACC_TOL})"
    )

# (f) packed twin gates: same exclusions as the schedule, finite params,
# accuracy within tolerance of the UNPACKED faulted run, and the packing
# record present in the result.
acc_packed = packed["history"][-1]["accuracy"]
if abs(acc_packed - acc_chaos) > ACC_TOL:
    fail.append(
        f"packed faulted run diverged from unpacked: {acc_packed:.4f} vs "
        f"{acc_chaos:.4f} (tol {ACC_TOL})"
    )
if not isinstance(packed.get("packing"), dict) or packed["packing"]["interleave"] != 2:
    fail.append("packed run result carries no packing record")
for r, rec in enumerate(packed["history"]):
    rob = rec.get("robust")
    if rob is None:
        fail.append(f"packed round {r}: no robustness metadata")
        continue
    sched = schedule_for_round(cfg.faults, r, cfg.num_clients)
    expect = set(np.flatnonzero(sched.dropped).tolist()) | set(
        np.flatnonzero(sched.poison).tolist()
    )
    got = {i for i, p in enumerate(rob["participation"]) if not p}
    if got != expect:
        fail.append(
            f"packed round {r}: excluded {sorted(got)} but schedule says "
            f"{sorted(expect)}"
        )
for leaf in jax.tree_util.tree_leaves(packed["params"]):
    if not np.all(np.isfinite(np.asarray(leaf))):
        fail.append("packed run's final params contain non-finite values")
        break

# (e) events.jsonl cross-check: the structured log must tell the SAME
# story as the fault schedule — per-round exclusions, retries, and the
# experiment_end counters, all exactly.
events_summary = {}
try:
    evs = obs_events.read_events(events_path)  # strict parse
except (OSError, ValueError) as e:
    evs = []
    fail.append(f"events.jsonl unusable: {e}")
if evs:
    robust_by_round = {
        e["round"]: e for e in evs if e["event"] == "round_robust"
    }
    retries_by_round = {}
    for e in evs:
        if e["event"] == "round_retry":
            retries_by_round[e["round"]] = retries_by_round.get(e["round"], 0) + 1
    sched_drop = sched_nan = 0
    for r in range(cfg.rounds):
        sched = schedule_for_round(cfg.faults, r, cfg.num_clients)
        n_drop = int(np.count_nonzero(sched.dropped))
        n_nan = int(np.count_nonzero(sched.poison))
        sched_drop += n_drop
        sched_nan += n_nan
        rob = robust_by_round.get(r)
        if rob is None:
            fail.append(f"events.jsonl: no round_robust event for round {r}")
            continue
        if rob["excluded"].get("scheduled", 0) != n_drop:
            fail.append(
                f"events.jsonl round {r}: scheduled exclusions "
                f"{rob['excluded'].get('scheduled')} != schedule {n_drop}"
            )
        if rob["excluded"].get("nonfinite", 0) != n_nan:
            fail.append(
                f"events.jsonl round {r}: nonfinite exclusions "
                f"{rob['excluded'].get('nonfinite')} != schedule {n_nan}"
            )
        expect_excl = set(np.flatnonzero(sched.dropped).tolist()) | set(
            np.flatnonzero(sched.poison).tolist()
        )
        got_excl = {
            i for i, p in enumerate(rob["participation"]) if not p
        }
        if got_excl != expect_excl:
            fail.append(
                f"events.jsonl round {r}: excluded {sorted(got_excl)} != "
                f"schedule {sorted(expect_excl)}"
            )
    for r in cfg.faults.fail_rounds:
        if retries_by_round.get(r, 0) < 1:
            fail.append(
                f"events.jsonl: device-loss round {r} logged no round_retry"
            )
    end = [e for e in evs if e["event"] == "experiment_end"]
    counters = (end[-1].get("metrics") or {}) if end else {}
    if counters.get("exclusions.scheduled", 0) != sched_drop:
        fail.append(
            f"events.jsonl counters: exclusions.scheduled "
            f"{counters.get('exclusions.scheduled')} != schedule {sched_drop}"
        )
    if counters.get("exclusions.nonfinite", 0) != sched_nan:
        fail.append(
            f"events.jsonl counters: exclusions.nonfinite "
            f"{counters.get('exclusions.nonfinite')} != schedule {sched_nan}"
        )
    if counters.get("round.retries", 0) != sum(retries_by_round.values()):
        fail.append(
            "events.jsonl counters: round.retries "
            f"{counters.get('round.retries')} != logged retry events "
            f"{sum(retries_by_round.values())}"
        )
    events_summary = {
        "events": len(evs),
        "retries": sum(retries_by_round.values()),
        "exclusions_scheduled": sched_drop,
        "exclusions_nonfinite": sched_nan,
        "counters": counters,
    }

# (g) streaming twin gates: every round commits at quorum; the per-round
# stream_round events' arrival/dedup/retry counters match the
# deterministic schedule EXACTLY; cross-round staleness bookkeeping is
# conserved; experiment_end stream.* counters equal the per-round sums;
# accuracy within tolerance of the synchronous faulted twin.
stream_summary = {}
try:
    sevs = obs_events.read_events(os.environ["CHAOS_STREAM_EVENTS_PATH"])
except (OSError, ValueError) as e:
    sevs = []
    fail.append(f"stream events.jsonl unusable: {e}")
if sevs:
    stream_by_round = {
        e["round"]: e for e in sevs if e["event"] == "stream_round"
    }
    exp_arrivals = exp_dups = exp_retries = exp_rejected = 0
    for r in range(stream_cfg.rounds):
        ev = stream_by_round.get(r)
        if ev is None:
            fail.append(f"stream events: no stream_round event for round {r}")
            continue
        sched = schedule_for_round(stream_faults, r, cfg.num_clients)
        arr = schedule_arrivals(stream_faults, r, cfg.num_clients)
        alive = int(np.count_nonzero(~sched.dropped))
        n_dup = int(arr.duplicate.sum())
        n_tran = int(arr.transient.sum())
        n_rej = int(np.count_nonzero(sched.poison))
        # every alive client delivers once (transients via their single
        # retry) and each duplicated delivery adds one more arrival
        want = {
            "arrivals": alive + n_dup,
            "duplicates": n_dup,
            "retries": n_tran,
            "rejected": n_rej,
        }
        for k, v in want.items():
            if ev.get(k) != v:
                fail.append(
                    f"stream round {r}: {k} {ev.get(k)} != schedule {v}"
                )
        if not ev.get("committed"):
            fail.append(f"stream round {r}: did not commit at quorum")
        if ev.get("fresh", 0) < ev.get("quorum", 99):
            fail.append(
                f"stream round {r}: committed with fresh {ev.get('fresh')} "
                f"below quorum {ev.get('quorum')}"
            )
        exp_arrivals += want["arrivals"]
        exp_dups += n_dup
        exp_retries += n_tran
        exp_rejected += n_rej
    # cross-round staleness conservation: what round r carried either
    # folds or is excluded as stale in round r+1 (tau=1 forbids a second
    # carry)
    for r in range(stream_cfg.rounds - 1):
        a, b = stream_by_round.get(r), stream_by_round.get(r + 1)
        if a is None or b is None:
            continue
        if a["carried"] != b["stale_folded"] + b["stale_excluded"]:
            fail.append(
                f"stream rounds {r}->{r + 1}: carried {a['carried']} != "
                f"stale_folded {b['stale_folded']} + stale_excluded "
                f"{b['stale_excluded']}"
            )
    send = [e for e in sevs if e["event"] == "experiment_end"]
    scounters = (send[-1].get("metrics") or {}) if send else {}
    for name, want_total in (
        ("stream.arrivals", exp_arrivals),
        ("stream.duplicates", exp_dups),
        ("stream.retries", exp_retries),
        ("stream.rejected", exp_rejected),
    ):
        if scounters.get(name, 0) != want_total:
            fail.append(
                f"stream counters: {name} {scounters.get(name)} != "
                f"schedule {want_total}"
            )
    # surviving (round_robust) must equal fresh + stale folds (stream_round)
    srobust = {e["round"]: e for e in sevs if e["event"] == "round_robust"}
    for r, ev in stream_by_round.items():
        rr = srobust.get(r)
        if rr is None:
            fail.append(f"stream events: no round_robust for round {r}")
        elif rr["surviving"] != ev["fresh"] + ev["stale_folded"]:
            fail.append(
                f"stream round {r}: surviving {rr['surviving']} != fresh "
                f"{ev['fresh']} + stale {ev['stale_folded']}"
            )
    acc_stream = streamed["history"][-1]["accuracy"]
    if abs(acc_stream - acc_chaos) > ACC_TOL:
        fail.append(
            f"streaming twin diverged from synchronous: {acc_stream:.4f} "
            f"vs {acc_chaos:.4f} (tol {ACC_TOL})"
        )
    stream_summary = {
        "events": len(sevs),
        "arrivals": exp_arrivals,
        "duplicates": exp_dups,
        "retries": exp_retries,
        "rejected": exp_rejected,
        "counters": {
            k: v for k, v in scounters.items() if k.startswith("stream.")
        },
        "rounds": [
            {k: stream_by_round[r][k]
             for k in ("round", "committed", "quorum", "fresh",
                       "stale_folded", "carried", "duplicates", "retries")}
            for r in sorted(stream_by_round)
        ],
    }
import jax as _jax_s

for leaf in _jax_s.tree_util.tree_leaves(streamed["params"]):
    if not np.all(np.isfinite(np.asarray(leaf))):
        fail.append("streaming twin's final params contain non-finite values")
        break

# (i) hybrid-HE uplink twin (ISSUE 11): the identical streaming fault
# schedule under upload_kind=hhe — symmetric uploads, server-side
# transciphering into CKKS, everything downstream unchanged. The arrival
# machinery is cipher-agnostic, so the stream.* counters must equal the
# SAME schedule totals the direct streaming twin was gated on.
from hefl_tpu.fl import HheConfig

hhe_events = os.path.join(os.path.dirname(events_path), "hhe_events.jsonl")
hhe_cfg = dataclasses.replace(
    stream_cfg,
    events_path=hhe_events,
    packing=PackingConfig(bits=8, interleave=2, clip=0.5),
    stream=dataclasses.replace(stream_cfg.stream, upload_kind="hhe"),
    hhe=HheConfig(key_seed=0),
)
print("chaos smoke: hybrid-HE streaming twin (upload_kind=hhe, b=8 k=2) ...",
      flush=True)
hhe_run = run_experiment(hhe_cfg, verbose=False)

hhe_summary = {}
hrec = hhe_run.get("hhe")
if not isinstance(hrec, dict) or hrec.get("expansion_hhe") is None:
    fail.append("hhe twin: result carries no hhe wire record")
elif hrec["expansion_hhe"] > 1.1:
    fail.append(
        f"hhe twin: wire expansion {hrec['expansion_hhe']} > the 1.1x gate"
    )
acc_hhe = hhe_run["history"][-1]["accuracy"]
if abs(acc_hhe - acc_chaos) > ACC_TOL:
    fail.append(
        f"hhe twin diverged from synchronous faulted run: {acc_hhe:.4f} "
        f"vs {acc_chaos:.4f} (tol {ACC_TOL})"
    )
for leaf in _jax_s.tree_util.tree_leaves(hhe_run["params"]):
    if not np.all(np.isfinite(np.asarray(leaf))):
        fail.append("hhe twin's final params contain non-finite values")
        break
try:
    hevs = obs_events.read_events(hhe_events)
except (OSError, ValueError) as e:
    hevs = []
    fail.append(f"hhe events.jsonl unusable: {e}")
if hevs:
    hhe_by_round = {
        e["round"]: e for e in hevs if e["event"] == "stream_round"
    }
    for r in range(hhe_cfg.rounds):
        ev = hhe_by_round.get(r)
        if ev is None:
            fail.append(f"hhe twin: no stream_round event for round {r}")
        elif not ev.get("committed"):
            fail.append(f"hhe twin round {r}: did not commit at quorum")
    hend = [e for e in hevs if e["event"] == "experiment_end"]
    hcounters = (hend[-1].get("metrics") or {}) if hend else {}
    # The schedule totals, recomputed here (not borrowed from the direct
    # twin's event check, which may have failed independently).
    h_arr = h_dup = h_ret = h_rej = 0
    for r in range(hhe_cfg.rounds):
        sched = schedule_for_round(stream_faults, r, cfg.num_clients)
        arr = schedule_arrivals(stream_faults, r, cfg.num_clients)
        n_dup = int(arr.duplicate.sum())
        h_arr += int(np.count_nonzero(~sched.dropped)) + n_dup
        h_dup += n_dup
        h_ret += int(arr.transient.sum())
        h_rej += int(np.count_nonzero(sched.poison))
    for name, want_total in (
        ("stream.arrivals", h_arr),
        ("stream.duplicates", h_dup),
        ("stream.retries", h_ret),
        ("stream.rejected", h_rej),
    ):
        if hcounters.get(name, 0) != want_total:
            fail.append(
                f"hhe twin counters: {name} {hcounters.get(name)} != the "
                f"direct streaming twin's schedule total {want_total}"
            )
    transciphered = hcounters.get("hhe.uploads_transciphered", 0)
    if transciphered <= 0:
        fail.append("hhe twin: hhe.uploads_transciphered counter is 0")
    hhe_summary = {
        "events": len(hevs),
        "wire": hrec,
        "uploads_transciphered": transciphered,
        "acc_hhe": acc_hhe,
        "rounds_committed": sorted(
            r for r, e in hhe_by_round.items() if e.get("committed")
        ),
    }

# (j) cohort-only streaming twin (ISSUE 15): the SAME streaming fault
# schedule with a sampled cohort (6 of 8; quorum scales to the cohort),
# run cohort-only (the default: just the cohort's slots gathered and
# trained) AND with the full-C producer (--full-cohort-train semantics).
# Gates: every round commits in both, the per-round unsampled exclusions
# equal C - cohort, and the two runs' final params are BITWISE equal —
# the committed-aggregate equality of the cohort gather, at experiment
# level, under the full chaos schedule.
from hefl_tpu.fl import StreamConfig as _SC15

cohort_stream = _SC15(
    cohort_size=6, quorum=0.3, deadline_s=2.0, max_retries=1,
    staleness_rounds=1, seed=0, cohort_only=True,
)
cohort_cfg = dataclasses.replace(
    stream_cfg, events_path="", stream=cohort_stream,
)
fullc_cfg = dataclasses.replace(
    cohort_cfg,
    stream=dataclasses.replace(cohort_stream, cohort_only=False),
)
print("chaos smoke: cohort-only streaming twin (cohort 6/8) ...", flush=True)
cohort_run = run_experiment(cohort_cfg, verbose=False)
print("chaos smoke: full-C-trained cohort twin ...", flush=True)
fullc_run = run_experiment(fullc_cfg, verbose=False)

cohort_summary = {}
cohort_bitwise = True
for a, b in zip(
    _jax_s.tree_util.tree_leaves(cohort_run["params"]),
    _jax_s.tree_util.tree_leaves(fullc_run["params"]),
):
    if not np.array_equal(np.asarray(a), np.asarray(b)):
        cohort_bitwise = False
        fail.append(
            "cohort-only twin's final params differ bitwise from the "
            "full-C-trained twin at the same sampled cohorts"
        )
        break
for r, (rec_c, rec_f) in enumerate(
    zip(cohort_run["history"], fullc_run["history"])
):
    for name, rec_ in (("cohort-only", rec_c), ("full-C", rec_f)):
        st = rec_.get("stream") or {}
        if not st.get("committed"):
            fail.append(f"cohort twin ({name}) round {r}: did not commit")
    rob = rec_c.get("robust") or {}
    unsampled = (rob.get("excluded") or {}).get("unsampled")
    # Exactly C - cohort in round 0; later rounds may be lower because a
    # STALE fold from a client outside the current cohort legitimately
    # clears its unsampled attribution (it participated via its carry).
    want_unsampled = cfg.num_clients - 6
    bad = (
        unsampled != want_unsampled if r == 0 else
        unsampled is None or unsampled > want_unsampled
    )
    if bad:
        fail.append(
            f"cohort twin round {r}: unsampled exclusions {unsampled} "
            f"inconsistent with C - cohort = {want_unsampled}"
        )
    if rec_c.get("stream") != rec_f.get("stream"):
        fail.append(
            f"cohort twin round {r}: stream record diverged between the "
            "cohort-only and full-C producers"
        )
for leaf in _jax_s.tree_util.tree_leaves(cohort_run["params"]):
    if not np.all(np.isfinite(np.asarray(leaf))):
        fail.append("cohort-only twin's final params contain non-finite values")
        break
cohort_summary = {
    "cohort_size": 6,
    "num_clients": cfg.num_clients,
    "bitwise_equal_to_full_c": cohort_bitwise,
    "acc_cohort_by_round": [h["accuracy"] for h in cohort_run["history"]],
    "rounds_committed": [
        r for r, h in enumerate(cohort_run["history"])
        if (h.get("stream") or {}).get("committed")
    ],
}

# (h) crash-recovery twin (ISSUE 9): the streaming schedule under the
# write-ahead journal, killed mid-journal-append in round 1 (leaving a
# REAL torn record), then recovered by simply re-running the config. No
# checkpoint on purpose: the journal alone must carry the recovery (and
# without checkpoint compaction every round's commit record survives for
# the hash-chain comparison below).
from hefl_tpu.fl import CrashConfig, SimulatedCrash
from hefl_tpu.fl import journal as jr

CRASH_ROUND, CRASH_FOLDS = 1, 2
recovery_faults = dataclasses.replace(stream_faults, fail_rounds=())
crash_cfg = dataclasses.replace(
    stream_cfg, faults=recovery_faults, events_path="",
    max_round_retries=0, checkpoint_path=None,
    journal_path=os.path.join(os.path.dirname(events_path), "crash.wal"),
    crash=CrashConfig(round=CRASH_ROUND, at="mid_append",
                      after_folds=CRASH_FOLDS),
)
twin_wal = os.path.join(os.path.dirname(events_path), "twin.wal")
twin_cfg = dataclasses.replace(crash_cfg, crash=None, journal_path=twin_wal)
print("chaos smoke: journaled uninterrupted twin ...", flush=True)
jtwin = run_experiment(twin_cfg, verbose=False)
print(f"chaos smoke: crash-recovery twin (mid-append kill, round "
      f"{CRASH_ROUND}) ...", flush=True)
try:
    run_experiment(crash_cfg, verbose=False)
    fail.append("crash injection never fired (SimulatedCrash not raised)")
    recovered = None
except SimulatedCrash:
    print("chaos smoke: server crashed as injected; recovering ...",
          flush=True)
    recovered = run_experiment(
        dataclasses.replace(crash_cfg, crash=None), verbose=False
    )

recovery_summary = {}
if recovered is not None:
    rj = recovered.get("journal") or {}
    rec = rj.get("recovered") or {}
    rmetrics = recovered["obs"]["metrics"]
    twin_records = jr.read_journal(twin_wal)
    crash_records = jr.read_journal(crash_cfg.journal_path)
    twin_commits = {
        e["round"]: e["sum_sha"] for e in twin_records
        if e["kind"] == "commit"
    }
    got_commits = {
        e["round"]: e["sum_sha"] for e in crash_records
        if e["kind"] == "commit"
    }
    if got_commits != twin_commits:
        fail.append(
            f"recovered journal commit hashes {got_commits} != "
            f"uninterrupted twin {twin_commits}"
        )
    # recovery.* counters == the injected schedule, exactly: the torn
    # record is truncated once; the re-folded uploads are every fold the
    # journal held at the kill — all of sealed round 0's plus the
    # (after_folds - 1) that completed before the torn append.
    r0_folds = sum(
        1 for e in twin_records
        if e["kind"] == "fold" and e["round"] < CRASH_ROUND
    )
    want_refolded = r0_folds + CRASH_FOLDS - 1
    checks = {
        "journal.torn_tail_truncated": 1,
        "recovery.refolded_uploads": want_refolded,
        "recovery.resumed_rounds": 1,
        "recovery.count": 1,
    }
    for name, want in checks.items():
        if rmetrics.get(name, 0) != want:
            fail.append(
                f"recovery counters: {name} {rmetrics.get(name)} != "
                f"injected schedule {want}"
            )
    if rec.get("open_round") != CRASH_ROUND:
        fail.append(
            f"recovery report: open_round {rec.get('open_round')} != "
            f"crash round {CRASH_ROUND}"
        )
    # bitwise equality of the recovered model vs the uninterrupted twin
    for a, b in zip(
        _jax_s.tree_util.tree_leaves(jtwin["params"]),
        _jax_s.tree_util.tree_leaves(recovered["params"]),
    ):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            fail.append(
                "recovered params differ bitwise from the uninterrupted "
                "journaled twin"
            )
            break
    acc_jtwin = jtwin["history"][-1]["accuracy"]
    acc_rec = recovered["history"][-1]["accuracy"]
    if acc_rec != acc_jtwin:
        fail.append(
            f"recovered accuracy {acc_rec} != uninterrupted twin "
            f"{acc_jtwin} (must be exact: replay is bitwise)"
        )
    recovery_summary = {
        "crash_round": CRASH_ROUND,
        "crash_at": "mid_append",
        "commit_sha_by_round": got_commits,
        "refolded_uploads": rmetrics.get("recovery.refolded_uploads"),
        "torn_tail_truncated": rmetrics.get("journal.torn_tail_truncated"),
        "acc_recovered": acc_rec,
        "acc_uninterrupted": acc_jtwin,
        "recovered_report": rec,
    }

# (k) hierarchical aggregation twin (ISSUE 16): flat (num_hosts=0) vs
# two-tier (num_hosts=4) engines at the SAME 8-client streaming
# schedule, under a duplicate storm (3 duplicated deliveries) and under
# a regional outage (1 of 4 hosts dark for the round — the
# --outage-hosts schedule; the flat twin sees the identical schedule,
# only its aggregation topology differs). Gates: every round's stream
# record identical between the twins and the final params BITWISE
# equal — the fold tree commits exactly the flat aggregate under chaos.
hier_checks = {}
hier_storm_faults = dataclasses.replace(
    recovery_faults, duplicate_clients=3, arrival_delay_s=0.5,
)
# The outage leg swaps the generic dropout/poison draws for the
# regional schedule (stragglers/retries stay): stacking a 2-client
# outage on top of the 25% dropout would push rounds below the 3/8
# quorum — a correct degrade, but this leg gates COMMITTED equality.
hier_outage_faults = dataclasses.replace(
    recovery_faults, drop_fraction=0.0, nan_clients=0,
    duplicate_clients=0, outage_hosts=1, num_hosts=4,
)
for hname, hfaults in (("duplicate-storm", hier_storm_faults),
                       ("regional-outage", hier_outage_faults)):
    hflat_cfg = dataclasses.replace(
        stream_cfg, faults=hfaults, events_path="",
    )
    hhier_cfg = dataclasses.replace(
        hflat_cfg,
        stream=dataclasses.replace(hflat_cfg.stream, num_hosts=4),
    )
    print(f"chaos smoke: hierarchical twin ({hname}, 4 hosts) ...",
          flush=True)
    hflat_run = run_experiment(hflat_cfg, verbose=False)
    hhier_run = run_experiment(hhier_cfg, verbose=False)
    hier_equal = True
    for a, b in zip(
        _jax_s.tree_util.tree_leaves(hflat_run["params"]),
        _jax_s.tree_util.tree_leaves(hhier_run["params"]),
    ):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            hier_equal = False
            fail.append(
                f"hierarchical twin ({hname}): final params differ "
                "bitwise from the flat-aggregation twin"
            )
            break
    for r, (rec_fl, rec_hi) in enumerate(
        zip(hflat_run["history"], hhier_run["history"])
    ):
        for tname, rec_ in (("flat", rec_fl), ("hierarchical", rec_hi)):
            if not (rec_.get("stream") or {}).get("committed"):
                fail.append(
                    f"hierarchical twin ({hname}, {tname}) round {r}: "
                    "did not commit"
                )
        # the hierarchical record carries an extra `hosts` sub-record
        # (tier landings/counters, ISSUE 17) the flat topology has no
        # analogue for; everything else must match exactly
        st_fl = dict(rec_fl.get("stream") or {})
        st_hi = dict(rec_hi.get("stream") or {})
        st_hi.pop("hosts", None)
        if st_fl != st_hi:
            fail.append(
                f"hierarchical twin ({hname}) round {r}: stream record "
                "diverged between the flat and hierarchical topologies"
            )
    hier_checks[hname] = {
        "num_hosts": 4,
        "bitwise_equal_to_flat": hier_equal,
        "acc_hier_by_round": [h["accuracy"] for h in hhier_run["history"]],
        "rounds_committed": [
            r for r, h in enumerate(hhier_run["history"])
            if (h.get("stream") or {}).get("committed")
        ],
    }

# (l) lossy-DCN leg (ISSUE 17): the same streaming schedule with the
# tier->root uplinks faulted — one transient ship loss (recovered by
# the ship retry), one duplicated delivery (root dedup), and per-uplink
# delivery delay — vs the flat twin at the IDENTICAL client schedule
# (link faults draw on an independent PRNG stream and the flat engine
# has no uplinks). Gates: every committed round's stream record and the
# final params BITWISE equal, and the retry/dedup counters equal the
# injected link schedule EXACTLY (no exclusions: nothing is dark and
# there is no ship deadline).
from hefl_tpu.fl import schedule_links

lossy_faults = dataclasses.replace(
    recovery_faults, num_hosts=4, link_loss_hosts=1, link_dup_hosts=1,
    link_delay_s=0.5,
)
lossy_flat_cfg = dataclasses.replace(
    stream_cfg, faults=lossy_faults, events_path="",
)
lossy_hier_cfg = dataclasses.replace(
    lossy_flat_cfg,
    stream=dataclasses.replace(
        lossy_flat_cfg.stream, num_hosts=4, host_quorum=0.5,
        host_staleness_rounds=1,
    ),
)
print("chaos smoke: lossy-DCN twin (loss 1 + dup 1 + delay 0.5s) ...",
      flush=True)
lossy_flat_run = run_experiment(lossy_flat_cfg, verbose=False)
lossy_hier_run = run_experiment(lossy_hier_cfg, verbose=False)
lossy_equal = True
for a, b in zip(
    _jax_s.tree_util.tree_leaves(lossy_flat_run["params"]),
    _jax_s.tree_util.tree_leaves(lossy_hier_run["params"]),
):
    if not np.array_equal(np.asarray(a), np.asarray(b)):
        lossy_equal = False
        fail.append(
            "lossy-DCN twin: final params differ bitwise from the flat "
            "twin — a retried/duplicated ship changed the committed sum"
        )
        break
lossy_counters = []
for r, (rec_fl, rec_hi) in enumerate(
    zip(lossy_flat_run["history"], lossy_hier_run["history"])
):
    st_fl = dict(rec_fl.get("stream") or {})
    st_hi = dict(rec_hi.get("stream") or {})
    hosts = st_hi.pop("hosts", None) or {}
    if not st_hi.get("committed"):
        fail.append(f"lossy-DCN twin round {r}: did not commit")
        continue
    if st_fl != st_hi:
        fail.append(
            f"lossy-DCN twin round {r}: stream record diverged from the "
            "flat twin under link faults"
        )
    # counters == the injected link schedule, exactly: every nonempty
    # tier ships; transient uplinks lose + retry ONCE, duplicate uplinks
    # deliver twice and dedup ONCE, nothing is missed or excluded
    lf = schedule_links(lossy_faults, r)
    landed = set(hosts.get("landed") or ())
    want_lost = sum(1 for h in landed if lf.transient[h])
    want_dup = sum(1 for h in landed if lf.duplicate[h])
    got = {
        "round": r,
        "ship_lost": hosts.get("ship_lost"),
        "ship_retries": hosts.get("ship_retries"),
        "ship_deduped": hosts.get("ship_deduped"),
        "missed": hosts.get("missed"),
    }
    lossy_counters.append(got)
    if len(landed) != hosts.get("nonempty") or hosts.get("missed"):
        fail.append(
            f"lossy-DCN twin round {r}: a tier missed the round — "
            f"{hosts.get('missed')} (nothing is dark and there is no "
            "ship deadline; retries must recover every loss)"
        )
    if (got["ship_lost"] != want_lost or got["ship_retries"] != want_lost
            or got["ship_deduped"] != want_dup):
        fail.append(
            f"lossy-DCN twin round {r}: retry/dedup counters {got} != "
            f"link schedule (lost/retried {want_lost}, deduped {want_dup})"
        )
    rob = rec_hi.get("robust") or {}
    exc = rob.get("excluded") or {}
    for cause in ("host_timeout", "host_unreachable", "host_stale"):
        if exc.get(cause, 0):
            fail.append(
                f"lossy-DCN twin round {r}: unexpected {cause} "
                f"exclusions {exc.get(cause)} (schedule injects none)"
            )
lossy_summary = {
    "num_hosts": 4,
    "link_loss_hosts": 1,
    "link_dup_hosts": 1,
    "link_delay_s": 0.5,
    "bitwise_equal_to_flat": lossy_equal,
    "counters_by_round": lossy_counters,
    "rounds_committed": [
        r for r, h in enumerate(lossy_hier_run["history"])
        if (h.get("stream") or {}).get("committed")
    ],
}

artifact = {
    "preset": "chaos-smoke",
    "acc_clean_by_round": [h["accuracy"] for h in clean["history"]],
    "acc_chaos_by_round": [h["accuracy"] for h in chaos["history"]],
    "acc_packed_by_round": [h["accuracy"] for h in packed["history"]],
    "acc_stream_by_round": [h["accuracy"] for h in streamed["history"]],
    "acc_hhe_by_round": [h["accuracy"] for h in hhe_run["history"]],
    "packing": packed.get("packing"),
    "stream": streamed.get("stream"),
    "hhe": hrec,
    "rounds": rounds,
    "acc_tolerance": ACC_TOL,
    # The structured-event cross-check (events.jsonl vs fault schedule).
    "events_check": events_summary,
    # The streaming twin's cross-check (stream events vs arrival schedule).
    "stream_check": stream_summary,
    # The crash-recovery twin's cross-check (recovered journal vs the
    # uninterrupted journaled twin + recovery.* counters vs the schedule).
    "recovery_check": recovery_summary,
    # The hybrid-HE twin's cross-check (stream counters vs the schedule
    # + the wire-expansion record).
    "hhe_check": hhe_summary,
    # The cohort-only twin's cross-check (bitwise equality vs the full-C
    # producer + unsampled attribution, ISSUE 15).
    "cohort_check": cohort_summary,
    # The hierarchical-aggregation twins' cross-check (flat vs two-tier
    # bitwise equality under duplicate-storm and regional-outage
    # schedules, ISSUE 16).
    "hier_check": hier_checks,
    # The lossy-DCN twin's cross-check (ship loss + duplication + delay
    # vs flat bitwise equality + retry/dedup counters == link schedule,
    # ISSUE 17).
    "lossy_dcn_check": lossy_summary,
    "passed": not fail,
    "failures": fail,
}
with open("CHAOS_SMOKE.json", "w") as f:
    json.dump(artifact, f, indent=1)

if fail:
    print("CHAOS SMOKE FAILED:")
    for f_ in fail:
        print(" -", f_)
    sys.exit(1)
print(
    f"chaos smoke OK: clean {acc_clean:.4f} vs chaos {acc_chaos:.4f} vs "
    f"packed {acc_packed:.4f} vs streamed "
    f"{streamed['history'][-1]['accuracy']:.4f}, exclusions match the "
    "schedule exactly (packed + streaming twins included), no unflagged "
    "NaNs, device-loss retry exercised, events.jsonl counters match the "
    "fault schedule, streaming rounds all committed at quorum, the "
    "mid-append-killed server recovered to the bitwise state of its "
    "uninterrupted twin (commit sha chain + params identical, recovery "
    "counters == injected schedule), the hybrid-HE twin committed "
    f"every round at {hrec.get('expansion_hhe') if isinstance(hrec, dict) else '?'}x "
    "wire expansion with counters matching the same schedule, and the "
    "cohort-only twin (6/8) committed every round bitwise-equal to its "
    "full-C-trained twin, and the hierarchical twins (4 hosts) committed "
    "bitwise-equal to flat aggregation under both the duplicate-storm "
    "and regional-outage schedules, and the lossy-DCN twin (ship loss + "
    "duplication + delay) committed bitwise-equal to flat with retry/"
    "dedup counters matching the link schedule exactly"
)
PY
