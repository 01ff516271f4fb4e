"""Round-lifecycle span tracing tests (ISSUE 20):

  * flat-engine conservation: per-round span counts equal the
    stream.*/journal.* counter deltas EXACTLY (the COUNTER_OF contract),
    across faulty rounds with stragglers/dups/transients and stale carry
  * hierarchical + lossy-DCN conservation: tier_ship/ship_retry spans
    equal the dcn.* counter deltas under link loss
  * journaled rounds carry journal_append/group_commit_flush/fsync
    spans matching the journal.* counters
  * replay-equals-twin: a crashed+recovered round's span tree signature
    is identical to the uninterrupted twin's (modulo recovery_replay and
    wall-clock IO spans), and the replay records a recovery_replay span
  * HHE rounds record a transcipher span and stay conserved
  * Chrome-trace export round-trips through obs.trace.load_trace_events;
    span events on the JSONL log rebuild the same tree
  * the trend gate (obs.trend): clean history passes, the seeded
    regression fixture fails it, an empty history exits 2
  * the host-span recorder (ISSUE 24): nesting and `parent`, `call` and
    `round` ids, the bound, nothing written to events.jsonl, PhaseTimer
    and SpanTracer.measure as its users, `--span-trace` holding a
    synchronous run's spans
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hefl_tpu.ckks.keys import CkksContext, keygen
from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
from hefl_tpu.fl import (
    AggregationServer,
    CrashConfig,
    FaultConfig,
    HheConfig,
    PackingConfig,
    SimulatedCrash,
    StreamConfig,
    StreamEngine,
    TrainConfig,
)
from hefl_tpu.ckks.packing import PackedSpec
from hefl_tpu.models import SmallCNN
from hefl_tpu.obs import events as obs_events
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import spans as obs_spans
from hefl_tpu.obs import trace as obs_trace
from hefl_tpu.obs import trend as obs_trend
from hefl_tpu.parallel import make_mesh

CFG = TrainConfig(
    epochs=1, batch_size=4, num_classes=10, augment=False, val_fraction=0.25
)

FIXTURE = os.path.join(
    os.path.dirname(__file__), "fixtures", "BENCH_r99_seeded_regression.json"
)
BASELINE_FIXTURE = os.path.join(
    os.path.dirname(__file__), "fixtures", "BENCH_r98_seeded_baseline.json"
)


def _setup(num_clients, per_client=8, seed=0):
    n = num_clients * per_client
    (x, y), _, _ = make_dataset("mnist", seed=seed, n_train=n, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(n, num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    return model, params, jnp.asarray(xs), jnp.asarray(ys)


def _assert_conserved(tracer, delta):
    errs = obs_spans.conservation_errors(tracer.counts(), delta)
    assert errs == [], errs


def _hcount(delta, name):
    """A histogram's observation count out of a snapshot_delta."""
    v = delta.get(name)
    return int(v.get("count", 0)) if isinstance(v, dict) else 0


# ------------------------------------------------- flat conservation


def test_flat_span_conservation_across_faulty_rounds():
    # Two faulty rounds: stragglers past the deadline (carried stale into
    # round 1), a duplicate, and a transient retry. Every round's span
    # tree must balance the counters exactly — including fold ==
    # stream.folds == fresh + stale_folded on the degraded->carry round.
    num_clients = 8
    model, params, xs, ys = _setup(num_clients)
    mesh = make_mesh(num_clients)
    ctx = CkksContext.create(n=256)
    _, pk = keygen(ctx, jax.random.key(1))
    eng = StreamEngine(
        StreamConfig(quorum=0.75, staleness_rounds=1, seed=3,
                     deadline_s=20.0),
        FaultConfig(seed=5, straggler_fraction=0.3, straggler_delay_s=30.0,
                    duplicate_clients=1, transient_fail_clients=1),
    )
    for r in range(2):
        base = obs_metrics.snapshot()
        _, _, _, sm = eng.run_round(
            model, CFG, mesh, ctx, pk, params, xs, ys,
            jax.random.key(100 + r), r,
        )
        delta = obs_metrics.snapshot_delta(base)
        tracer = eng.last_spans
        assert tracer is not None and tracer.root.kind == "round"
        _assert_conserved(tracer, delta)
        counts = tracer.counts()
        # the contract's load-bearing identity, also checked vs the meta
        assert counts.get("fold", 0) == sm.fresh + sm.stale_folded
        assert counts.get("commit", 0) == 1
        # the round root is sealed and spans every child
        kids = [s for s in tracer.root.walk() if s is not tracer.root]
        assert kids and all(
            s.clock == "wall" or s.t1 <= tracer.root.t1 + 1e-9 for s in kids
        )
    # the second round folded carried stale uploads
    assert eng.last_spans.counts().get("fold", 0) > 0


def test_flat_commit_latency_histogram_moves_with_commit_span():
    model, params, xs, ys = _setup(4)
    mesh = make_mesh(4)
    ctx = CkksContext.create(n=256)
    _, pk = keygen(ctx, jax.random.key(1))
    eng = StreamEngine(StreamConfig(quorum=1.0, deadline_s=5.0), None)
    base = obs_metrics.snapshot()
    _, _, _, sm = eng.run_round(
        model, CFG, mesh, ctx, pk, params, xs, ys, jax.random.key(7), 0
    )
    d = obs_metrics.snapshot_delta(base)
    assert sm.committed
    assert _hcount(d, "stream.commit_latency_s") == 1
    # one arrival_to_fold observation per fold
    assert _hcount(d, "stream.arrival_to_fold_s") == d.get("stream.folds", 0)
    [commit] = [
        s for s in eng.last_spans.spans() if s.kind == "commit"
    ]
    assert commit.args["committed"] is True


# ------------------------------------- hierarchical + lossy DCN uplinks


def test_hierarchical_span_conservation_under_link_loss():
    num_clients = 8
    model, params, xs, ys = _setup(num_clients)
    mesh = make_mesh(num_clients)
    ctx = CkksContext.create(n=256)
    _, pk = keygen(ctx, jax.random.key(21))
    eng = StreamEngine(
        StreamConfig(cohort_size=8, quorum=0.5, deadline_s=2.0,
                     num_hosts=4, max_retries=2),
        FaultConfig(seed=3, num_hosts=4, link_loss_hosts=1),
    )
    saw_ship_retry = False
    for r in range(2):
        base = obs_metrics.snapshot()
        _, _, _, sm = eng.run_round(
            model, CFG, mesh, ctx, pk, params, xs, ys,
            jax.random.key(200 + r), r,
        )
        delta = obs_metrics.snapshot_delta(base)
        tracer = eng.last_spans
        _assert_conserved(tracer, delta)
        counts = tracer.counts()
        # every shipped tier shows up as one tier_ship span
        assert counts.get("tier_ship", 0) == delta.get(
            "dcn.ship.landed", 0
        ) + delta.get("dcn.ship.missed", 0)
        assert counts.get("tier_ship", 0) >= 1
        saw_ship_retry |= counts.get("ship_retry", 0) > 0
        # landed ships observed an RTT each
        assert _hcount(delta, "dcn.ship_rtt_s") == delta.get(
            "dcn.ship.landed", 0
        )
    # link_loss_hosts=1 loses a first delivery every round — the retry
    # machinery must have fired at least once across the two rounds
    assert saw_ship_retry


# --------------------------------------- journaled rounds + replay twin


def test_journal_spans_and_replay_tree_matches_twin(tmp_path):
    num_clients = 4
    model, params, xs, ys = _setup(num_clients)
    mesh = make_mesh(num_clients)
    ctx = CkksContext.create(n=256)
    _, pk = keygen(ctx, jax.random.key(21))
    fc = FaultConfig(seed=3, straggler_fraction=0.25, straggler_delay_s=3.0,
                     duplicate_clients=1)
    sc = StreamConfig(quorum=0.75, deadline_s=1.0, staleness_rounds=1)
    args = (model, CFG, mesh, ctx, pk, params, xs, ys, jax.random.key(100), 0)

    # uninterrupted twin (no journal): the reference virtual-clock tree
    twin_eng = StreamEngine(sc, fc)
    twin_eng.run_round(*args)
    twin_sig = obs_spans.tree_signature(twin_eng.last_spans.root)

    # journaled run: journal spans must balance the journal counters
    jp = str(tmp_path / "spans.wal")
    srv = AggregationServer(
        sc, fc, journal_path=jp, fsync_policy=None,
        crash=CrashConfig(round=0, at="post_fold", after_folds=2),
    )
    with pytest.raises(SimulatedCrash):
        srv.run_round(*args)

    base = obs_metrics.snapshot()
    srv2 = AggregationServer(sc, fc, journal_path=jp, fsync_policy=None)
    srv2.run_round(*args)
    delta = obs_metrics.snapshot_delta(base)
    tracer = srv2.engine.last_spans
    _assert_conserved(tracer, delta)
    counts = tracer.counts()
    assert counts.get("journal_append", 0) == delta.get("journal.appends", 0)
    assert counts.get("journal_append", 0) > 0
    assert counts.get("fsync", 0) == delta.get("journal.fsyncs", 0)
    # the recovery pass left its marker...
    assert counts.get("recovery_replay", 0) == 1
    # ...and the replayed round's deterministic tree equals the twin's
    # (recovery_replay + wall-clock IO spans dropped by the defaults)
    assert obs_spans.tree_signature(tracer.root) == twin_sig


def test_journaled_clean_round_has_journal_spans(tmp_path):
    model, params, xs, ys = _setup(4)
    mesh = make_mesh(4)
    ctx = CkksContext.create(n=256)
    _, pk = keygen(ctx, jax.random.key(21))
    srv = AggregationServer(
        StreamConfig(quorum=1.0, deadline_s=5.0), None,
        journal_path=str(tmp_path / "clean.wal"), fsync_policy="commit",
    )
    base = obs_metrics.snapshot()
    srv.run_round(
        model, CFG, mesh, ctx, pk, params, xs, ys, jax.random.key(5), 0
    )
    delta = obs_metrics.snapshot_delta(base)
    tracer = srv.engine.last_spans
    _assert_conserved(tracer, delta)
    counts = tracer.counts()
    assert counts.get("journal_append", 0) > 0
    assert counts.get("group_commit_flush", 0) == delta.get(
        "journal.write_batches", 0
    )
    assert counts.get("fsync", 0) >= 1
    assert _hcount(delta, "journal.flush_latency_s") == counts.get(
        "group_commit_flush", 0
    )


# ----------------------------------------------------------- HHE rounds


def test_hhe_round_records_transcipher_span():
    num_clients = 4
    model, params, xs, ys = _setup(num_clients)
    mesh = make_mesh(num_clients)
    ctx = CkksContext.create(n=256)
    _, pk = keygen(ctx, jax.random.key(7))
    spec = PackedSpec.for_params(
        params, ctx,
        PackingConfig(bits=8, interleave=4, clip=0.5, guard_bits=12),
        num_clients,
    )
    eng = StreamEngine(
        StreamConfig(quorum=1.0, deadline_s=5.0, upload_kind="hhe"), None
    )
    base = obs_metrics.snapshot()
    eng.run_round(
        model, CFG, mesh, ctx, pk, params, xs, ys, jax.random.key(22), 0,
        packing=spec, hhe=HheConfig(),
    )
    delta = obs_metrics.snapshot_delta(base)
    tracer = eng.last_spans
    _assert_conserved(tracer, delta)
    trans = [s for s in tracer.spans() if s.kind == "transcipher"]
    assert len(trans) == 1
    assert trans[0].clock == "wall"
    assert trans[0].args["uploads"] == num_clients


# ------------------------------------------------- export + event log


def test_chrome_trace_export_roundtrips(tmp_path, monkeypatch):
    monkeypatch.setenv("HEFL_EVENTS", "1")   # conftest defaults it off
    model, params, xs, ys = _setup(4)
    mesh = make_mesh(4)
    ctx = CkksContext.create(n=256)
    _, pk = keygen(ctx, jax.random.key(1))
    ev_path = str(tmp_path / "events.jsonl")
    obs_events.configure(ev_path)
    try:
        eng = StreamEngine(StreamConfig(quorum=1.0, deadline_s=5.0), None)
        eng.run_round(
            model, CFG, mesh, ctx, pk, params, xs, ys, jax.random.key(9), 0
        )
    finally:
        obs_events.configure(None)
    tracer = eng.last_spans

    # (a) Chrome trace-viewer JSON, loadable by the repo's own parser
    out = str(tmp_path / "spans.trace.json.gz")
    obs_spans.export_chrome_trace(out, [tracer])
    events = obs_trace.load_trace_events(out)
    assert len(events) == len(tracer.spans())
    names = {e["name"] for e in events}
    assert names <= {f"hefl.span.{k}" for k in obs_spans.SPAN_KINDS}
    assert "hefl.span.round" in names and "hefl.span.commit" in names
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
        assert e["args"]["round"] == 0

    # (b) the span events on the JSONL log rebuild the SAME tree
    trees = obs_spans.trees_from_events(obs_events.read_events(ev_path))
    assert list(trees) == [tracer.trace_id]
    rebuilt = trees[tracer.trace_id]
    assert obs_spans.span_counts(rebuilt) == tracer.counts()
    assert obs_spans.tree_signature(
        rebuilt, ignore=(), include_wall=True
    ) == obs_spans.tree_signature(
        tracer.root, ignore=(), include_wall=True
    )


# ------------------------------------------------- the host-span recorder


def test_recorder_nesting_parent_call_and_round_ids():
    rec = obs_spans.Recorder()
    with rec.span("outside") as outside:
        pass
    with rec.call() as first:
        with rec.span("hefl.round", round=7) as rnd:
            with rec.span("hefl.phase.decrypt") as phase:
                with rec.span("hefl.phase.decrypt.decode") as step:
                    pass
            with rec.span("hefl.phase.evaluate", round=8) as other:
                pass
    with rec.call() as second:
        with rec.span("hefl.setup") as setup:
            pass
    rows = rec.recorded()
    assert [s.name for s in rows] == [   # in the order they ended
        "outside", "hefl.phase.decrypt.decode", "hefl.phase.decrypt",
        "hefl.phase.evaluate", "hefl.round", "hefl.setup"]
    assert len({s.id for s in rows}) == len(rows)
    assert (outside.parent, outside.call, outside.round) == (None, None, None)
    assert (rnd.parent, phase.parent, step.parent) == (None, rnd.id, phase.id)
    assert other.parent == rnd.id
    # `round` is the parent's unless given; `call` counts calls from 0
    assert (rnd.round, phase.round, step.round, other.round) == (7, 7, 7, 8)
    assert (first, second) == (0, 1)
    assert {s.call for s in (rnd, phase, step, other)} == {0}
    assert (setup.call, setup.round) == (1, None)
    for child, parent in ((step, phase), (phase, rnd), (other, rnd)):
        assert parent.t0_ns <= child.t0_ns <= child.t1_ns <= parent.t1_ns
    assert step.seconds == (step.t1_ns - step.t0_ns) * 1e-9


def test_recorder_is_bounded_and_closes_what_an_error_leaves_open():
    rec = obs_spans.Recorder(bound=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    assert [s.name for s in rec.recorded()] == ["s6", "s7", "s8", "s9"]
    # start()/stop() with no `with` and no handle: an error between them
    # leaves the span and its children open; the call closes them on its
    # way out
    rec = obs_spans.Recorder()
    rec.start("hefl.setup")
    rec.start("hefl.setup.model")
    rec.stop("hefl.setup")          # closes what is open inside it too
    rec.stop("hefl.setup")          # nothing of that name is open: no-op
    assert [s.name for s in rec.recorded()] == ["hefl.setup.model", "hefl.setup"]
    assert rec._stack() == []
    rec = obs_spans.Recorder()
    with pytest.raises(KeyError):
        with rec.call():
            rec.start("hefl.round", round=0)
            rec.start("hefl.phase.decrypt")
            raise KeyError("lost")
    rows = rec.recorded()
    assert [s.name for s in rows] == ["hefl.phase.decrypt", "hefl.round"]
    assert all(s.t1_ns is not None and s.t1_ns >= s.t0_ns for s in rows)
    assert rec.call_id is None and rec._stack() == []
    with rec.span("after") as after:   # the stack is clean again
        pass
    assert after.parent is None
    # run_experiment begins and ends its call by hand, so an error can
    # pass without ending it: the next call ends it first
    assert rec.begin_call() == 1
    rec.start("hefl.round", round=3)
    assert rec.call_id == 1 and len(rec._stack()) == 1
    with rec.call() as third:
        assert third == 2 and rec._stack() == []
        assert rec.recorded()[-1].name == "hefl.round"
        assert rec.recorded(call=1) == rec.recorded()[-1:]
    assert rec.call_id is None
    rec.end_call()   # with no call open: harmless
    assert rec.call_id is None and rec.recorded(call=2) == []
    assert obs_spans.MAX_SPANS >= 1 << 14
    assert obs_spans._RECORDER._spans.maxlen == obs_spans.MAX_SPANS


def test_recording_writes_nothing_to_the_event_log(tmp_path, monkeypatch):
    monkeypatch.setenv("HEFL_EVENTS", "1")
    ev_path = str(tmp_path / "events.jsonl")
    obs_events.configure(ev_path)
    try:
        obs_events.emit("marker")
        size = os.path.getsize(ev_path)
        before = len(obs_spans.recorded())
        for _ in range(100):
            with obs_spans.span("hefl.phase.decrypt"):
                with obs_spans.span("hefl.phase.decrypt.decode"):
                    pass
        assert len(obs_spans.recorded()) == min(before + 200,
                                                obs_spans.MAX_SPANS)
        assert os.path.getsize(ev_path) == size
    finally:
        obs_events.configure(None)


def test_phase_timer_summary_is_its_spans_durations():
    from hefl_tpu.utils import PhaseTimer

    timer = PhaseTimer()
    before = len(obs_spans.recorded())
    with timer.phase("train+encrypt+aggregate"):
        pass
    with timer.phase("decrypt"):
        with obs_spans.span("hefl.phase.decrypt.decode"):
            pass
    with pytest.raises(ValueError):
        with timer.phase("decrypt"):   # re-entered, and left by an error
            raise ValueError
    rows = obs_spans.recorded()[before:]
    assert [s.name for s in rows] == [
        "hefl.phase.train+encrypt+aggregate", "hefl.phase.decrypt.decode",
        "hefl.phase.decrypt", "hefl.phase.decrypt"]
    want = {}
    for s in rows:
        if s.name.count(".") == 2:
            key = s.name[len(obs_spans.PHASE_PREFIX):]
            want[key] = want.get(key, 0.0) + s.seconds
    summary = timer.summary()
    assert list(summary) == ["train+encrypt+aggregate", "decrypt", "total"]
    for key, seconds in want.items():
        assert summary[key] == round(seconds, 4)
    assert summary["total"] == round(sum(want.values()), 4)


def test_span_tracer_measure_is_on_the_recorders_clock():
    tracer = obs_spans.SpanTracer(5)
    t_lo = obs_spans.now_ns()
    with tracer.measure("fsync", frames=3) as sp:
        pass
    t_hi = obs_spans.now_ns()
    row = obs_spans.recorded()[-1]
    assert (row.name, row.round) == ("hefl.span.fsync", 5)
    assert t_lo <= row.t0_ns <= row.t1_ns <= t_hi
    # the tree's wall-clock span is the same interval, in unix seconds
    assert sp.clock == "wall" and sp.args == {"frames": 3}
    assert sp.t0 == row.t0_ns * 1e-9 and sp.t1 == row.t1_ns * 1e-9
    assert t_lo * 1e-9 - 1.0 <= tracer.wall0 <= sp.t0
    # exported from the tracer's opening by default, from a shared base
    # when it is laid beside the recorder's rows
    own = [e for e in tracer.to_trace_events()
           if e["name"] == "hefl.span.fsync"]
    assert 0 <= own[0]["ts"] <= (t_hi - t_lo) / 1e3 + 1e6
    host = obs_spans.host_trace_events([row], base_ns=row.t0_ns)
    assert host[0]["ts"] == 0 and host[0]["args"]["t0_ns"] == row.t0_ns
    assert host[0]["dur"] == (row.t1_ns - row.t0_ns) / 1e3


def test_span_trace_holds_a_synchronous_runs_spans(tmp_path):
    from hefl_tpu.experiment import ExperimentConfig, HEConfig, run_experiment

    out_path = str(tmp_path / "spans.trace.json")
    out = run_experiment(ExperimentConfig(
        model="smallcnn", dataset="mnist", num_clients=2, rounds=2,
        train=CFG, he=HEConfig(n=256), n_train=32, n_test=16, seed=3,
        span_trace_path=out_path,
    ), verbose=False)
    assert out["span_trace"] == out_path
    with open(out_path) as f:
        doc = json.load(f)
    events = obs_trace.load_trace_events(out_path)
    names = [e["name"] for e in events]
    assert names.count("hefl.setup") == 1 and names.count("hefl.round") == 2
    for name in ("hefl.phase.train+encrypt+aggregate.dispatch",
                 "hefl.phase.decrypt.decode", "hefl.phase.evaluate"):
        assert names.count(name) == 2
    calls = {e["args"]["call"] for e in events}
    assert len(calls) == 1 and None not in calls   # this call's spans only
    assert min(e["ts"] for e in events) == 0
    assert doc["wall_base_ns"] == min(e["args"]["t0_ns"] for e in events)
    assert {e["args"]["round"] for e in events
            if e["name"] == "hefl.round"} == {0, 1}


# ------------------------------------------------------- trend gate


def _bench(dirpath, name, value):
    p = os.path.join(dirpath, name)
    with open(p, "w") as f:
        json.dump({"cmd": "x", "n": 1, "rc": 0,
                   "parsed": {"value": value}, "tail": ""}, f)
    return p


def test_trend_gate_clean_then_seeded_regression(tmp_path):
    d = str(tmp_path)
    _bench(d, "BENCH_r01.json", 100.0)
    _bench(d, "BENCH_r02.json", 90.0)      # improvement: fine
    out = str(tmp_path / "TREND.md")
    assert obs_trend._main(["--root", d, "--out", out, "--quiet"]) == 0
    md = open(out).read()
    assert "pipeline.wallclock_s" in md and "No regressions" in md

    # within tolerance (25%): 90 -> 110 vs best 90 is +22%, still ok
    _bench(d, "BENCH_r03.json", 110.0)
    assert obs_trend._main(["--root", d, "--quiet"]) == 0

    # past tolerance: regression, exit 1
    bad = _bench(d, "BENCH_r04.json", 200.0)
    assert obs_trend._main(["--root", d, "--quiet"]) == 1
    os.unlink(bad)

    # the same artifact appended via --extra (the seeded-fixture hook)
    extra = _bench(str(tmp_path / ".."), "BENCH_r99_extra.json", 200.0)
    assert obs_trend._main(
        ["--root", d, "--quiet", "--extra", extra]
    ) == 1

    # an empty history is not a silent pass
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    assert obs_trend._main(["--root", empty, "--quiet"]) == 2


def test_trend_gate_repo_history_is_clean_and_fixture_fails_it():
    # The committed BENCH_*.json artifacts must pass their own gate (this
    # is the schema contract: a renamed key zeroes a series and a real
    # regression fails CI)...
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = obs_trend.evaluate(root)
    assert sum(len(r.points) for r in rows) > 0
    assert [r.metric for r in rows if r.regressed] == []
    # every spec resolves at least one point from the committed history —
    # except the pipeline series: no chip record of it is committed (the
    # early ones were deleted in PR 21; the driver's ledger is the history)
    by_metric = {r.metric: r for r in rows}
    for spec in obs_trend.SPECS:
        if spec.metric != "pipeline.wallclock_s":
            assert by_metric[spec.metric].points, spec.metric
    # ...and the seeded fixture, gated against its seeded baseline, proves
    # the gate CAN fail.
    assert os.path.exists(FIXTURE) and os.path.exists(BASELINE_FIXTURE)
    rows = obs_trend.evaluate(root, extra=[BASELINE_FIXTURE, FIXTURE])
    bad = [r for r in rows if r.regressed]
    assert [r.metric for r in bad] == ["pipeline.wallclock_s"]
    assert rows and bad[0].points[-1][0] == os.path.basename(FIXTURE)
