"""Observability subsystem (hefl_tpu.obs): trace parser on the committed
golden fixture, named-scope survival through jit for both client-fusion
backends, the events JSONL log, the metrics registry, and the roofline
timing-floor guards."""

import dataclasses
import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hefl_tpu.obs import events as obs_events
from hefl_tpu.obs import metrics as obs_metrics
from hefl_tpu.obs import scopes as obs_scopes
from hefl_tpu.obs import trace as obs_trace
from hefl_tpu.utils import roofline

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
GOLDEN_TRACE = os.path.join(FIXTURES, "golden.trace.json.gz")
GOLDEN_HLO = os.path.join(FIXTURES, "golden_hlo.txt")


# ---------------------------------------------------------------- scopes


def test_scope_of_takes_deepest_and_handles_decoration():
    assert obs_scopes.scope_of(
        "jit(f)/jit(main)/hefl.sgd_core/jit(aug)/hefl.augment/gather"
    ) == "hefl.augment"
    # Transformations decorate the component: still found.
    assert obs_scopes.scope_of(
        "jit(f)/vmap(hefl.sgd_core)/vmap(jit(_shuffle))/while"
    ) == "hefl.sgd_core"
    assert obs_scopes.scope_of("jit(f)/jit(main)/reduce_sum") is None


# ----------------------------------------------------- golden-trace parse


def _golden_hlo() -> str:
    with open(GOLDEN_HLO) as f:
        return f.read()


def test_hlo_scope_map_covers_instructions_and_call_aliases():
    sm = obs_trace.hlo_scope_map(_golden_hlo())
    assert sm["dot.1"] == "hefl.sgd_core"       # vmap(hefl.sgd_core) decoration
    assert sm["fusion.2"] == "hefl.encrypt"
    assert sm["tanh.4.clone"] == "hefl.val"
    # call.N carries no metadata; resolved through to_apply=%parallel_X.
    assert sm["call.3"] == "hefl.val"
    assert "mystery.9" not in sm
    assert "while.5" not in sm


def test_golden_trace_bucketing():
    rec = obs_trace.trace_attribution(GOLDEN_TRACE, [_golden_hlo()])
    rows = rec["rows"]
    # Same op on two overlapping worker threads: union, not sum.
    assert rows["hefl.sgd_core"] == {"device_seconds": 150e-6, "op_events": 2}
    assert rows["hefl.encrypt"]["device_seconds"] == pytest.approx(50e-6)
    # The call wrapper and the inner op it spans merge into one val union.
    assert rows["hefl.val"] == {"device_seconds": 40e-6, "op_events": 2}
    # The scope-less mystery op and the scope-less container's uncovered
    # remainder land in unattributed; attributed time is never re-counted.
    assert rec["unattributed_s"] == pytest.approx(260e-6)
    assert rec["device_total_s"] == pytest.approx(500e-6)
    # Events of modules without supplied HLO are excluded entirely.
    assert set(rec["modules"]) == {"jit_golden"}
    assert rec["op_events"] == 7
    assert obs_trace.attributed_sum_s(rec) == pytest.approx(500e-6)


def test_trace_rows_order_follows_canonical_phases():
    rec = obs_trace.trace_attribution(GOLDEN_TRACE, [_golden_hlo()])
    found = list(rec["rows"])
    canon = [p for p in obs_scopes.PHASES if p in rec["rows"]]
    assert found == canon


def test_corrupt_and_truncated_traces_fail_loud(tmp_path):
    # Truncated gzip.
    blob = open(GOLDEN_TRACE, "rb").read()
    bad = tmp_path / "truncated.trace.json.gz"
    bad.write_bytes(blob[: len(blob) // 2])
    with pytest.raises(obs_trace.TraceParseError):
        obs_trace.trace_attribution(str(bad), [_golden_hlo()])
    # Valid gzip, malformed JSON.
    bad2 = tmp_path / "garbage.trace.json.gz"
    bad2.write_bytes(gzip.compress(b"{not json"))
    with pytest.raises(obs_trace.TraceParseError):
        obs_trace.trace_attribution(str(bad2), [_golden_hlo()])
    # Valid JSON, no traceEvents.
    bad3 = tmp_path / "empty.trace.json.gz"
    bad3.write_bytes(gzip.compress(json.dumps({"traceEvents": []}).encode()))
    with pytest.raises(obs_trace.TraceParseError):
        obs_trace.trace_attribution(str(bad3), [_golden_hlo()])
    # A logdir with no trace at all.
    with pytest.raises(obs_trace.TraceParseError):
        obs_trace.trace_attribution(str(tmp_path / "nothing"), [_golden_hlo()])
    # Events present but none for the supplied modules.
    with pytest.raises(obs_trace.TraceParseError):
        obs_trace.trace_attribution(
            GOLDEN_TRACE, ["HloModule jit_absent\nENTRY %m { ROOT %r = () tuple() }"]
        )
    # No HLO at all.
    with pytest.raises(obs_trace.TraceParseError):
        obs_trace.trace_attribution(GOLDEN_TRACE, [])


def test_host_trace_annotations_become_host_rows():
    # Driver-side hefl.* TraceAnnotations (no hlo_module in args) must
    # surface as first-class host_rows — e.g. the straggler wait — without
    # perturbing the device rows or the wall-agreement quantity.
    base = obs_trace.trace_attribution(GOLDEN_TRACE, [_golden_hlo()])
    events = obs_trace.load_trace_events(GOLDEN_TRACE)
    events = events + [
        {"ph": "X", "name": "hefl.straggler_wait", "ts": 1000.0,
         "dur": 250.0, "args": {}},
        {"ph": "X", "name": "hefl.straggler_wait", "ts": 2000.0,
         "dur": 150.0},
        {"ph": "X", "name": "hefl.phase.decrypt", "ts": 0.0, "dur": 50.0},
        # Non-hefl host events stay ignored.
        {"ph": "X", "name": "SomeRuntimeThing", "ts": 0.0, "dur": 9999.0},
    ]
    rec = obs_trace.trace_attribution(events, [_golden_hlo()])
    assert rec["host_rows"]["hefl.straggler_wait"] == {
        "seconds": pytest.approx(400e-6), "spans": 2,
    }
    assert rec["host_rows"]["hefl.phase.decrypt"]["spans"] == 1
    assert "SomeRuntimeThing" not in rec["host_rows"]
    # Device-side attribution is untouched by host spans.
    assert rec["rows"] == base["rows"]
    assert rec["device_total_s"] == base["device_total_s"]
    assert rec["unattributed_s"] == base["unattributed_s"]


# --------------------------------------- scopes survive jit, both backends


@pytest.mark.parametrize("backend", ["vmap", "fused"])
def test_named_scopes_survive_jit(backend):
    """The phase annotations must reach the compiled HLO for BOTH
    cross-client training backends — lose them and trace attribution
    silently degrades to one 'unattributed' bucket."""
    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.fl import TrainConfig
    from hefl_tpu.fl.fedavg import _build_round_fn, replicate_on
    from hefl_tpu.models import create_model
    from hefl_tpu.parallel import make_mesh

    (x, y), _, _ = make_dataset("mnist", seed=0, n_train=16, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), 2))
    module, params = create_model("smallcnn", rng=jax.random.key(0))
    cfg = TrainConfig(
        epochs=1, batch_size=4, num_classes=10, val_fraction=0.25,
        client_fusion=backend,
    )
    mesh = make_mesh(2)
    gp = replicate_on(mesh, params)
    keys = jax.random.split(jax.random.key(1), 2)
    fn = _build_round_fn(module, cfg, mesh)
    # metadata_preserving_compile: a persistent-cache-deserialized
    # executable answers as_text() without op_name metadata, which would
    # make this test flaky across warm suite reruns.
    with obs_trace.metadata_preserving_compile():
        txt = fn.lower(gp, jnp.asarray(xs), jnp.asarray(ys), keys).compile().as_text()
    for scope in (obs_scopes.SGD_CORE, obs_scopes.AUGMENT, obs_scopes.VAL,
                  obs_scopes.AGGREGATE):
        assert scope in txt, f"{scope} lost in jit under {backend} backend"
    sm = obs_trace.hlo_scope_map(txt)
    assert set(sm.values()) >= {
        obs_scopes.SGD_CORE, obs_scopes.AUGMENT, obs_scopes.VAL,
        obs_scopes.AGGREGATE,
    }


# ----------------------------------------------------------------- events


def test_event_log_roundtrip(tmp_path):
    path = tmp_path / "sub" / "events.jsonl"
    log = obs_events.EventLog(str(path))
    log.emit("round_phase", round=0, phase="train", seconds=1.5)
    log.emit("round_robust", round=0, excluded={"scheduled": 2},
             participation=np.asarray([1, 0], np.int32))
    log.close()
    evs = obs_events.read_events(str(path))
    assert [e["event"] for e in evs] == ["log_open", "round_phase", "round_robust"]
    assert evs[0]["schema_version"] == obs_events.SCHEMA_VERSION
    assert evs[1]["seconds"] == 1.5
    # numpy payloads are converted, not crashed on.
    assert evs[2]["participation"] == [1, 0]
    assert all("ts" in e for e in evs)


def test_event_log_reopen_truncates_torn_tail(tmp_path):
    # ISSUE 9 satellite: a crash mid-append leaves a torn final line (no
    # trailing newline). Reopening must truncate it and record a
    # torn_tail_recovered event — the log stays strictly parseable
    # forever instead of poisoning read_events(strict=True).
    path = str(tmp_path / "events.jsonl")
    log = obs_events.EventLog(path)
    log.emit("round_end", round=0)
    log.close()
    with open(path, "a") as f:
        f.write('{"ts": 1.0, "event": "round_e')   # the torn write
    with pytest.raises(ValueError, match="malformed"):
        obs_events.read_events(path)               # poisoned as-is
    log2 = obs_events.EventLog(path)
    log2.emit("round_end", round=1)
    log2.close()
    evs = obs_events.read_events(path)             # strict: must be clean
    kinds = [e["event"] for e in evs]
    assert kinds == [
        "log_open", "round_end", "torn_tail_recovered", "round_end"
    ]
    torn = next(e for e in evs if e["event"] == "torn_tail_recovered")
    assert torn["truncated_bytes"] == len('{"ts": 1.0, "event": "round_e')
    # a healthy reopen adds nothing
    log3 = obs_events.EventLog(path)
    log3.emit("round_end", round=2)
    log3.close()
    assert [e["event"] for e in obs_events.read_events(path)] == kinds + [
        "round_end"
    ]


def test_event_log_rotates_at_size_cap(tmp_path, monkeypatch):
    # HEFL_EVENTS_MAX_BYTES: the append-only log must rotate to <path>.1
    # instead of growing unbounded; both generations stay strictly
    # parseable and no emitted record is lost across the boundary.
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("HEFL_EVENTS_MAX_BYTES", "400")
    log = obs_events.EventLog(str(path))
    for i in range(30):
        log.emit("tick", i=i, pad="x" * 32)
    log.close()
    assert path.with_suffix(".jsonl.1").exists() or (
        tmp_path / "events.jsonl.1"
    ).exists()
    cur = obs_events.read_events(str(path))
    old = obs_events.read_events(str(path) + ".1")
    assert path.stat().st_size <= 400 + 120  # cap + one record of slack
    # The fresh generation announces where the history went.
    assert cur[0]["event"] == "log_open" and cur[0]["rotated_from"].endswith(
        "events.jsonl.1"
    )
    # The newest ticks are all in the current file, ending at the last one.
    ticks = [e["i"] for e in cur if e["event"] == "tick"]
    assert ticks == sorted(ticks) and ticks[-1] == 29
    # No duplicates across generations (one generation of history kept).
    all_ticks = ticks + [e["i"] for e in old if e["event"] == "tick"]
    assert len(all_ticks) == len(set(all_ticks))
    # Cap disabled: no rotation however many emits.
    monkeypatch.setenv("HEFL_EVENTS_MAX_BYTES", "0")
    log2 = obs_events.EventLog(str(tmp_path / "nocap.jsonl"))
    for i in range(50):
        log2.emit("tick", i=i, pad="x" * 32)
    log2.close()
    assert not (tmp_path / "nocap.jsonl.1").exists()


def test_rotation_shipper_hook(tmp_path, monkeypatch):
    # ISSUE 7 satellite: a pluggable shipper hook fires with the rotated
    # generation's path on every rotation (while the file still exists),
    # defaults to no-op, and a raising hook is swallowed — telemetry
    # shipping must never take down the run.
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("HEFL_EVENTS_MAX_BYTES", "400")
    shipped: list[str] = []

    log = obs_events.EventLog(str(path))

    def shipper(rotated_path):
        # the rotated generation must exist at callback time
        import os

        assert os.path.exists(rotated_path)
        shipped.append(rotated_path)
        # a hook may itself emit (e.g. record the shipment): the fresh
        # generation is already open, so this must not re-enter the
        # rotation or clobber the rotated_from header
        log.emit("shipped", path=rotated_path)

    def broken(rotated_path):
        raise RuntimeError("uploader down")

    obs_events.on_rotation(shipper)
    obs_events.on_rotation(shipper)   # idempotent registration
    obs_events.on_rotation(broken)    # must not break emission
    try:
        for i in range(30):
            log.emit("tick", i=i, pad="x" * 32)
        log.close()
    finally:
        assert obs_events.remove_rotation_hook(shipper)
        assert obs_events.remove_rotation_hook(broken)
        assert not obs_events.remove_rotation_hook(shipper)  # already gone
    assert shipped and all(p == str(path) + ".1" for p in shipped)
    # every rotation fired the hook exactly once (no double-registration)
    cur = obs_events.read_events(str(path))
    assert cur[0]["event"] == "log_open" and "rotated_from" in cur[0]
    # the log itself survived the broken hook: no record lost after it
    ticks = [e["i"] for e in cur if e["event"] == "tick"]
    assert ticks[-1] == 29


def test_histogram_metric_and_snapshot_delta():
    # The staleness-histogram leg: cumulative buckets, JSON-ready value,
    # per-run deltas through snapshot_delta, and type collisions loud.
    from hefl_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("stream.staleness_rounds", (0.0, 1.0, 2.0))
    for v in (0, 0, 1, 3, 10):
        h.observe(v)
    val = reg.snapshot()["stream.staleness_rounds"]
    assert val == {
        "le_0": 2, "le_1": 3, "le_2": 3, "le_inf": 5, "count": 5, "sum": 14.0
    }
    base = reg.snapshot()
    h.observe(1)
    delta = reg.snapshot_delta(base)["stream.staleness_rounds"]
    assert delta["count"] == 1 and delta["le_1"] == 1 and delta["le_0"] == 0
    # same name, same instance; different type or conflicting bounds, loud
    assert reg.histogram("stream.staleness_rounds") is h
    assert reg.histogram("stream.staleness_rounds", (0.0, 1.0, 2.0)) is h
    with pytest.raises(ValueError, match="bounds"):
        reg.histogram("stream.staleness_rounds", (0.0, 10.0))
    with pytest.raises(TypeError):
        reg.counter("stream.staleness_rounds")
    reg.counter("y")
    with pytest.raises(TypeError):
        reg.histogram("y")


def test_global_emit_honors_opt_out(tmp_path, monkeypatch):
    path = tmp_path / "events.jsonl"
    obs_events.configure(str(path))
    try:
        monkeypatch.setenv("HEFL_EVENTS", "0")
        assert obs_events.emit("compile", seconds=1.0) is None
        monkeypatch.setenv("HEFL_EVENTS", "1")
        assert obs_events.emit("compile", seconds=1.0) is not None
    finally:
        obs_events.configure(None)
    evs = obs_events.read_events(str(path))
    assert [e["event"] for e in evs] == ["log_open", "compile"]
    # Unconfigured global log: emit is a no-op, never an error.
    assert obs_events.emit("compile", seconds=2.0) is None
    assert obs_events.current_path() is None


def test_read_events_strict_fails_on_malformed(tmp_path):
    path = tmp_path / "events.jsonl"
    path.write_text('{"ts": 1, "event": "ok"}\nnot json\n')
    with pytest.raises(ValueError):
        obs_events.read_events(str(path))
    path.write_text('{"ts": 1}\n')  # missing required "event"
    with pytest.raises(ValueError):
        obs_events.read_events(str(path))
    assert obs_events.read_events(str(path), strict=False) == [{"ts": 1}]
    # Valid JSON that is not an object (torn write): same failure class.
    path.write_text('42\n')
    with pytest.raises(ValueError):
        obs_events.read_events(str(path))
    assert obs_events.read_events(str(path), strict=False) == []


def test_default_events_path():
    assert obs_events.default_events_path(None) == "events.jsonl"
    assert obs_events.default_events_path("/runs/x/ck.npz") == "/runs/x/events.jsonl"
    assert obs_events.default_events_path("ck.npz") == os.path.join(".", "events.jsonl")


def test_record_round_meta_publishes_counters_and_event(tmp_path, monkeypatch):
    from hefl_tpu.fl.faults import RoundMeta, record_round_meta

    monkeypatch.setenv("HEFL_EVENTS", "1")
    path = tmp_path / "events.jsonl"
    obs_events.configure(str(path))
    before = obs_metrics.snapshot()
    try:
        meta = RoundMeta.from_bits(np.asarray([0, 1, 2, 0]))
        record_round_meta(meta, round_index=3)
    finally:
        obs_events.configure(None)
    after = obs_metrics.snapshot()
    assert after.get("exclusions.scheduled", 0) - before.get("exclusions.scheduled", 0) == 1
    assert after.get("exclusions.nonfinite", 0) - before.get("exclusions.nonfinite", 0) == 1
    assert after.get("clients.excluded", 0) - before.get("clients.excluded", 0) == 2
    evs = obs_events.read_events(str(path))
    rob = [e for e in evs if e["event"] == "round_robust"]
    assert len(rob) == 1 and rob[0]["round"] == 3 and rob[0]["surviving"] == 2


# ---------------------------------------------------------------- metrics


def test_metrics_registry_basics():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(2)
    reg.gauge("b").set(5)
    reg.gauge("peak").max(10)
    reg.gauge("peak").max(7)
    assert reg.snapshot() == {"a": 3, "b": 5, "peak": 10}
    # Per-run view: counters delta against a baseline, gauges current.
    base = reg.snapshot()
    reg.counter("a").inc(4)
    reg.counter("new").inc()
    reg.gauge("b").set(9)
    assert reg.snapshot_delta(base) == {"a": 4, "b": 9, "new": 1, "peak": 10}
    with pytest.raises(TypeError):
        reg.gauge("a")
    with pytest.raises(TypeError):
        reg.counter("b")
    reg.reset()
    assert reg.snapshot() == {}


def test_compile_listener_counts_new_executables():
    obs_metrics.install_jax_listeners()
    obs_metrics.install_jax_listeners()  # idempotent
    before = obs_metrics.snapshot().get("jax.new_executables", 0)

    @jax.jit
    def _fresh(x):
        return x * 3.5 + 17.25

    _fresh(jnp.ones(3)).block_until_ready()
    mid = obs_metrics.snapshot().get("jax.new_executables", 0)
    assert mid > before
    _fresh(jnp.ones(3)).block_until_ready()  # cached: no new executable
    assert obs_metrics.snapshot().get("jax.new_executables", 0) == mid


# ----------------------------------------------- roofline timing floor


def test_utilization_clamped_and_flagged():
    counts = {"int_ops": 1e12, "bytes": 1e6}
    dev = "TPU v5 lite"
    rec = roofline.he_phase_stats(1e-6, counts, device=dev)  # util >> 1
    assert rec["util_vs_peak_int_ops"] == 1.0
    assert rec["timing_floor_suspect"] is True
    assert rec["util_vs_peak_int_ops_raw"] > 1.0
    ok = roofline.he_phase_stats(100.0, counts, device=dev)
    assert ok["util_vs_peak_int_ops"] < 1.0
    assert "timing_floor_suspect" not in ok
    # phase_stats mfu gets the same guard.
    ps = roofline.phase_stats(1e-9, flops=1e12, device=dev)
    assert ps["mfu"] == 1.0 and ps["timing_floor_suspect"] is True


def test_phase_seconds_never_round_to_zero():
    rec = roofline.phase_stats(3.2e-4)
    assert rec["seconds"] == 0.00032
    he = roofline.he_phase_stats(3.2e-4, {"int_ops": 1.0, "bytes": 1.0})
    assert he["seconds"] == 0.00032


def test_steady_seconds_repetition_times_sub_floor_phases():
    calls = []

    def tiny():
        calls.append(1)
        return jnp.zeros(())

    t = roofline.steady_seconds(tiny, reps=2, warmup=1)
    assert 0.0 < t < roofline.TIMING_FLOOR_S
    # Sub-floor measurement must have fallen back to a repetition chain:
    # far more calls than the warmup + 2 single-dispatch reps.
    assert len(calls) > 10


def test_steady_seconds_leaves_long_phases_alone():
    import time as _time

    calls = []

    def slow():
        calls.append(1)
        _time.sleep(roofline.TIMING_FLOOR_S * 2)
        return jnp.zeros(())

    roofline.steady_seconds(slow, reps=2, warmup=1)
    assert len(calls) == 3  # warmup + 2 reps, no repetition chain


# ------------------------------------------------------- quantiles (ISSUE 20)


def test_histogram_quantile_empty_single_and_validation():
    from hefl_tpu.obs.metrics import Histogram, exact_percentile

    h = Histogram(bounds=(1.0, 2.0, 4.0))
    assert h.quantile(0.5) == 0.0          # empty -> 0.0, not an error
    h.observe(3.25)
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) == 3.25       # single sample: every quantile
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(-0.1)
    assert exact_percentile([], 50) == 0.0
    assert exact_percentile([7.0], 99) == 7.0


def test_histogram_quantile_exact_matches_shared_percentile():
    # While the reservoir covers every observation, quantile() is EXACT —
    # bitwise the shared exact_percentile (the one _pctl delegates to).
    from hefl_tpu.obs.metrics import Histogram, exact_percentile

    rng = np.random.default_rng(7)
    xs = rng.uniform(0.0, 8.0, size=200).tolist()
    h = Histogram(bounds=(1.0, 2.0, 4.0, 8.0))
    for v in xs:
        h.observe(v)
    for q in (0.05, 0.5, 0.95, 0.99):
        assert h.quantile(q) == exact_percentile(xs, q * 100.0)
        # and the shared path IS np.percentile's linear interpolation
        assert abs(
            h.quantile(q) - float(np.percentile(np.asarray(xs), q * 100))
        ) < 1e-9


def test_histogram_quantile_reservoir_vs_bucket_agreement():
    # Past RESERVOIR_SIZE the estimate falls back to cumulative-bucket
    # interpolation; its error is bounded by the bucket width the
    # quantile lands in (the declared contract).
    from hefl_tpu.obs.metrics import RESERVOIR_SIZE, Histogram

    bounds = tuple(float(b) for b in range(1, 11))   # width-1 buckets
    rng = np.random.default_rng(11)
    xs = rng.uniform(0.0, 10.0, size=RESERVOIR_SIZE * 4)
    h = Histogram(bounds=bounds)
    for v in xs:
        h.observe(float(v))
    assert h.count > RESERVOIR_SIZE     # bucket path engaged
    for q in (0.1, 0.5, 0.9):
        est = h.quantile(q)
        exact = float(np.percentile(xs, q * 100))
        assert abs(est - exact) <= 1.0  # <= one bucket width
    # +inf-bucket rank clamps to max(top bound, mean), never unbounded
    h2 = Histogram(bounds=(1.0,))
    for v in (0.5, 50.0, 50.0):
        h2.observe(v)
    for v in np.linspace(0.1, 0.9, RESERVOIR_SIZE).tolist():
        h2.observe(v)
    assert h2.quantile(1.0) == max(1.0, h2.sum / h2.count)


def test_histogram_quantile_of_snapshot_delta():
    # The per-run view: a snapshot_delta dict carries buckets only (no
    # reservoir), so quantile_of is the bucket estimate over exactly the
    # run's observations — earlier runs subtracted out.
    from hefl_tpu.obs.metrics import Histogram, MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("lat", (1.0, 2.0, 4.0))
    for v in (0.5, 0.5, 3.0):           # an earlier run's observations
        h.observe(v)
    base = reg.snapshot()
    for v in (1.5, 1.5, 1.5, 3.5):
        h.observe(v)
    delta = reg.snapshot_delta(base)["lat"]
    assert delta["count"] == 4
    q50 = Histogram.quantile_of(delta, 0.5)
    assert 1.0 <= q50 <= 2.0            # the (1, 2] bucket, not (0, 1]
    assert Histogram.quantile_of({"count": 0}, 0.5) == 0.0
    with pytest.raises(ValueError, match="quantile"):
        Histogram.quantile_of(delta, 2.0)
