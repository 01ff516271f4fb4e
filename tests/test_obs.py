"""Observability subsystem (hefl_tpu.obs): the xplane reader on a fixture cut
from a chip's trace, named-scope survival through jit for both client-fusion
backends, the events JSONL log and the metrics registry."""

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

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


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


# ------------------------------------------- the chip's xplane, by the wire

# Cut by `python tests/fixtures/cut_xplane.py <chip trace> <fixture> 700`
# (then `gzip -9 -n`) from the `.xplane.pb` that `benchmarks/run.py
# --workload medcnn.sync_e10 --seed 3600000051 --trace 1 --keep-trace` wrote
# on a TPU v5e with an empty compile cache (PR 36): the head of the round
# program's ops, one whole training step among them, and the decrypt
# programs' ops, with their metadata; 315 KB, 56 KB as committed.
CHIP_TRACE_GZ = os.path.join(FIXTURES, "chip_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """The fixture as the profiler wrote it, in a logdir's layout (for
    `ProfileData` and the logdir search; the reader takes the `.gz` too)."""
    run = tmp_path_factory.mktemp("logdir") / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(gzip.open(CHIP_TRACE_GZ).read())
    return str(run / "host.xplane.pb")


@pytest.fixture(scope="module")
def chip(chip_trace):
    devices, host = obs_trace.read_xplane(chip_trace)
    assert len(devices) == 1
    return devices[0], host


@pytest.fixture(scope="module")
def chip_record(chip_trace):
    return obs_trace.trace_attribution(chip_trace)


def test_wire_reader_equals_profile_data(chip, chip_trace):
    """Names and nanoseconds of every event as `jax.profiler.ProfileData`
    reports them; `tf_op` and the rest are what it does not show."""
    from jax.profiler import ProfileData

    dev, host = chip
    plane = next(p for p in ProfileData.from_file(chip_trace).planes
                 if p.name == dev.plane)
    theirs = next(list(ln.events) for ln in plane.lines
                  if ln.name == obs_trace.OPS_LINE)
    mine = list(dev.events())
    assert len(mine) == len(theirs) > 400
    for a, b in zip(mine, theirs):
        assert (a["name"], a["start_ns"], a["dur_ns"]) == (
            b.name, b.start_ns, b.duration_ns)
        assert "tf_op" not in dict(b.stats)
    assert sum(1 for e in mine if e["tf_op"]) > len(mine) // 2
    assert {e["program"] for e in mine} == {
        "jit_decrypt", "jit_outer", "jit__decode_unpack"}
    # the recorder's host spans are on the same clock
    spans = [e for p in ProfileData.from_file(chip_trace).planes
             if p.name.startswith(obs_trace.HOST_PLANE)
             for ln in p.lines for e in ln.events if e.name == "hefl.round"]
    assert sorted((lo // 1000, (hi - lo) // 1000) for lo, hi in host["hefl.round"]
                  ) == sorted((int(e.start_ns), int(e.duration_ns)) for e in spans)


def test_wire_reader_equals_tensorflows_xplane_pb2(chip, chip_trace):
    """Against the generated module, where TensorFlow imports (in a process of
    its own: 13 s, and not beside JAX)."""
    code = (
        "import json, sys\n"
        "from tensorflow.tsl.profiler.protobuf import xplane_pb2\n"
        "x = xplane_pb2.XSpace(); x.ParseFromString(open(sys.argv[1], 'rb').read())\n"
        "p = next(p for p in x.planes if p.name.startswith('/device:TPU:'))\n"
        "names = {k: v.name for k, v in p.stat_metadata.items()}\n"
        "ln = next(l for l in p.lines if l.name == 'XLA Ops')\n"
        "out = []\n"
        "for e in ln.events:\n"
        "    m = p.event_metadata[e.metadata_id]\n"
        "    st = {names[s.metadata_id]: getattr(s, s.WhichOneof('value'))\n"
        "          for s in m.stats}\n"
        "    out.append([m.name, m.display_name, e.offset_ps, e.duration_ps,\n"
        "        st.get('tf_op', ''), st.get('hlo_category', ''),\n"
        "        st.get('flops', 0)])\n"
        "print(json.dumps(out))\n")
    import subprocess
    import sys

    run = subprocess.run([sys.executable, "-c", code, chip_trace],
                         capture_output=True, text=True, timeout=180,
                         env={**os.environ, "CUDA_VISIBLE_DEVICES": ""})
    if run.returncode != 0:
        pytest.skip("tensorflow's xplane_pb2 does not import here")
    dev, _ = chip
    mine = [[e["name"], e["display_name"], int(e["start_ns"]), int(e["dur_ns"]),
             e["tf_op"], e["hlo_category"], e["flops"]] for e in dev.events()]
    theirs = json.loads(run.stdout.strip().splitlines()[-1])
    assert [[n, d, o // 1000, t // 1000, *rest] for n, d, o, t, *rest in theirs
            ] == mine


def test_self_time_under_a_while(chip):
    # a `while` of 100 spans two ops of 30 and 20, the second holding one of 5
    start = np.array([0, 10, 50, 55, 200], np.int64)
    dur = np.array([100, 30, 20, 5, 7], np.int64)
    self_ps, leaf = obs_trace._self_times(start, dur)
    assert self_ps.tolist() == [50, 30, 15, 5, 7]
    assert leaf.tolist() == [False, True, False, True, True]
    # on the chip's line: the round program's `while` is there, self times
    # add up to the union of the intervals, and no op counts twice
    dev, _ = chip
    whiles = [i for i, k in enumerate(dev.op_id.tolist())
              if dev.ops[k].hlo_category == "while"]
    assert whiles and not dev.leaf[whiles].any()
    assert (dev.self_ps[whiles] < dev.dur_ps[whiles]).all()
    union = obs_trace._union_s(
        zip(dev.start_ps.tolist(), (dev.start_ps + dev.dur_ps).tolist()))
    assert dev.self_ps.sum() * 1e-12 == pytest.approx(union, rel=1e-12)


def test_deepest_scope_and_membership_at_any_depth(chip_record):
    tf_op = ("jit(outer)/while/body/closed_call/hefl.sgd_core/"
             "transpose(jvp(hefl.moe.experts))/while/body/hefl.moe_gmm/"
             "jit(gmm)/select_n:")
    assert obs_scopes.scope_of(tf_op) == "hefl.moe_gmm"
    assert obs_scopes.scopes_in(tf_op) == [
        "hefl.sgd_core", "hefl.moe.experts", "hefl.moe_gmm"]
    assert obs_scopes.scopes_in(
        "jit(f)/hefl.val/cond/jit(f)/hefl.val/cond/branch_1_fun/hefl.val/"
        "checkpoint/hefl.conv/while:") == [
        "hefl.val", "hefl.val", "hefl.val", "hefl.conv"]
    rec = chip_record
    assert "hefl.val/hefl.val" not in " ".join(rec["paths"])
    for scope, row in rec["rows"].items():  # deepest: chains that END there
        assert row["device_seconds"] == pytest.approx(sum(
            r["device_seconds"] for chain, r in rec["paths"].items()
            if chain.split("/")[-1] == scope))
    for scope, row in rec["under"].items():  # any depth: chains that HOLD it
        assert row["device_seconds"] == pytest.approx(sum(
            r["device_seconds"] for chain, r in rec["paths"].items()
            if scope in chain.split("/")))
        assert row["device_seconds"] >= rec["rows"].get(
            scope, {"device_seconds": 0.0})["device_seconds"]
    core = rec["under"]["hefl.sgd_core"]
    assert 0 < core["backward_seconds"] < core["device_seconds"]
    assert rec["backward_s"] == pytest.approx(core["backward_seconds"])


def test_kernel_families_are_custom_calls_less_their_number(chip, chip_record):
    op = obs_trace.Op(name="%x", display_name="splash_mqa_fwd_residuals.90",
                      hlo_category="custom-call")
    assert op.family == "splash_mqa_fwd_residuals"
    assert dataclasses.replace(op, display_name="hefl.encrypt.3").family == (
        "hefl.encrypt")
    assert dataclasses.replace(op, hlo_category="loop fusion").family is None
    dev, _ = chip
    calls = {dev.ops[k].family for k in dev.op_id.tolist()} - {None}
    assert "hefl.decrypt" in calls
    assert set(chip_record["families"]) == calls
    assert chip_record["families"]["hefl.decrypt"]["op_events"] == 2


def test_flops_and_bytes_count_leaf_ops_only(chip, chip_record):
    dev, _ = chip
    flops = sum(dev.ops[k].flops for k, leaf in
                zip(dev.op_id.tolist(), dev.leaf.tolist()) if leaf)
    every = sum(dev.ops[k].flops for k in dev.op_id.tolist())
    assert chip_record["flops"] == flops > 0
    assert every > flops  # the `while`s count their bodies again
    assert chip_record["bytes_accessed"] == sum(
        dev.ops[k].bytes_accessed for k, leaf in
        zip(dev.op_id.tolist(), dev.leaf.tolist()) if leaf)


def test_chip_trace_bucketing(chip, chip_record, chip_trace):
    rec, (dev, _) = chip_record, chip
    busy = dev.self_ps.sum() * 1e-12
    assert rec["device_total_s"] == pytest.approx(busy)
    assert sum(r["device_seconds"] for r in rec["rows"].values()
               ) + rec["unattributed_s"] == pytest.approx(busy)
    assert sum(rec["modules"].values()) == pytest.approx(busy)
    assert sum(r["device_seconds"] for r in rec["paths"].values()
               ) == pytest.approx(busy)
    assert rec["unattributed_s"] == rec["paths"][""]["device_seconds"]
    assert rec["op_events"] == len(dev.op_id) == sum(
        r["op_events"] for r in rec["paths"].values())
    assert rec["planes"] == 1 and rec["source"] == "xplane"
    # the decrypt kernel's program and, since PR 36, the decode's are under
    # `hefl.decrypt` whole, but for the decode's unscoped parameter copies
    decrypt = sum(s for m, s in rec["modules"].items() if "dec" in m)
    assert 0.95 * decrypt < rec["rows"]["hefl.decrypt"]["device_seconds"] <= decrypt
    # a logdir is searched for its newest .xplane.pb; the .gz reads the same
    logdir = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        chip_trace))))
    assert obs_trace.trace_attribution(logdir)["paths"] == rec["paths"]
    assert obs_trace.trace_attribution(CHIP_TRACE_GZ)["paths"] == rec["paths"]
    assert "hefl.decrypt" in obs_trace.format_table(rec)


def test_trace_rows_order_follows_canonical_phases(chip_record):
    found = list(chip_record["rows"])
    canon = [p for p in obs_scopes.PHASES if p in chip_record["rows"]]
    assert found == canon and len(found) >= 4


def _rewritten(tmp_path, old: bytes, new: bytes) -> str:
    """The fixture with a name replaced by one as long (lengths hold)."""
    assert len(old) == len(new)
    blob = gzip.open(CHIP_TRACE_GZ).read()
    assert old in blob
    path = tmp_path / "rewritten.xplane.pb"
    path.write_bytes(blob.replace(old, new))
    return str(path)


@pytest.mark.parametrize("fault", ["truncated", "no_device_plane",
                                   "no_ops_line", "no_tf_op", "no_file"])
def test_unusable_traces_fail_loud(tmp_path, fault):
    if fault == "truncated":
        blob = gzip.open(CHIP_TRACE_GZ).read()
        path = tmp_path / "half.xplane.pb"
        path.write_bytes(blob[: len(blob) // 2])
        path, error = str(path), obs_trace.TraceParseError
    elif fault == "no_device_plane":
        path = _rewritten(tmp_path, b"/device:TPU:0", b"/device:XPU:0")
        error = obs_trace.NoDevicePlane
    elif fault == "no_ops_line":
        path = _rewritten(tmp_path, b"XLA Ops", b"XLA Opz")
        error = obs_trace.TraceParseError
    elif fault == "no_tf_op":
        path = _rewritten(tmp_path, b"tf_op", b"tf_oq")
        error = obs_trace.NoScopeMetadata
    else:
        path, error = str(tmp_path / "nothing"), obs_trace.TraceParseError
    with pytest.raises(error):
        obs_trace.trace_attribution(path)


@pytest.mark.parametrize("as_json", [False, True])
def test_cli_prints_the_table_of_a_profiled_run(tmp_path, capsys, as_json,
                                                chip_trace):
    """`hefl-train --profile DIR` ends by printing what `DIR` holds."""
    from hefl_tpu.cli import print_trace_attribution

    print_trace_attribution(os.path.dirname(chip_trace), as_json=as_json)
    out = capsys.readouterr().out
    if as_json:
        assert json.loads(out)["trace_attribution"]["source"] == "xplane"
    else:
        assert "a fusion counts under the scope of its root" in out
        assert "hefl.decrypt" in out and "jit_decrypt" in out
    # a CPU run's trace: said on standard error, and the run has not failed
    cpu = os.path.dirname(_rewritten(tmp_path, b"/device:TPU:0", b"/device:XPU:0"))
    print_trace_attribution(cpu, as_json=as_json)
    said = capsys.readouterr()
    assert said.out == "" and "no device seconds by scope" in said.err


def test_host_trace_annotations_become_host_rows(chip, chip_record):
    # Driver-side hefl.* TraceAnnotations (`obs.spans`) surface as
    # first-class host_rows, beside and apart from the device rows.
    _, host = chip
    rows = chip_record["host_rows"]
    assert set(rows) == set(host) and all(k.startswith("hefl.") for k in rows)
    assert rows["hefl.round"]["spans"] == 2
    wait = "hefl.phase.train+encrypt+aggregate.device_wait"
    assert 0 < rows[wait]["seconds"] < rows["hefl.round"]["seconds"]
    assert rows["hefl.phase.decrypt"]["seconds"] == pytest.approx(
        sum(hi - lo for lo, hi in host["hefl.phase.decrypt"]) * 1e-12)
    assert not any(k.startswith("hefl.phase") for k in chip_record["rows"])


# --------------------------------------- scopes survive jit, both backends


def _op_name_scopes(hlo_text: str) -> set[str]:
    """Every scope chain the compiled program's `op_name`s hold."""
    import re

    return {"/".join(dict.fromkeys(obs_scopes.scopes_in(name)))
            for name in re.findall(r'op_name="([^"]*)"', hlo_text)}


@pytest.mark.parametrize("backend,model,dataset", [
    ("vmap", "smallcnn", "mnist"), ("fused", "smallcnn", "mnist"),
    ("vmap", "resnet20", "cifar10"), ("fused", "resnet20", "cifar10"),
])
def test_named_scopes_survive_jit(backend, model, dataset):
    """The phase annotations must reach the compiled HLO for BOTH
    cross-client training backends and both image models — lose them and
    trace attribution silently degrades to one 'unattributed' bucket."""
    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.fl import TrainConfig
    from hefl_tpu.fl.fedavg import _build_round_fn, replicate_on
    from hefl_tpu.models import create_model
    from hefl_tpu.parallel import make_mesh

    (x, y), _, _ = make_dataset(dataset, seed=0, n_train=16, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), 2))
    module, params = create_model(model, rng=jax.random.key(0))
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
    chains = _op_name_scopes(txt)
    held = {s for chain in chains for s in chain.split("/")}
    model_scopes = {obs_scopes.CONV, obs_scopes.DENSE} | (
        {obs_scopes.NORM} if model == "resnet20" else set())
    wanted = {obs_scopes.SGD_CORE, obs_scopes.AUGMENT, obs_scopes.VAL,
              obs_scopes.AGGREGATE, obs_scopes.ADAM, obs_scopes.BATCH
              } | model_scopes
    assert held >= wanted, f"{wanted - held} lost in jit under {backend}"
    # the augment warp sits inside the step under both lowerings
    assert f"{obs_scopes.SGD_CORE}/{obs_scopes.AUGMENT}" in chains
    # the model's scopes sit INSIDE the step's and validation's
    for scope in model_scopes:
        assert f"{obs_scopes.SGD_CORE}/{scope}" in chains
        assert f"{obs_scopes.VAL}/{scope}" in chains


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
