"""The ten per-layer metrics that read the program's span recorder and its
compile counters (PR 24): each reader on a hand-made store, on a program
that has no recorder, and on a small trace recorded on the chip with the
nested `hefl.phase.<phase>.<step>` annotations in it; and the reduction's
old numbers (the three phases' busy seconds, the idle total) with and
without those children.

Listed in BENCHMARK.json's `paths`. No device or topology call at import.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks")
TRAIN = "hefl.phase.train+encrypt+aggregate"
PHASES = (TRAIN, "hefl.phase.decrypt", "hefl.phase.evaluate")
NEW = ("round_dispatch_ms", "round_wait_idle_ms", "decrypt_launch_s",
       "decrypt_decode_s", "decrypt_wait_s", "setup_data_s", "setup_start_s",
       "setup_rounds_s", "setup_program_load_s", "setup_executables")


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run():   # loading run.py puts benchmarks/ on sys.path, as the command does
    return _load("_hefl_bench_run_spans", os.path.join(BENCH, "run.py"))


@pytest.fixture(scope="module")
def red(run):
    return sys.modules["reduce"]


@pytest.fixture()
def readers(run):
    return {name: run._module_at(os.path.join(BENCH, "layer_metrics",
                                              name + ".py")).read
            for name in NEW}


@pytest.fixture(scope="module")
def recorded_trace():
    with open(os.path.join(HERE, "trace_nested.json")) as f:
        return json.load(f)


class Store:
    """A hand-made recorder store: rows in seconds, kept in nanoseconds."""

    def __init__(self):
        from hefl_tpu.obs.spans import HostSpan

        self._row, self.rows = HostSpan, []

    def add(self, name, t0, seconds, call=None, round=None):
        self.rows.append(self._row(
            len(self.rows), None, name, call, round,
            int(t0 * 1e9), int((t0 + seconds) * 1e9)))


def _hand_made() -> Store:
    st = Store()
    st.add("hefl.setup.data", 0.0, 5.0)                # made ahead of the calls
    st.add("hefl.setup.stage", 6.0, 1.0, call=0)       # the warm-up call
    st.add("hefl.setup.model", 7.0, 0.5, call=0)
    st.add("hefl.setup", 6.0, 3.0, call=0)
    st.add("hefl.round", 9.0, 4.0, call=0, round=0)
    st.add("hefl.round", 13.0, 2.0, call=0, round=1)
    st.add(TRAIN + ".dispatch", 13.0, 0.5, call=0, round=1)   # not the window's
    st.add("hefl.setup.stage", 15.0, 0.7, call=1)      # the measured call
    st.add("hefl.setup.keygen", 15.7, 0.3, call=1)
    st.add("hefl.setup", 15.0, 1.5, call=1)
    st.add("hefl.round", 17.0, 2.0, call=1, round=0)   # the lead-in round
    for r, dispatch_ms in enumerate((2.0, 3.0, 4.0, 100.0), start=1):
        t = 17.0 + 2.0 * r
        st.add(TRAIN + ".dispatch", t, dispatch_ms * 1e-3, call=1, round=r)
        st.add(TRAIN + ".device_wait", t + 0.2, 1.0, call=1, round=r)
        st.add("hefl.phase.decrypt.kernel", t + 1.3, 0.002, call=1, round=r)
        st.add("hefl.phase.decrypt.decode", t + 1.4, 0.1, call=1, round=r)
        st.add("hefl.phase.decrypt.unpack", t + 1.5, 0.02, call=1, round=r)
        st.add("hefl.phase.decrypt.wait", t + 1.6, 0.005, call=1, round=r)
        st.add("hefl.round", t, 1.9, call=1, round=r)
    st.add("hefl.setup.data", 30.0, 9.0)   # after the window: the checks' own
    return st


def _counters(monkeypatch, values):
    from hefl_tpu.obs import metrics as obs_metrics

    monkeypatch.setattr(
        obs_metrics, "counter",
        lambda name: types.SimpleNamespace(value=values.get(name, 0)))


def test_each_reader_on_a_hand_made_store(readers, monkeypatch):
    from hefl_tpu.obs import spans as obs_spans

    monkeypatch.setattr(obs_spans, "recorded", lambda: list(_hand_made().rows))
    _counters(monkeypatch, {"jax.compile_seconds": 31.25,
                            "jax.new_executables": 150})
    trace = {"rounds_traced": 2,
             "phase_busy_s": {TRAIN: 2.4, TRAIN + ".device_wait": 1.990}}
    got = {name: read({}, trace) for name, read in readers.items()}
    assert got == pytest.approx({
        "round_dispatch_ms": 3.5,        # median of the window's 2, 3, 4, 100
        "round_wait_idle_ms": 5.0,       # (2 x 1.0 s waited - 1.990 s busy) / 2
        "decrypt_launch_s": 0.002,
        "decrypt_decode_s": 0.12,        # decode + unpack, summed in a round
        "decrypt_wait_s": 0.005,
        "setup_data_s": 5.0,             # not the 9 s made after the window
        "setup_start_s": 2.5,            # 1.0 + 0.5 + 0.7 + 0.3: both calls
        "setup_rounds_s": 8.0,           # two warm-up rounds and the lead-in
        "setup_program_load_s": 31.25,
        "setup_executables": 150.0,
    }, rel=1e-6)
    # a plain run (no trace) and a trace of a program without the child
    # annotation leave the trace's metric out, and only that one
    assert readers["round_wait_idle_ms"]({}, None) is None
    assert readers["round_wait_idle_ms"](
        {}, {"rounds_traced": 2, "phase_busy_s": {TRAIN: 2.4}}) is None
    assert readers["round_dispatch_ms"]({}, None) == pytest.approx(3.5)


def test_readers_find_nothing_in_a_program_without_the_recorder(
        readers, monkeypatch):
    """What the parent commit gives these files: no `recorded`, no rows, no
    counts. Every reader returns None and raises nothing."""
    from hefl_tpu.obs import spans as obs_spans

    trace = {"rounds_traced": 2, "phase_busy_s": {TRAIN: 2.4}}
    _counters(monkeypatch, {})
    monkeypatch.setattr(obs_spans, "recorded", lambda: [])
    assert {name: read({}, trace) for name, read in readers.items()} == dict.fromkeys(NEW)
    monkeypatch.delattr(obs_spans, "recorded")
    assert {name: read({}, trace) for name, read in readers.items()} == dict.fromkeys(NEW)


def test_the_benchmark_declares_the_ten_and_finds_their_files(run):
    declares_the_ten_and_finds_their_files(
        run, os.path.join(ROOT, "BENCHMARK.json"))


def declares_the_ten_and_finds_their_files(run, path) -> None:
    with open(path) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])    # appended as one block; later PRs append after
    assert first >= 11 and names[first:first + 10] == list(NEW)
    layers = {m["layer"] for m in bench["per_layer"][:first]}
    for name in NEW:
        assert os.path.exists(run._find(bench["paths"], "layer_metrics",
                                        name + ".py"))
        assert entries[name]["moves"] == (
            "setup_s" if name.startswith("setup_") else "round_s")
    # the driver's and the owner's metrics keep the layer names that are there
    assert {entries[n]["layer"] for n in NEW[:5] + NEW[6:8]} <= layers


HELD = (declares_the_ten_and_finds_their_files,)  # of a copy with additions
# too: `test_benchmark_additions.py`


def _host(events):
    return [e for e in events if not e["plane"].startswith("/device:")]


def _raster_busy_s(events, lo, hi, step=100.0):
    """Device-busy seconds in [lo, hi) by a 100 ns raster of the timeline:
    the reduction's number by another method."""
    import numpy as np

    t_hi = max(e["start_ns"] + e["dur_ns"] for e in events)
    busy = np.zeros(int(t_hi / step) + 2, bool)
    for e in events:
        if e["plane"].startswith("/device:"):
            busy[int(e["start_ns"] / step):
                 int((e["start_ns"] + e["dur_ns"]) / step) + 1] = True
    return busy[int(lo / step): int(hi / step)].sum() * step * 1e-9


def test_old_numbers_read_the_same_with_and_without_the_children(
        red, recorded_trace):
    """`read_xplane` keeps every host annotation named `hefl.phase.*`, so the
    steps inside a phase reach `reduce_trace` with no edit to it. They add
    keys to `phase_busy_s`; the three phases' own keys, the window, the busy
    seconds and the idle total by owner stay what they were."""
    events = recorded_trace["events"]
    steps = [e for e in _host(events) if e["scope"].count(".") == 3]
    assert {e["name"] for e in steps} == {
        TRAIN + ".dispatch", TRAIN + ".prefetch", TRAIN + ".device_wait",
        "hefl.phase.decrypt.kernel", "hefl.phase.decrypt.decode",
        "hefl.phase.decrypt.unpack", "hefl.phase.decrypt.wait"}
    with_steps = red.reduce_trace(events, 2)
    without = red.reduce_trace([e for e in events if e not in steps], 2)
    assert set(without["phase_busy_s"]) == set(PHASES)
    assert set(with_steps["phase_busy_s"]) == set(PHASES) | {
        e["name"] for e in steps}
    for phase in PHASES:
        assert with_steps["phase_busy_s"][phase] == without["phase_busy_s"][phase]
    for key in ("window_s", "busy_s", "scope_s"):
        assert with_steps[key] == without[key]
    assert with_steps["breakdown"] == without["breakdown"]
    assert sum(v for _, v in with_steps["breakdown"]["idle_gaps"]) == pytest.approx(
        with_steps["window_s"] - with_steps["busy_s"], rel=1e-6)
    # each step lies inside a phase of its name's head, and the chip's work
    # under a phase is found again under its steps
    for e in steps:
        parent = e["name"].rsplit(".", 1)[0]
        assert any(p["name"] == parent and p["start_ns"] <= e["start_ns"]
                   and e["start_ns"] + e["dur_ns"] <= p["start_ns"] + p["dur_ns"]
                   for p in _host(events))
    busy = with_steps["phase_busy_s"]
    under_steps = sum(v for k, v in busy.items()
                      if k.startswith("hefl.phase.decrypt."))
    assert under_steps == pytest.approx(busy["hefl.phase.decrypt"], rel=0.05)
    # the decode is the host's: the chip is busy under 3% of it
    decode = next(e for e in steps if e["name"].endswith(".decode"))
    assert busy["hefl.phase.decrypt.decode"] < 0.03 * decode["dur_ns"] * 1e-9


def test_each_reader_on_a_recorded_chip_trace(readers, red, recorded_trace,
                                              monkeypatch):
    """The recorder's rows of two rounds, cut to the trace's window and laid
    on its axis, and the reduction of that trace: what a `--trace 1` run
    hands the readers, at a size a test can hold."""
    from hefl_tpu.obs import spans as obs_spans

    rows = [obs_spans.HostSpan(**s) for s in recorded_trace["spans"]]
    monkeypatch.setattr(obs_spans, "recorded", lambda: rows)
    _counters(monkeypatch, {})
    events = recorded_trace["events"]
    trace = red.reduce_trace(events, 2)
    got = {name: read({}, trace) for name, read in readers.items()}
    ms = lambda name, rnd: next(   # noqa: E731
        (s.t1_ns - s.t0_ns) * 1e-6 for s in rows
        if s.name == name and s.round == rnd)
    # the idle under the driver's wait: by the store's seconds less a raster
    # of the device's timeline under the trace's two `device_wait` annotations
    waits = [e for e in _host(events) if e["name"] == TRAIN + ".device_wait"]
    rastered = sum(_raster_busy_s(events, e["start_ns"],
                                  e["start_ns"] + e["dur_ns"]) for e in waits)
    waited_ms = ms(TRAIN + ".device_wait", 1) + ms(TRAIN + ".device_wait", 2)
    assert got["round_wait_idle_ms"] == pytest.approx(
        (waited_ms - rastered * 1e3) / 2, abs=0.02)
    assert 0 < got["round_wait_idle_ms"] < 1.0   # of 2 ms waited a round
    assert got["round_dispatch_ms"] == pytest.approx(ms(TRAIN + ".dispatch", 2))
    assert 2.0 < got["round_dispatch_ms"] < 8.0
    assert got["decrypt_launch_s"] == pytest.approx(
        ms("hefl.phase.decrypt.kernel", 1) * 1e-3)
    assert got["decrypt_decode_s"] == pytest.approx(
        (ms("hefl.phase.decrypt.decode", 1) + ms("hefl.phase.decrypt.unpack", 1)) * 1e-3)
    assert got["decrypt_wait_s"] == pytest.approx(
        ms("hefl.phase.decrypt.wait", 1) * 1e-3)
    # the three steps are the phase, to a fraction of a millisecond
    phase_s = ms("hefl.phase.decrypt", 1) * 1e-3
    steps_s = (got["decrypt_launch_s"] + got["decrypt_decode_s"]
               + got["decrypt_wait_s"])
    assert phase_s - 5e-4 < steps_s <= phase_s
    # the cut holds no lead-in round and the counters read nothing: no set-up
    assert [got[n] for n in NEW[5:]] == [None] * 5
    # a row and the trace's annotation of the same name are one interval, to
    # the tens of microseconds the profiler's own Python hooks take
    for s in rows:
        if s.name.startswith("hefl.phase."):
            assert any(e["name"] == s.name
                       and abs(e["start_ns"] - s.t0_ns) < 50e3
                       and abs(e["start_ns"] + e["dur_ns"] - s.t1_ns) < 50e3
                       for e in _host(events)), s
