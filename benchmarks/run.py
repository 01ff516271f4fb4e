"""One run of one benchmark cell: the encrypted federated round on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration file
(`<path>/configs/<name>.json`, named by the entry's `file`) and a traffic
mix (`<path>/traffic/<mix>.json`), merged field by field into the program's
`ExperimentConfig`. Per-layer metrics are read by `<path>/layer_metrics/
<metric>.py`; what a round's work counts and the numbers that decide `correct`
come from `<path>/checks/<check>.py`, which the configuration names with its
key `check` (`image_classifier` without it) and which knows the kind of
model and round, with the plain references in `<path>/reference/`; `<path>`
is any directory of BENCHMARK.json's `paths`. Adding a cell, a mix, a
configuration, a check or a per-layer metric adds files and entries and
edits none.

The run: set-up (imports, data from the seed, keys, compile or cache load,
a two-round warm-up call of `run_experiment`), then a measured call whose
first round is a lead-in and whose remaining whole rounds are the window,
then the checks that decide `correct`, outside the window. The last line of
standard output is the result object, its last key `checks` each number
compared beside its limit (also the last lines of standard error); earlier
lines say what was run.
"""

from __future__ import annotations

import time

_T0 = time.time()  # process start, before the heavy imports

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
import typing  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
for _p in (ROOT, _HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import reduce as red  # noqa: E402  (benchmarks/reduce.py)

PHASES = ("train+encrypt+aggregate", "decrypt", "evaluate")
MIN_WINDOW_ROUNDS = 3
TRACED_ROUNDS = 2
DEFAULT_CHECK = "image_classifier"  # of a configuration without the key `check`


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# --------------------------------------------------------------------------
# the cell: BENCHMARK.json + its data files
# --------------------------------------------------------------------------


def _find(paths, *parts) -> str:
    for base in paths:
        cand = os.path.join(ROOT, base, *parts)
        if os.path.exists(cand):
            return cand
    raise FileNotFoundError(f"{os.path.join(*parts)} under none of {paths}")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench_path: str, workload: str) -> dict:
    bench = _read_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    paths = bench["paths"]

    def here(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    config = _read_json(os.path.join(ROOT, conf["file"]))
    return {
        "cell": cell, "paths": paths, "config": config,
        # a file of the cell found by name: module("reference", "adam")
        "module": lambda kind, name: _module_at(
            _find(paths, kind, name + ".py")),
        "check": _find(paths, "checks",
                       config.get("check", DEFAULT_CHECK) + ".py"),
        "traffic": _read_json(_find(paths, "traffic", cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
    }


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _dataclass_in(hint):
    if dataclasses.is_dataclass(hint):
        return hint
    return next((a for a in typing.get_args(hint) if dataclasses.is_dataclass(a)),
                None)


def from_dict(cls, data: dict):
    """A (nested, frozen) dataclass from JSON: generic over its fields, so a
    mix may set any field of ExperimentConfig by name (stream, packing, hhe,
    faults, mesh_ct, ...). Lists become tuples (the configs are hashed)."""
    hints = typing.get_type_hints(cls)
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = set(data) - known
    if unknown:
        raise ValueError(f"{cls.__name__} has no field(s) {sorted(unknown)}")
    kwargs = {}
    for name, val in data.items():
        sub = _dataclass_in(hints[name])
        if sub is not None and isinstance(val, dict):
            val = from_dict(sub, val)
        elif isinstance(val, list):
            val = tuple(val)
        kwargs[name] = val
    return cls(**kwargs)


def build_config(cell: dict, seed: int, **over):
    from hefl_tpu.experiment import ExperimentConfig

    fields = _merge(cell["config"]["experiment"], cell["traffic"]["experiment"])
    return dataclasses.replace(from_dict(ExperimentConfig, fields),
                               seed=int(seed), **over)


@functools.lru_cache(maxsize=None)  # one instance a file, whoever asks
def _module_at(path: str) -> types.ModuleType:
    name = "_bench_" + hashlib.sha1(path.encode()).hexdigest()[:12]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# reading the program's own record of a call
# --------------------------------------------------------------------------


def last_call_events(events_path: str) -> list[dict]:
    from hefl_tpu.obs import events as obs_events

    evs = obs_events.read_events(events_path)
    start = max(i for i, e in enumerate(evs) if e["event"] == "experiment_start")
    return evs[start:]


def window_record(call_events: list[dict], history: list[dict]) -> dict:
    """The window of a measured call: from the end of its lead-in round to
    the end of its last. Round times are differences of consecutive
    `round_end` stamps, so they hold the driver's time between phases."""
    ends = [e["ts"] for e in call_events if e["event"] == "round_end"]
    if len(ends) != len(history) or len(ends) < 2:
        raise RuntimeError(f"{len(ends)} round_end events, {len(history)} rounds")
    rounds = [
        {"wall_s": hi - lo, "phases": {k: rec["phases"][k] for k in PHASES
                                        if k in rec["phases"]}}
        for lo, hi, rec in zip(ends, ends[1:], history[1:])
    ]
    compiles = [e for e in call_events
                if e["event"] == "compile" and e["ts"] > ends[0]]
    failed = sum(
        1 for e in call_events
        if e["event"] in ("round_retry", "round_failed") and e["ts"] > ends[0]
    ) + sum(
        1 for rec in history[1:]
        if any(rec.get("robust", {}).get("excluded", {}).values())
        or rec.get("stream", {}).get("committed") is False
    )
    return {
        "t_open": ends[0], "window_s": ends[-1] - ends[0], "rounds": rounds,
        "executables_in_window": len(compiles), "failed": failed,
    }


def data_digest(arrays) -> str:
    import numpy as np

    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# --------------------------------------------------------------------------
# the checks that decide `correct`: the configuration's check module gives the
# numbers (`<path>/checks/<name>.py`), the harness judges them
# --------------------------------------------------------------------------


def judge(numbers: dict, limits: dict) -> list[dict]:
    """Each number beside its limit. `limits[name]` is {"max": v} or
    {"min": v}; a number without a limit is a fault of the cell's files."""
    rows = []
    for name, value in numbers.items():
        lim = limits[name]
        ok = (value <= lim["max"]) if "max" in lim else (value >= lim["min"])
        rows.append({"check": name, "value": value, **lim,
                     "ok": bool(ok and math.isfinite(value))})
    return rows


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


class RoundTracer:
    """Wraps the program's event emitter for one call: starts the profiler
    when the lead-in round ends and stops it `rounds` rounds later, so the
    trace holds steady rounds only (the program's own `profile_dir` traces
    the cold round 0)."""

    def __init__(self, logdir: str, rounds: int):
        self.logdir, self.rounds, self.seen, self.on = logdir, rounds, 0, False

    def __enter__(self):
        from hefl_tpu.obs import events as obs_events

        self._events, self._emit = obs_events, obs_events.emit

        def emit(event, **fields):
            import jax

            rec = self._emit(event, **fields)
            if event == "round_end":
                self.seen += 1
                if self.seen == 1:
                    jax.profiler.start_trace(self.logdir)
                    self.on = True
                elif self.seen == 1 + self.rounds and self.on:
                    jax.profiler.stop_trace()
                    self.on = False
            return rec

        obs_events.emit = emit
        return self

    def __exit__(self, *exc):
        import jax

        self._events.emit = self._emit
        if self.on:
            jax.profiler.stop_trace()
            self.on = False


def _xplane_under(logdir: str) -> str:
    hits = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
            for f in fs if f.endswith(".xplane.pb")]
    if not hits:
        raise RuntimeError(f"no .xplane.pb under {logdir}")
    return max(hits, key=os.path.getmtime)


def run_cell(bench_path: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True, workdir: str | None = None,
             keep_trace: str | None = None) -> dict:
    """-> the result object (the caller prints it as the last line)."""
    # This frame keeps the 97 slots it had when the checks' locals lived in it
    # (PR 26 took 15 out). JAX traces the round program hundreds of frames
    # above, CPython 3.12 is 40-100x slower on a call that straddles a 16 KB
    # chunk of its frame stack, and the bytes below decide which hot calls do:
    # at 82 slots resnet20.sync_e1's warm `setup_s` read 74-75 s against the
    # parent's 79-80 (PERF.md, PR 24 and PR 26). A change of this frame's
    # size is not wrong, but it re-rolls that and owes both cells a chip run.
    _0 = _1 = _2 = _3 = _4 = _5 = _6 = _7 = _8 = _9 = _10 = _11 = _12 = _13 = \
        _14 = None
    cell = load_cell(bench_path, workload)
    for key, val in cell["config"].get("env", {}).items():
        os.environ[key] = str(val)  # backend pins, before hefl_tpu is imported
    import jax
    import jax.extend
    import numpy as np

    chips = int(cell["cell"]["chips"])
    if require_tpu and (jax.default_backend() != "tpu"
                        or jax.device_count() < chips):
        raise SystemExit(
            f"benchmarks/run.py: {workload} needs {chips} TPU chip(s); found "
            f"{jax.device_count()} x {jax.default_backend()}")
    from hefl_tpu import experiment
    from hefl_tpu.obs import metrics as obs_metrics
    from hefl_tpu.utils.device import setup_compile_cache

    cache_dir = setup_compile_cache() if require_tpu else None
    if cache_dir:  # also keep the programs that compile in under a second
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    obs_metrics.install_jax_listeners()
    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="hefl_bench_")
    events_path = os.path.join(workdir, "events.jsonl")
    os.environ["HEFL_EVENTS"] = "1"
    pauses, began = [], [0.0]  # every collection of Python's: (end, seconds)

    def gc_watch(phase, info):
        if phase == "start":
            began[0] = time.time()
        else:
            pauses.append((time.time(), time.time() - began[0]))

    gc.callbacks.append(gc_watch)
    try:
        # The data of one seed is made once for the process: run_experiment
        # makes it anew in each call, and the arrays are the same.
        make = experiment.make_dataset
        make_once = experiment.make_dataset = functools.lru_cache(None)(make)
        cfg = build_config(cell, seed, events_path=events_path)
        data = make_once(cfg.dataset, seed=cfg.seed, n_train=cfg.n_train,
                         n_test=cfg.n_test)
        digest = data_digest((*data[0], *data[1]))

        warm = experiment.run_experiment(
            dataclasses.replace(cfg, rounds=2), verbose=False)
        ends = [e["ts"] for e in last_call_events(events_path)
                if e["event"] == "round_end"]
        round_est = max(ends[-1] - ends[-2], 1e-3)
        n_window = max(MIN_WINDOW_ROUNDS, int(seconds / round_est))
        if trace:
            n_window = max(n_window, TRACED_ROUNDS + 1)
        run_cfg = dataclasses.replace(cfg, rounds=n_window + 1)
        trace_dir = os.path.join(workdir, "trace")
        if trace:
            with RoundTracer(trace_dir, TRACED_ROUNDS):
                out = experiment.run_experiment(run_cfg, verbose=False)
        else:
            out = experiment.run_experiment(run_cfg, verbose=False)
        win = window_record(last_call_events(events_path), out["history"])
        devices = jax.devices()[:chips]
        memory = red.memory_peak(
            [d.memory_stats() or {} for d in devices],
            jax.extend.backend.get_backend().live_executables())
        history = out["history"]

        check = _module_at(cell["check"])
        work = check.round_work(cell, cfg, data)
        samples_round = work["samples_per_round"]
        record = {
            "rounds": win["rounds"], "window_s": win["window_s"],
            "samples_per_round": samples_round, "chips": chips,
            "memory_peak_bytes": memory["peak_bytes"],
            "device_kind": devices[0].device_kind,
            "train_flops_per_round": work["train_flops_per_round"],
        }
        walls = [r["wall_s"] for r in win["rounds"]]
        values = {  # all the time of the window over all of its rounds
            "round_s": win["window_s"] / len(walls),
            "samples_per_s": red.samples_per_s(
                len(walls), samples_round, win["window_s"], chips),
            "setup_s": win["t_open"] - _T0,
        }
        device = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": memory["peak_bytes"],
        }
        breakdown = None
        if trace:
            xplane = _xplane_under(trace_dir)
            if keep_trace:
                os.makedirs(os.path.dirname(keep_trace) or ".", exist_ok=True)
                shutil.copy(xplane, keep_trace)
            tr = red.reduce_trace(red.read_xplane(xplane), TRACED_ROUNDS)
            record["peaks"] = red.load_peaks(devices[0].device_kind)
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            breakdown = tr["breakdown"]
            values = {}
            for m in cell["per_layer"]:
                got = cell["module"]("layer_metrics", m["name"]).read(record, tr)
                if got is not None:
                    values[m["name"]] = got
        units = {m["name"]: m["unit"]
                 for m in cell["per_layer" if trace else "end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items() if k in units}

        # ---- the checks, outside the window --------------------------------
        numbers = check.numbers(cell, cfg, data)
        overflow = sum(int(np.sum(rec.get("encode_overflow", 0)))
                       for rec in history) + numbers.pop("encode_overflow", 0)
        numbers.update(encode_overflow=overflow,
                       executables_in_window=win["executables_in_window"],
                       failed_rounds=win["failed"])
        limits = dict(cell["config"]["limits"],
                      encode_overflow={"max": 0},
                      executables_in_window={"max": 0},
                      failed_rounds={"max": 0})
        for name in [k for k, v in limits.items() if v is None]:
            # a null limit in the configuration's file: read and printed, not
            # judged, where no limit separates sound runs from the fault
            del limits[name]
            say(not_judged=name, value=numbers.pop(name))
        rows = judge(numbers, limits)
        finite = all(math.isfinite(v["value"]) and v["value"] > 0
                     for v in metrics.values()) and all(
            math.isfinite(rec["accuracy"]) for rec in history)
        for row in rows:
            say(**row)
        say(
            workload=workload, seed=seed, device=device, chips=chips,
            memory=memory,
            selections={k: out[k] for k in ("he_backend", "augment_backend",
                                            "client_fusion")},
            rounds_in_window=len(walls), warmup_rounds=len(warm["history"]),
            window_s=win["window_s"], samples_per_round=samples_round,
            round_walls_s=walls,
            round_phases_s=[[r["phases"].get(k) for k in PHASES]
                            for r in win["rounds"]],
            gc_pauses_in_window_s=[d for t, d in pauses if win["t_open"] < t
                                   <= win["t_open"] + win["window_s"]
                                   and d > 0.01],
            compile={"warmup_call": warm["obs"]["metrics"],
                     "measured_call_executables": out["obs"]["metrics"].get(
                         "jax.new_executables", 0),
                     "in_window": win["executables_in_window"]},
            compile_cache=cache_dir, data_sha256=digest,
            accuracy_last_round=history[-1]["accuracy"],
            metrics_finite=finite, total_s=time.time() - _T0,
        )
        result = {
            "correct": bool(finite and all(r["ok"] for r in rows)),
            "attempted": len(walls), "failed": win["failed"],
            "metrics": metrics, "device": device,
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        # last in the line: each number compared, beside its limit
        result["checks"] = {r["check"]: {k: v for k, v in r.items() if k != "check"}
                            for r in rows}
        return result
    finally:
        gc.callbacks.remove(gc_watch)
        experiment.make_dataset = make
        if own_dir:
            shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--keep-trace", default=None,
                    help="copy the run's .xplane.pb here (to read by hand)")
    args = ap.parse_args(argv)
    result = run_cell(args.benchmark, args.workload, args.seed, args.seconds,
                      bool(args.trace), keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    for name, row in result["checks"].items():  # and as standard error's last lines
        print(json.dumps({"check": name, **row}), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
