"""One run of one benchmark cell: the encrypted federated round on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration file
(`<path>/configs/<name>.json`, named by the entry's `file`) and a traffic
mix (`<path>/traffic/<mix>.json`), merged field by field into the program's
`ExperimentConfig`. Per-layer metrics are read by `<path>/layer_metrics/
<metric>.py`, the plain references live in `<path>/reference/<model>.py`;
`<path>` is any directory of BENCHMARK.json's `paths`. Adding a cell, a mix,
a configuration or a per-layer metric adds files and entries and edits none.

The run: set-up (imports, data from the seed, keys, compile or cache load,
a two-round warm-up call of `run_experiment`), then a measured call whose
first round is a lead-in and whose remaining whole rounds are the window,
then the checks that decide `correct`, outside the window. The last line of
standard output is the result object; earlier lines say what was run.
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
CHECK_STEPS = 3   # optimizer steps the plain reference follows


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

    return {
        "cell": cell, "paths": paths,
        "config": _read_json(os.path.join(ROOT, conf["file"])),
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
# the checks that decide `correct`
# --------------------------------------------------------------------------


def _norms(tree):
    import jax
    import numpy as np

    return [float(np.linalg.norm(np.asarray(leaf, np.float64)))
            for leaf in jax.tree_util.tree_leaves(tree)]


def norm_gap(got, want) -> float:
    """Worst leaf: |norm(got) - norm(want)| against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    ref = _norms(want)
    floor = sorted(ref)[len(ref) // 2]
    return max(abs(g - r) / max(r, floor) for g, r in zip(_norms(got), ref))


def whole_norm_gap(got, want) -> float:
    """|norm(got) - norm(want)| / norm(want) over all leaves as one vector."""
    whole = lambda t: math.sqrt(sum(n * n for n in _norms(t)))  # noqa: E731
    return abs(whole(got) - whole(want)) / whole(want)


@functools.lru_cache(maxsize=None)
def _grad_fns(module, ref, quant):
    """(reference, system) jitted value-and-grad of the loss with the logits,
    over (params, batch, onehot): compiled once for a cell, whatever the
    seed, so the persistent cache serves every later run."""
    import jax

    from hefl_tpu.fl.loss import loss_fn

    def system(p, x, onehot):  # at the program's own precision
        if quant is not None:
            return ref.loss(p, x, onehot, quant)
        return (loss_fn(module, p, x, onehot)[0],
                module.apply({"params": p}, x))

    def reference(p, x, onehot):
        with jax.default_matmul_precision("highest"):
            return ref.loss(p, x, onehot)

    return (jax.jit(jax.value_and_grad(reference, has_aux=True)),
            jax.jit(jax.value_and_grad(system, has_aux=True)))


def model_numbers(module, ref, x, onehot, seed: int, quant=None) -> dict:
    """The system's own loss and gradient (`fl.loss.loss_fn`, what the SGD
    step differentiates) against the plain float32 reference, on one timed
    batch of the seed's images and the reference's seeded weights. The
    logits' widest error is given in units of the widest error that the
    reference makes when it is computed in float8, on the same weights and
    batch: how far a precision moves the logits swings sixfold with the
    seed, the ratio of two precisions far less. The loss is held to the
    cross-entropy of the system's own logits, the gradient to the
    reference's, leaf by leaf. With `quant` the reference computed in that
    precision stands in the system's place: the control."""
    import numpy as np

    params = ref.init(seed, x.shape[1:], onehot.shape[-1])
    ref_fn, sys_fn = _grad_fns(module, ref, quant)
    (_, z_ref), g_ref = ref_fn(params, x, onehot)
    (l_sys, z_sys), g_sys = sys_fn(params, x, onehot)
    (_, z_fp8), _ = _grad_fns(module, ref, fp8_quant)[1](params, x, onehot)
    z_ref = np.asarray(z_ref, np.float64)
    err = lambda z: float(np.max(np.abs(  # noqa: E731
        np.asarray(z, np.float64) - z_ref)))
    # The loss arithmetic apart from the forward's precision: the system's
    # loss against the cross-entropy of its own logits in float64.
    z = np.asarray(z_sys, np.float64)
    z = z - z.max(-1, keepdims=True)
    ce = float(np.mean(np.log(np.exp(z).sum(-1)) - (z * onehot).sum(-1)))
    return {
        "loss_gap": abs(float(l_sys) - ce) / ce,
        "logit_err_vs_fp8": err(z_sys) / err(z_fp8),
        "grad_norm_gap": norm_gap(g_sys, g_ref),
        "logit_err_max": err(z_sys),
    }


def fp8_quant(a):
    """The control's precision: float8 (e4m3) values forward, the identity
    backward, where the configuration states bfloat16."""
    import jax
    import jax.numpy as jnp

    return a + jax.lax.stop_gradient(
        a.astype(jnp.float8_e4m3fn).astype(jnp.float32) - a)


@functools.lru_cache(maxsize=None)
def _ref_loss(ref):
    import jax

    def loss(p, x, onehot):
        with jax.default_matmul_precision("highest"):
            return ref.loss(p, x, onehot)[0]

    return jax.jit(loss)


def train_numbers(cfg, module, ref, adam, x, y, steps: int = CHECK_STEPS) -> dict:
    """One round through the timed entry points (`secure_fedavg_round`, then
    `decrypt_average`) with the cell's clients, batch, client lowering and HE
    parameters, from the reference's seeded weights. Its local scan is cut to
    `steps` optimizer steps of one epoch on the head of each client's shard,
    and its random warp is off: the plain reference cannot follow the
    program's augmentation. Compared: the in-program plain mean against a
    plain float32 Adam run over the same batches (the norm of the
    parameters' change, as one vector and by the worst leaf), each client's
    validation loss at its trained weights against the reference's, and the
    decrypted average against the plain mean."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hefl_tpu.ckks.keys import keygen
    from hefl_tpu.ckks.packing import PackSpec
    from hefl_tpu.data import iid_contiguous, stack_federated
    from hefl_tpu.fl import decrypt_average, secure_fedavg_round
    from hefl_tpu.fl.client import epoch_index_streams, train_batch_geometry
    from hefl_tpu.fl.fedavg import pad_federated
    from hefl_tpu.parallel import client_mesh_size, client_sharding, make_mesh

    if (cfg.partition != "iid" or cfg.mesh_ct > 1 or cfg.stream is not None
            or (cfg.packing is not None and cfg.packing.enabled)):
        raise NotImplementedError(
            "the training check follows a synchronous, unpacked, IID round on "
            "a 1-D mesh; the benchmark PR that adds another kind of cell "
            "proves its check on the chip with it")
    n_cl, classes = cfg.num_clients, cfg.train.num_classes
    tc = dataclasses.replace(cfg.train, epochs=1, augment=False)
    m = next(k for k in range(steps * tc.batch_size, len(y) // n_cl + 1)
             if train_batch_geometry(tc, k)[2] == steps)
    n_tr, grp, _ = train_batch_geometry(tc, m)
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), n_cl))
    xs, ys = np.asarray(xs[:, :m]), np.asarray(ys[:, :m])
    shape = tuple(int(d) for d in xs.shape[2:])
    params0 = ref.init(cfg.seed, shape, classes)

    # ---- the system: one round of the timed entry, then the owner's decrypt
    mesh = make_mesh(n_cl)
    xs_p, ys_p, num_real = pad_federated(xs, ys, client_mesh_size(mesh))
    place = client_sharding(mesh)
    ctx = cfg.he.build()
    _, k_he = jax.random.split(jax.random.key(cfg.seed))
    sk, pk = keygen(ctx, k_he)
    key = jax.random.fold_in(jax.random.key(cfg.seed), 1000)
    gp = jax.tree_util.tree_map(jnp.asarray, params0)
    outs = secure_fedavg_round(
        module, tc, mesh, ctx, pk, gp, jax.device_put(xs_p, place),
        jax.device_put(ys_p, place), key, with_plain_reference=True,
        num_real_clients=num_real)
    ct, mets, overflow, plain = outs[0], outs[1], outs[2], outs[-1]
    avg = decrypt_average(ctx, sk, ct, n_cl, PackSpec.for_params(gp, ctx.n),
                          meta=outs[3] if len(outs) == 5 else None,
                          base_params=gp)
    val_sys = np.asarray(mets, np.float64)[:n_cl, 0, 0]

    # ---- the reference: the same batches, client after client. The batches
    # are the program's own shuffle of the round key (secure_fedavg_round
    # splits it into a training and an encryption key, then per client).
    train_keys = jax.random.split(jax.random.split(key)[0], n_cl)
    perms = np.asarray(epoch_index_streams(tc, train_keys, m)[0])
    eye = np.eye(classes, dtype=np.float32)
    scaled = lambda a: np.asarray(a, np.float32) / 255.0  # noqa: E731
    ref_vg = _grad_fns(module, ref, None)[0]
    total, short, val_gaps, untrained, first_losses = None, None, [], [], []
    as_f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: np.asarray(a, np.float32), t)
    add = lambda acc, t: t if acc is None else jax.tree_util.tree_map(  # noqa: E731
        np.add, acc, t)
    for c in range(n_cl):
        if len(set(perms[c].ravel().tolist())) != steps * grp:
            raise RuntimeError("the check's batches repeat a row")
        x_tr, y_tr = xs[c, m - n_tr:], ys[c, m - n_tr:]
        trail, losses = adam.steps(
            ref_vg, params0, [(scaled(x_tr[i]), eye[y_tr[i]]) for i in perms[c]],
            tc.lr, tc.lr_decay, tc.warmup_steps)
        total, short = add(total, trail[-1]), add(short, trail[-2])
        first_losses.append(losses[0])
        val = scaled(xs[c, :m - n_tr]), eye[ys[c, :m - n_tr]]
        want = float(_ref_loss(ref)(as_f32(trail[-1]), *val))
        val_gaps.append(abs(val_sys[c] - want) / want)
        untrained.append(abs(float(_ref_loss(ref)(as_f32(params0), *val))
                             - want) / want)
    mean = lambda t: jax.tree_util.tree_map(lambda a: a / n_cl, t)  # noqa: E731
    moved = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - b, t, params0)
    leaves = lambda t: [np.asarray(v, np.float64)  # noqa: E731
                        for v in jax.tree_util.tree_leaves(t)]
    err = max(float(np.max(np.abs(a - b)))
              for a, b in zip(leaves(avg), leaves(plain)))
    say(check_round={"clients": n_cl, "images_a_client": m, "steps": steps,
                     "batch": grp, "validation_rows": m - n_tr},
        reference_first_step_loss=first_losses)
    d_sys, d_ref = moved(plain), moved(mean(total))
    return {
        "step_norm_gap": whole_norm_gap(d_sys, d_ref),
        "leaf_step_gap": norm_gap(d_sys, d_ref),
        "val_loss_gap": float(max(val_gaps)),
        "he_avg_err": err if math.isfinite(err) else float("inf"),
        "check_round_overflow": int(np.sum(np.asarray(overflow))),
        # for the record, what two faults read on the reference's side: an
        # optimizer step that returns its state unchanged (`step_norm_gap`),
        # a validation loss taken at the round's input weights
        "skipped_step_reads": whole_norm_gap(moved(mean(short)), d_ref),
        "untrained_val_reads": float(max(untrained)),
    }


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
    from hefl_tpu.fl.client import train_batch_geometry
    from hefl_tpu.models import create_model
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
        (x, y), (xt, yt), _ = make_once(cfg.dataset, seed=cfg.seed,
                                        n_train=cfg.n_train, n_test=cfg.n_test)
        digest = data_digest((x, y, xt, yt))

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

        _, grp, steps = train_batch_geometry(
            cfg.train, len(y) // cfg.num_clients)
        samples_round = cfg.num_clients * cfg.train.epochs * steps * grp
        ref = _module_at(_find(cell["paths"], "reference",
                               cell["config"]["reference"] + ".py"))
        shape = tuple(int(d) for d in x.shape[1:])
        record = {
            "rounds": win["rounds"], "window_s": win["window_s"],
            "samples_per_round": samples_round, "chips": chips,
            "memory_peak_bytes": memory["peak_bytes"],
            "device_kind": devices[0].device_kind,
            "train_flops_per_round": 3 * samples_round * ref.forward_flops(
                shape, cfg.train.num_classes),
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
                reader = _module_at(_find(cell["paths"], "layer_metrics",
                                          m["name"] + ".py"))
                got = reader.read(record, tr)
                if got is not None:
                    values[m["name"]] = got
        units = {m["name"]: m["unit"]
                 for m in cell["per_layer" if trace else "end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items() if k in units}

        # ---- the checks, outside the window --------------------------------
        module, _ = create_model(cfg.model, num_classes=cfg.train.num_classes,
                                 input_shape=shape)
        bs = cfg.train.batch_size
        xb = np.asarray(x[:bs], np.float32) / 255.0
        onehot = np.eye(cfg.train.num_classes, dtype=np.float32)[y[:bs]]
        numbers = model_numbers(module, ref, xb, onehot, cfg.seed)
        say(logit_err_max=numbers.pop("logit_err_max"))  # for the record
        adam = _module_at(_find(cell["paths"], "reference", "adam.py"))
        he = train_numbers(cfg, module, ref, adam, x, y)
        overflow = sum(int(np.sum(rec.get("encode_overflow", 0)))
                       for rec in history) + he.pop("check_round_overflow")
        say(skipped_step_would_read=he.pop("skipped_step_reads"),
            untrained_val_would_read=he.pop("untrained_val_reads"))
        numbers.update(he, encode_overflow=overflow,
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
