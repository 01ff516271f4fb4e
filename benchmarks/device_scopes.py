"""What the device-scope readers of `layer_metrics/` share: the program's
own reduction of the traced rounds' `.xplane.pb` to device seconds by scope
and by kernel (`hefl_tpu.obs.trace.trace_attribution`: the ops' `tf_op`,
`hlo_category` and `display_name`, read from the events' metadata, which
`reduce.read_xplane`'s `ProfileData` does not show), made once a run.

- Seconds are SELF seconds of device ops (an op's duration less the ops
  nested in it), a mean over the device planes, per traced round
  (`/ trace["rounds_traced"]`).
- `under(trace, *scopes)`: ops that have one of the scopes anywhere in their
  chain of `hefl.*` scopes, each op once; `within=` keeps of them the ops
  whose chain holds that scope too, `outside=` those whose chain holds none
  of these. The model's layers are read `within=STEP`: a model's scopes open
  in validation and in evaluation as well (`val_dev_s`, `evaluate_dev_s`),
  and read at any depth they would not be parts of `sgd_dev_s`.
  `family(trace, prefix)`: custom calls (the Pallas kernels) whose display
  name less its number starts so, wherever they ran.
- The first reader to ask prints the line {"device_scopes": {...}}: the whole
  record (scope chains with their backward seconds, flops and bytes by the
  compiler's count, families, programs), which PERF.md section 5 is
  written from.

`run.py` hands a reader `(record, trace)` and no path: the trace is looked
for under `trace/` beside the program's event log, where `run_cell` puts
both (it removes them after the readers ran).

A program without the reader (a parent commit) or a trace without a TPU
plane (the CPU's) has nothing to read: every function returns None and the
metric is left out of the line, as `span_metrics.rows()` does. A file that
cannot be parsed raises, and so does a TPU plane whose ops carry no `tf_op`
(`NoScopeMetadata`): with the reader there, that is a broken yardstick (a
libtpu that dropped the stat), not a run with nothing to say.
"""

from __future__ import annotations

import functools
import json
import os

STEP = "hefl.sgd_core"  # local SGD: what the model-layer metrics are parts of
ATTENTION = ("hefl.mla", "hefl.gqa", "hefl.dsa.attend", "hefl.swa.attend")
MOE = ("hefl.moe.route", "hefl.moe.experts", "hefl.moe_gmm")


def _trace_dir() -> str | None:
    try:
        from hefl_tpu.obs import events
    except ImportError:
        return None
    log = events.current_path()
    path = os.path.join(os.path.dirname(log), "trace") if log else None
    return path if path and os.path.isdir(path) else None


@functools.lru_cache(maxsize=2)
def _attribution(logdir: str):
    from hefl_tpu.obs import trace as obs_trace

    if not hasattr(obs_trace, "read_xplane"):
        return None  # the program reads no xplane yet
    try:
        rec = obs_trace.trace_attribution(logdir)
    except obs_trace.NoDevicePlane:
        return None
    rec["trace_bytes"] = os.path.getsize(rec.pop("trace_file"))
    print(json.dumps({"device_scopes": rec}), flush=True)
    return rec


def record(trace):
    """The program's record of this run's traced rounds, or None."""
    if not trace:
        return None
    logdir = _trace_dir()
    return _attribution(logdir) if logdir else None


def _per_round(trace, seconds: float):
    return seconds / trace["rounds_traced"] if seconds > 0 else None


def under(trace, *scopes, within: str | None = None, outside=()):
    """Seconds per traced round of the ops under any of `scopes` (where
    given: under `within` as well, and under none of `outside`)."""
    rec = record(trace)
    if rec is None:
        return None
    total = 0.0
    for chain, row in rec["paths"].items():
        held = set(chain.split("/"))
        if (held.intersection(scopes) and within in (None, *held)
                and held.isdisjoint(outside)):
            total += row["device_seconds"]
    return _per_round(trace, total)


def family(trace, prefix: str):
    """Seconds per traced round of the custom calls of families `prefix*`."""
    rec = record(trace)
    if rec is None:
        return None
    return _per_round(trace, sum(
        row["device_seconds"] for name, row in rec["families"].items()
        if name.startswith(prefix)))


def unscoped_share(trace):
    """Percent of the device's busy seconds under no `hefl.*` scope."""
    rec = record(trace)
    if rec is None or rec["device_total_s"] <= 0:
        return None
    share = 100.0 * rec["unattributed_s"] / rec["device_total_s"]
    return share if share > 0 else None
