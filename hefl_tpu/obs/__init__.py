"""Trace-native observability: phase scopes, run events, metrics, traces.

Four legs, one subsystem (ISSUE 5):

  * `obs.scopes` — the canonical `jax.named_scope` names the round
    program's phases are annotated with (augment / sgd_core / val /
    sanitize / encrypt / psum_aggregate / aggregate / decrypt / evaluate,
    and the models' own inside them). They survive jit into HLO metadata
    and profiler traces.
  * `obs.trace` — reads the `.xplane.pb` that `jax.profiler.start_trace`
    writes (its wire format: the device ops' event metadata carries the HLO
    `op_name` as the stat `tf_op`, which `ProfileData` does not show) and
    yields a TPU run's device seconds by scope, by kernel family, forward
    against backward and by program, as self time, from the programs that
    ran: no HLO text, no second compile, no cache bypass.
  * `obs.events` / `obs.metrics` — a JSONL run-event log (events.jsonl
    next to checkpoints; HEFL_EVENTS=0 opt-out) and a process-wide
    counter/gauge registry (exclusions by cause, retries, resumes, XLA
    new-executable count, device-memory high-water) embedded in every
    run's result record.
  * `obs.spans` — the ONE recorder of host spans (ISSUE 24). Every
    host-side timing goes through `obs.spans.span(name)` (or `start(name)`
    / `stop(name)` where a `with` would indent too much): PhaseTimer's
    phases (`hefl.phase.<phase>`), the steps inside them
    (`hefl.phase.<phase>.<step>`: dispatch / prefetch / device_wait;
    decrypt's kernel / decode / unpack / wait), `run_experiment`'s start
    (`hefl.setup` and its `hefl.setup.<step>` children), one `hefl.round`
    a round, the straggler and quorum waits, and the engine's journal /
    fsync / transcipher / replay legs. Always on: a span is a row (id,
    parent, name, call, round, t0_ns, t1_ns) of a process-wide, bounded,
    in-memory store (`obs.spans.recorded()`), written nowhere at record
    time, plus a `jax.profiler.TraceAnnotation` over the same interval on
    the same unix-epoch clock, so a profiler trace carries it beside the
    device ops. Export: `ExperimentConfig.span_trace_path` /
    `--span-trace PATH` writes a call's spans as Chrome trace-viewer JSON.
    The module also holds the streaming engine's per-round lifecycle span
    TREE on its virtual clock (`SpanTracer`, ISSUE 20:
    arrival/fold/ship/commit/recovery, in the same export).
"""

from hefl_tpu.obs import events, metrics, scopes, spans, trace
from hefl_tpu.obs.events import EventLog
from hefl_tpu.obs.spans import SpanTracer
from hefl_tpu.obs.trace import TraceParseError, trace_attribution

__all__ = [
    "events",
    "metrics",
    "scopes",
    "spans",
    "trace",
    "EventLog",
    "SpanTracer",
    "TraceParseError",
    "trace_attribution",
]
