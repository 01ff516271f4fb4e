"""Trace-native observability: phase scopes, run events, metrics, traces.

Four legs, one subsystem (ISSUE 5):

  * `obs.scopes` — the canonical `jax.named_scope` names the round
    program's phases are annotated with (augment / sgd_core / val /
    sanitize / encrypt / psum_aggregate / aggregate / decrypt / evaluate).
    They survive jit into HLO metadata and profiler traces.
  * `obs.trace` — parses a `jax.profiler.start_trace` trace-viewer dump and
    joins its device-op events back to the scopes through the compiled
    program's own HLO, yielding per-phase device time from ONE program —
    the ground truth that replaces cross-program ablation subtraction.
  * `obs.events` / `obs.metrics` — a JSONL run-event log (events.jsonl
    next to checkpoints; HEFL_EVENTS=0 opt-out) and a process-wide
    counter/gauge registry (exclusions by cause, retries, resumes,
    autoselect outcomes, XLA new-executable count, device-memory
    high-water) embedded in every bench/profile/chaos artifact.
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
  * `obs.trend` (ISSUE 20) — the bench-history trend gate
    (`python -m hefl_tpu.obs.trend`) that turns the committed BENCH_*.json
    trajectory into TREND.md and a regression check.
"""

from hefl_tpu.obs import events, metrics, scopes, spans, trace, trend
from hefl_tpu.obs.events import EventLog
from hefl_tpu.obs.spans import SpanTracer
from hefl_tpu.obs.trace import TraceParseError, trace_attribution

__all__ = [
    "events",
    "metrics",
    "scopes",
    "spans",
    "trace",
    "trend",
    "EventLog",
    "SpanTracer",
    "TraceParseError",
    "trace_attribution",
]
