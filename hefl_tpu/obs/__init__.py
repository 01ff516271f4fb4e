"""Trace-native observability: phase scopes, run events, metrics, traces.

Three legs, one subsystem (ISSUE 5):

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
  * `obs.spans` / `obs.trend` (ISSUE 20) — per-round lifecycle span
    trees on the engine's virtual clock (arrival/fold/ship/commit/
    recovery, exported as Chrome trace-viewer JSON `obs.trace` can load)
    and the bench-history trend gate (`python -m hefl_tpu.obs.trend`)
    that turns the committed BENCH_*.json trajectory into TREND.md and a
    regression check.
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
