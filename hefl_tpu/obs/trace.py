"""Profiler-trace attribution: per-phase device time from ONE program.

profile_round.py's phase table was first computed by SUBTRACTING two
separately-compiled program variants — the method the ROADMAP calls out as
unreliable (XLA fuses each variant differently; raw deltas go negative on
fast rounds). This module replaces it with ground truth from a single
traced execution:

  1. The round program's phases are annotated with `jax.named_scope`
     (`obs.scopes`), which rides into every HLO instruction's
     `metadata={op_name="jit(f)/.../hefl.encrypt/..."}`.
  2. `jax.profiler.start_trace` (the `--profile` flag the experiment CLI
     and profile_round.py already expose) writes a trace-viewer
     `*.trace.json.gz` whose device-op events carry the HLO instruction
     name (`args.hlo_op`) and module (`args.hlo_module`) — but NOT the
     op_name metadata.
  3. `hlo_scope_map` recovers instruction -> scope from the compiled
     program's own HLO text; `trace_attribution` joins the two and sums
     per-phase device time as a UNION of event intervals per phase.

Why interval unions, not duration sums: the CPU backend logs one event per
thunk per worker thread (an intra-op-partitioned kernel appears on every
thread it ran on), and container ops (`while`, `conditional`, `call`)
each log an event SPANNING their children. Summing durations would double
count all of that; a per-phase interval union counts each wall-clock
nanosecond of a phase once. Container events that carry no scope are not
a bucket of their own — only the time no attributed event covers is
reported, as `unattributed`.

Failure policy: a truncated gzip, malformed JSON, an empty event list, or
a trace with no device-op events raises `TraceParseError`. Attribution
that silently parses garbage into an all-zeros table would poison the one
artifact this subsystem exists to make trustworthy.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
from typing import Any, Iterable, Mapping

from hefl_tpu.obs import scopes


class TraceParseError(RuntimeError):
    """The trace (or the HLO needed to attribute it) is unusable."""


@contextlib.contextmanager
def metadata_preserving_compile():
    """Disable the persistent XLA compilation cache for the duration.

    An executable DESERIALIZED from the persistent cache answers
    `as_text()` without per-instruction `op_name` metadata — exactly the
    join key the attribution needs — so the HLO texts handed to
    `trace_attribution` must come from a real compile. Instruction names
    are deterministic for identical input HLO, so a fresh compile's text
    still matches the trace events of a cache-loaded executable that
    actually ran. Costs one re-compile per program; only attribution
    drivers pay it, and only in --profile mode.
    """
    import jax

    prev = getattr(jax.config, "jax_compilation_cache_dir", None)
    if not prev:
        yield
        return
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# --------------------------------------------------------------------------
# HLO side: instruction name -> phase scope.
# --------------------------------------------------------------------------

_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.MULTILINE)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s*[^\n]*?"
    r'metadata=\{[^}]*?op_name="([^"]*)"',
    re.MULTILINE,
)
_CALL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s*[^\n]*?\bcall\("
    r"[^\n]*?to_apply=%?([A-Za-z0-9_.\-]+)",
    re.MULTILINE,
)


def hlo_module_name(hlo_text: str) -> str:
    m = _MODULE_RE.search(hlo_text)
    if not m:
        raise TraceParseError("HLO text has no 'HloModule <name>' header")
    return m.group(1)


def hlo_scope_map(hlo_text: str) -> dict[str, str]:
    """Instruction name -> deepest hefl.* scope, from compiled-HLO metadata.

    Covers the two spellings the CPU/TPU runtimes emit trace events under:
    the instruction's own name, and (for `call` wrappers the CPU backend
    creates around parallelized kernels, which carry no metadata of their
    own) the name resolved through `to_apply=%parallel_<inner>` to the
    inner instruction's scope.
    """
    by_name: dict[str, str] = {}
    for name, op_name in _INSTR_RE.findall(hlo_text):
        sc = scopes.scope_of(op_name)
        if sc is not None:
            by_name[name] = sc
    # call.N -> %parallel_X wraps instruction X (or X.clone): inherit.
    for name, target in _CALL_RE.findall(hlo_text):
        if name in by_name:
            continue
        inner = target[len("parallel_"):] if target.startswith("parallel_") else target
        for cand in (inner, inner + ".clone"):
            if cand in by_name:
                by_name[name] = by_name[cand]
                break
    return by_name


# --------------------------------------------------------------------------
# Trace side: load + bucket.
# --------------------------------------------------------------------------


def find_trace_file(logdir: str) -> str:
    """The newest trace-viewer JSON under a `jax.profiler.start_trace`
    logdir (layout: <logdir>/plugins/profile/<run>/<host>.trace.json.gz)."""
    hits = sorted(
        glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime,
    )
    if not hits:
        raise TraceParseError(
            f"no *.trace.json.gz under {logdir!r} — did the profiler run?"
        )
    return hits[-1]


def load_trace_events(path: str) -> list[dict]:
    """Parse one trace-viewer JSON (.trace.json.gz or plain .json): -> the
    traceEvents list. Truncated/corrupt input fails loudly."""
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            data = json.loads(f.read().decode("utf-8"))
    except (OSError, EOFError, ValueError, UnicodeDecodeError) as e:
        raise TraceParseError(f"unreadable trace {path!r}: {e}") from e
    events = data.get("traceEvents") if isinstance(data, dict) else None
    if not isinstance(events, list) or not events:
        raise TraceParseError(f"trace {path!r} carries no traceEvents")
    return events


def _merged_length_us(intervals: list[tuple[float, float]]) -> float:
    """Total covered length of a set of [start, end) intervals (overlaps —
    same op on several worker threads, containers over children — counted
    once)."""
    total = 0.0
    end = -float("inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _subtract_covered_us(
    intervals: list[tuple[float, float]], cover: list[tuple[float, float]]
) -> float:
    """Length of `intervals` NOT covered by `cover`: |A ∪ B| − |B|."""
    if not intervals:
        return 0.0
    return max(
        0.0,
        _merged_length_us(intervals + cover) - _merged_length_us(cover),
    )


def trace_attribution(
    trace: str | list[dict],
    hlo_texts: Iterable[str],
    phases: tuple[str, ...] = scopes.PHASES,
) -> dict[str, Any]:
    """Per-phase device time of a traced run: THE trace_attribution record.

    trace: a profiler logdir, a *.trace.json(.gz) path, or a pre-loaded
    traceEvents list. hlo_texts: the compiled HLO of every program executed
    in the traced region (`jitted.lower(*args).compile().as_text()`) — the
    join key between trace events (hlo_module/hlo_op) and scope names.

    -> {
      "rows": {phase: {"device_seconds", "op_events"}},   # union per phase
      "unattributed_s":   device-busy time no scoped op covers,
      "device_total_s":   union of ALL device-op events,
      "modules": {module: device_seconds},                # per program
      "host_rows": {span: {"seconds", "spans"}},          # hefl.* host
                          TraceAnnotations (driver-side work that owns
                          wall-clock but runs no device ops — straggler
                          waits, PhaseTimer brackets); NOT part of the
                          device rows or the wall-agreement gate,
      "op_events": total device-op events considered,
      "source": "trace",
    }

    device_total_s ~ the traced region's device-busy wall clock; rows sum
    to device_total_s - (cross-phase container overlap), so
    sum(rows) + unattributed_s is the number to check against the traced
    wall clock (run_perf_smoke.sh gates it at 15% on CPU).
    """
    if isinstance(trace, str):
        path = trace if os.path.isfile(trace) else find_trace_file(trace)
        events = load_trace_events(path)
        trace_file: str | None = path
    else:
        events, trace_file = trace, None

    scope_maps = {}
    for text in hlo_texts:
        scope_maps[hlo_module_name(text)] = hlo_scope_map(text)
    if not scope_maps:
        raise TraceParseError("no HLO texts supplied — nothing to attribute to")

    per_phase: dict[str, list[tuple[float, float]]] = {}
    per_phase_n: dict[str, int] = {}
    per_module: dict[str, list[tuple[float, float]]] = {}
    host_iv: dict[str, list[tuple[float, float]]] = {}
    all_iv: list[tuple[float, float]] = []
    attributed_iv: list[tuple[float, float]] = []
    n_ops = 0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        module = args.get("hlo_module")
        if module not in scope_maps:
            # Host-side hefl.* TraceAnnotations (e.g. hefl.straggler_wait,
            # the PhaseTimer hefl.phase.* brackets) carry no hlo_module:
            # bucket them as first-class host rows so driver-side waits
            # stop reading as an unexplained wall-vs-device gap.
            name = str(ev.get("name") or "")
            if name.startswith(scopes.PREFIX):
                ts, dur = float(ev.get("ts", 0.0)), float(ev.get("dur", 0.0))
                host_iv.setdefault(name, []).append((ts, ts + dur))
            continue
        op = args.get("hlo_op") or ev.get("name") or ""
        ts, dur = float(ev.get("ts", 0.0)), float(ev.get("dur", 0.0))
        iv = (ts, ts + dur)
        n_ops += 1
        all_iv.append(iv)
        per_module.setdefault(module, []).append(iv)
        sc = scope_maps[module].get(op)
        if sc is None and op.endswith(".clone"):
            sc = scope_maps[module].get(op[: -len(".clone")])
        if sc is None:
            continue
        per_phase.setdefault(sc, []).append(iv)
        per_phase_n[sc] = per_phase_n.get(sc, 0) + 1
        attributed_iv.append(iv)

    if n_ops == 0:
        raise TraceParseError(
            "trace has no device-op events for the supplied HLO modules "
            f"({sorted(scope_maps)}) — wrong trace dir, or the profiler "
            "captured no device activity"
        )
    # The trace-viewer JSON converter caps at 1e6 events and silently drops
    # the rest — an attribution from a truncated trace undercounts whatever
    # ran last. The cap applies to ALL event kinds (metadata and counter
    # rows included), so the guard counts the whole list.
    truncated = len(events) >= 950_000

    order = list(phases) + sorted(set(per_phase) - set(phases))
    rows = {
        ph: {
            "device_seconds": round(_merged_length_us(per_phase[ph]) / 1e6, 6),
            "op_events": per_phase_n[ph],
        }
        for ph in order
        if ph in per_phase
    }
    return {
        "rows": rows,
        "unattributed_s": round(
            _subtract_covered_us(all_iv, attributed_iv) / 1e6, 6
        ),
        "device_total_s": round(_merged_length_us(all_iv) / 1e6, 6),
        "modules": {
            m: round(_merged_length_us(iv) / 1e6, 6)
            for m, iv in sorted(per_module.items())
        },
        "host_rows": {
            name: {
                "seconds": round(_merged_length_us(iv) / 1e6, 6),
                "spans": len(iv),
            }
            for name, iv in sorted(host_iv.items())
        },
        "op_events": n_ops,
        **({"suspected_truncated": True} if truncated else {}),
        **({"trace_file": trace_file} if trace_file else {}),
        "source": "trace",
    }


def attributed_sum_s(record: Mapping[str, Any]) -> float:
    """sum(per-phase rows) + unattributed — the quantity the CI gate
    compares against the traced region's wall clock."""
    rows = record.get("rows") or {}
    return round(
        sum(r["device_seconds"] for r in rows.values())
        + float(record.get("unattributed_s") or 0.0),
        6,
    )
