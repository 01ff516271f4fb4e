"""Host spans: one recorder on the profiler's clock, and the streaming
engine's round-lifecycle tree (ISSUE 20, ISSUE 24).

**The recorder.** Every host-side timing of the program goes through
`span(name)` (a context manager; `start(name)` / `stop(name)` are its
handle-free twin): `utils.timers.PhaseTimer` phases (`hefl.phase.<phase>`),
the steps inside them (`hefl.phase.<phase>.<step>`), the start of
`run_experiment` (`hefl.setup` and its `hefl.setup.<step>` children), one
`hefl.round` a round, the straggler and quorum waits, and the wall-clock
IO legs of `SpanTracer.measure`. A span is a `HostSpan` row (`id`, `parent`
= the span open on the same thread when it began, `name`, `call` = which
`run_experiment` call of the process, `round`, `t0_ns`, `t1_ns`) kept in a
process-wide, in-memory, bounded store (`recorded()` reads it; the oldest
rows fall out past `MAX_SPANS`), and a `jax.profiler.TraceAnnotation` of
the same name over the same interval. Both are stamped by the unix-epoch
wall clock that the profiler stamps host events with (`now_ns`), so
whenever the profiler is on, the span is in the `.xplane.pb` beside the
device's operations, and when it is off the annotation costs what
PhaseTimer's always did. Recording is always on and writes nothing to
disk; `run_experiment` exports a call's spans on request (`--span-trace
PATH`, Chrome trace JSON, with the streaming rounds' trees).

**The engine's tree.** `obs.trace` attributes DEVICE time; the streaming
engine's own lifecycle — arrival -> fold -> ship -> commit -> recovery —
was counters only. `SpanTracer` records a structured span TREE per round
on the engine's virtual clock (`clock="virtual"`: seconds since round
start, the same axis `_Delivery.t` / `commit_s` / `ships_done_s` live on)
with wall-clock spans (`clock="wall"`: unix seconds on the recorder's
clock, so they can be laid against a device trace) for the process-IO
legs the virtual clock cannot see (journal writes, fsync, transciphering,
recovery replay); those are recorded through `span` too.

Span kinds and their producers:

  round               the tracer root (one per `StreamEngine.run_round`)
  arrival             every fresh delivery processed (== stream.arrivals)
  retry               every scheduled redelivery   (== stream.retries)
  fold                every client fold, fresh or stale (== stream.folds)
  transcipher         the HHE batch transcipher dispatch (wall)
  tier_fold           a carried stale HOST partial folded at the root
                      (== dcn.tier.stale_folded)
  tier_ship           one per shipped tier: first send -> landing/miss
                      (== dcn.ship.landed + dcn.ship.missed)
  ship_retry          every retried ship delivery (== dcn.retry.attempts)
  journal_append      every logical WAL append (wall, == journal.appends)
  group_commit_flush  every buffered-batch write(2) (wall,
                      == journal.write_batches)
  fsync               every journal fsync (wall, == journal.fsyncs)
  commit              the round verdict (committed or degraded)
  recovery_replay     a replayed round's marker (== recovery.rounds_replayed)

The `COUNTER_OF` table IS the conservation contract: for every kind it
maps, the per-round span count must equal the per-round delta of the
named `obs.metrics` counters exactly (`conservation_errors` checks it —
tests and the perf-smoke stage (q) both call it).

Spans ride `obs.events` as a new `span` event kind (one record per span,
emitted at record time; no-op when the global event log is off) and
export to Chrome trace-viewer JSON via `to_trace_events` /
`export_chrome_trace` — the format `obs/trace.py` already parses, so
engine timelines render with the same tooling as device traces and land
in `trace_attribution`'s host_rows (names are `hefl.span.<kind>`).

A replayed round's span tree matches its uninterrupted twin up to the
`recovery_replay` spans and the wall-clock IO spans (replay VERIFIES
journal records instead of appending them): compare with
`tree_signature`, which keys on the deterministic virtual-clock
structure and drops wall-clock spans by default.

Producers reach the active tracer through a module-level current-tracer
slot (`activate` / `current`): the engine installs one tracer per round
and the journal/hierarchy/transcipher layers record into it without
threading a parameter through every call.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gzip
import itertools
import json
import threading
import time
from typing import Any, Iterable, Iterator

import jax.profiler

from hefl_tpu.obs import events as obs_events

# ---------------------------------------------------------------------------
# The recorder: every host span of the program, on the profiler's clock.
# ---------------------------------------------------------------------------

# The profiler stamps host events (TraceMe) with the unix-epoch wall clock,
# so a row's t0_ns/t1_ns and its annotation in a trace are one axis.
now_ns = time.time_ns

MAX_SPANS = 1 << 16   # rows kept; a synchronous round records about a dozen

# Names the readers (benchmarks/layer_metrics) and the tests look up.
SETUP = "hefl.setup"
ROUND = "hefl.round"
PHASE_PREFIX = "hefl.phase."
TRACER_PREFIX = "hefl.span."   # SpanTracer.measure's legs, by kind


@dataclasses.dataclass(slots=True)
class HostSpan:
    """One recorded host span: a row of the store."""

    id: int
    parent: int | None   # the span open on the same thread when this began
    name: str
    call: int | None     # which run_experiment call of the process
    round: int | None
    t0_ns: int
    t1_ns: int | None = None   # None while the span is open

    @property
    def seconds(self) -> float:
        return ((self.t1_ns or self.t0_ns) - self.t0_ns) * 1e-9


class _OpenSpan:
    """`Recorder.span`'s handle: a context manager, or `start()`/`stop()`."""

    __slots__ = ("_recorder", "_name", "_round", "_annotation", "record")

    def __init__(self, recorder: "Recorder", name: str, round: int | None):
        self._recorder, self._name, self._round = recorder, name, round
        self.record: HostSpan | None = None

    def start(self) -> HostSpan:
        rec = self._recorder
        stack = rec._stack()
        parent = stack[-1].record if stack else None
        round = self._round
        if round is None and parent is not None:
            round = parent.round
        # stamped outside the annotation, as PhaseTimer's seconds always were
        t0 = now_ns()
        self._annotation = jax.profiler.TraceAnnotation(self._name)
        self._annotation.__enter__()
        self.record = HostSpan(
            next(rec._ids), None if parent is None else parent.id,
            self._name, rec.call_id, round, t0,
        )
        stack.append(self)
        return self.record

    def stop(self) -> None:
        if self.record is None or self.record.t1_ns is not None:
            return   # never started, or closed with an enclosing span
        stack = self._recorder._stack()
        while stack:   # children an exception left open end with this span
            top = stack.pop()
            top._annotation.__exit__(None, None, None)
            top.record.t1_ns = now_ns()
            self._recorder._spans.append(top.record)
            if top is self:
                break

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()


class Recorder:
    """A bounded in-memory store of closed host spans, a per-thread stack of
    the open ones (for `parent`) and the count of `run_experiment` calls."""

    def __init__(self, bound: int = MAX_SPANS):
        self._spans: collections.deque[HostSpan] = collections.deque(
            maxlen=bound)
        self._ids = itertools.count()
        self._open = threading.local()
        self._calls = itertools.count()
        self._call_depth = 0   # open spans on the thread when the call began
        self.call_id: int | None = None

    def _stack(self) -> list[_OpenSpan]:
        try:
            return self._open.stack
        except AttributeError:
            self._open.stack = []
            return self._open.stack

    def span(self, name: str, round: int | None = None) -> _OpenSpan:
        """Record `name` over a `with` body (yields the `HostSpan`, whose
        `t1_ns` is set on exit). `round` defaults to the parent's."""
        return _OpenSpan(self, name, round)

    def start(self, name: str, round: int | None = None) -> HostSpan:
        """Open `name` with no handle to keep: for a region too long to
        indent under a `with` (`run_experiment`'s set-up, a round's body).
        `stop(name)` closes it; so does leaving an enclosing span or the
        call, if an error passes first."""
        return _OpenSpan(self, name, round).start()

    def stop(self, name: str) -> None:
        """Close the innermost open span called `name` on this thread, and
        whatever is still open inside it."""
        for open_span in reversed(self._stack()):
            if open_span.record.name == name:
                open_span.stop()
                return

    def begin_call(self) -> int:
        """Number the `run_experiment` call that begins (0, 1, ... in the
        process): spans recorded until `end_call` carry it. Calls do not
        nest; a call an error left open is ended first."""
        if self.call_id is not None:
            self.end_call()
        self._call_depth = len(self._stack())
        self.call_id = next(self._calls)
        return self.call_id

    def end_call(self) -> None:
        """End the call and close what it left open on this thread."""
        stack = self._stack()
        if self.call_id is not None and len(stack) > self._call_depth:
            stack[self._call_depth].stop()
        self.call_id = None

    @contextlib.contextmanager
    def call(self):
        """`begin_call` .. `end_call` around a `with` body; yields the number."""
        try:
            yield self.begin_call()
        finally:
            self.end_call()

    def recorded(self, call: int | None = None) -> list[HostSpan]:
        """The closed spans still in the store, in the order they ended;
        with `call`, that call's only."""
        return [s for s in self._spans if call is None or s.call == call]


_RECORDER = Recorder()
span = _RECORDER.span
start = _RECORDER.start
stop = _RECORDER.stop
call = _RECORDER.call
begin_call = _RECORDER.begin_call
end_call = _RECORDER.end_call
recorded = _RECORDER.recorded


def host_trace_events(spans: Iterable[HostSpan], base_ns: int = 0) -> list[dict]:
    """Recorded spans as Chrome trace-viewer events, `ts` in microseconds
    since `base_ns`; the absolute stamp rides in `args`."""
    return [{
        "ph": "X", "name": s.name,
        "ts": (s.t0_ns - base_ns) / 1e3,
        "dur": (s.t1_ns - s.t0_ns) / 1e3,
        "args": {"id": s.id, "parent": s.parent, "call": s.call,
                 "round": s.round, "clock": "wall", "t0_ns": s.t0_ns},
    } for s in spans]


# ---------------------------------------------------------------------------
# The streaming engine's round-lifecycle tree.
# ---------------------------------------------------------------------------

SPAN_KINDS = (
    "round",
    "arrival",
    "retry",
    "fold",
    "transcipher",
    "tier_fold",
    "tier_ship",
    "ship_retry",
    "journal_append",
    "group_commit_flush",
    "fsync",
    "commit",
    "recovery_replay",
)

# Wall-clock span kinds: process-IO artifacts, not round-lifecycle
# structure. Excluded from `tree_signature` by default (replay verifies
# journal records instead of re-appending them, so these legitimately
# differ between a replayed round and its uninterrupted twin).
WALL_KINDS = frozenset(
    {"transcipher", "journal_append", "group_commit_flush", "fsync",
     "recovery_replay"}
)

# kind -> obs.metrics counter name(s) whose per-round delta the per-round
# span count must equal EXACTLY (a tuple sums). Kinds absent here
# ("round", "transcipher", "commit") have no counter twin.
COUNTER_OF: dict[str, tuple[str, ...]] = {
    "arrival": ("stream.arrivals",),
    "retry": ("stream.retries",),
    "fold": ("stream.folds",),
    "tier_fold": ("dcn.tier.stale_folded",),
    "tier_ship": ("dcn.ship.landed", "dcn.ship.missed"),
    "ship_retry": ("dcn.retry.attempts",),
    "journal_append": ("journal.appends",),
    "group_commit_flush": ("journal.write_batches",),
    "fsync": ("journal.fsyncs",),
    "recovery_replay": ("recovery.rounds_replayed",),
}

_TRACE_IDS = itertools.count()


@dataclasses.dataclass
class Span:
    """One recorded span. Times are seconds on the tracer's clock axis
    (`clock`: "virtual" = engine virtual clock from round start, "wall" =
    unix seconds on the recorder's clock)."""

    kind: str
    t0: float
    t1: float
    clock: str = "virtual"
    args: dict = dataclasses.field(default_factory=dict)
    children: list["Span"] = dataclasses.field(default_factory=list)

    @property
    def dur(self) -> float:
        return max(0.0, self.t1 - self.t0)

    def walk(self) -> Iterator["Span"]:
        """Pre-order traversal, self included."""
        yield self
        for ch in self.children:
            yield from ch.walk()


class SpanTracer:
    """One round's span tree + its event/export surface.

    `add` records a completed span at explicit (virtual-clock) times;
    `measure` is the wall-clock context manager for IO legs. Every
    recorded span also rides the global event log as a `span` event
    immediately (no-op when events are unconfigured), so a crash
    mid-round loses nothing that was recorded."""

    def __init__(self, round_index: int, kind: str = "round"):
        self.round_index = int(round_index)
        self.trace_id = f"r{int(round_index)}.{next(_TRACE_IDS)}"
        self.wall0 = self.wall()   # when the tracer opened, recorder's clock
        self._next_id = 0
        self.root = Span(kind, 0.0, 0.0, clock="virtual",
                         args={"round": int(round_index)})
        self._ids: dict[int, int] = {id(self.root): self._take_id()}
        self._finished = False

    def _take_id(self) -> int:
        i = self._next_id
        self._next_id += 1
        return i

    @staticmethod
    def wall() -> float:
        """Now on the wall-clock span axis: the recorder's clock, seconds."""
        return now_ns() * 1e-9

    def add(
        self,
        kind: str,
        t0: float,
        t1: float | None = None,
        parent: Span | None = None,
        clock: str = "virtual",
        **args: Any,
    ) -> Span:
        """Record a completed span (point span when t1 is omitted) under
        `parent` (the root by default) and emit its `span` event."""
        sp = Span(kind, float(t0), float(t0 if t1 is None else t1),
                  clock=clock, args=dict(args))
        (parent if parent is not None else self.root).children.append(sp)
        self._ids[id(sp)] = self._take_id()
        self._emit(sp, parent if parent is not None else self.root)
        return sp

    @contextlib.contextmanager
    def measure(self, kind: str, parent: Span | None = None, **args: Any):
        """Wall-clock span around a `with` body (journal IO, transcipher,
        recovery replay), timed by the recorder: the same interval is a
        `hefl.span.<kind>` row of the store and a profiler annotation."""
        sp = Span(kind, 0.0, 0.0, clock="wall", args=dict(args))
        (parent if parent is not None else self.root).children.append(sp)
        self._ids[id(sp)] = self._take_id()
        timed = span(TRACER_PREFIX + kind, round=self.round_index)
        sp.t0 = sp.t1 = timed.start().t0_ns * 1e-9
        try:
            yield sp
        finally:
            timed.stop()
            sp.t1 = timed.record.t1_ns * 1e-9
            self._emit(sp, parent if parent is not None else self.root)

    def finish(self, t1: float | None = None) -> None:
        """Seal the root: extend it to cover `t1` (and every child) and
        emit its event. Idempotent."""
        end = float(t1) if t1 is not None else 0.0
        for sp in self.root.walk():
            if sp is not self.root and sp.clock == "virtual":
                end = max(end, sp.t1)
        self.root.t1 = max(self.root.t1, end)
        if not self._finished:
            self._finished = True
            self._emit(self.root, None)

    # -- event + export surface --------------------------------------------

    def _emit(self, sp: Span, parent: Span | None) -> None:
        obs_events.emit(
            "span",
            trace=self.trace_id,
            round=self.round_index,
            span_kind=sp.kind,
            id=self._ids[id(sp)],
            parent=None if parent is None else self._ids[id(parent)],
            t0=round(sp.t0, 9),
            t1=round(sp.t1, 9),
            clock=sp.clock,
            args=sp.args,
        )

    def spans(self) -> list[Span]:
        """Every span, pre-order (root first)."""
        return list(self.root.walk())

    def counts(self) -> dict[str, int]:
        """Per-kind span counts (root excluded)."""
        out: dict[str, int] = {}
        for sp in self.root.walk():
            if sp is self.root:
                continue
            out[sp.kind] = out.get(sp.kind, 0) + 1
        return out

    def to_trace_events(self, wall_base: float | None = None) -> list[dict]:
        """Chrome trace-viewer events (`ph:"X"`, microsecond ts/dur) —
        the exact shape `obs.trace.load_trace_events` parses; names are
        `hefl.span.<kind>` so they land in trace_attribution host_rows.
        Virtual-clock spans count from the round's start, wall-clock ones
        from `wall_base` (unix seconds; the tracer's opening by default)."""
        base = self.wall0 if wall_base is None else wall_base
        out = []
        for sp in self.root.walk():
            t0 = sp.t0 - base if sp.clock == "wall" else sp.t0
            out.append({
                "ph": "X",
                "name": TRACER_PREFIX + sp.kind,
                "ts": round(t0 * 1e6, 3),
                "dur": round(sp.dur * 1e6, 3),
                "args": {
                    "round": self.round_index,
                    "trace": self.trace_id,
                    "clock": sp.clock,
                    **sp.args,
                },
            })
        return out


# ---------------------------------------------------------------------------
# The current-tracer slot producers record into.
# ---------------------------------------------------------------------------

_CURRENT: SpanTracer | None = None


def current() -> SpanTracer | None:
    """The active tracer (None outside a traced round)."""
    return _CURRENT


@contextlib.contextmanager
def activate(tracer: SpanTracer):
    """Install `tracer` as the current tracer for the `with` body. Nested
    activations restore the outer tracer on exit."""
    global _CURRENT
    prev = _CURRENT
    _CURRENT = tracer
    try:
        yield tracer
    finally:
        _CURRENT = prev


# ---------------------------------------------------------------------------
# Export, reconstruction, conservation, twin comparison.
# ---------------------------------------------------------------------------


def export_chrome_trace(
    path: str,
    tracers: Iterable[SpanTracer],
    host_spans: Iterable[HostSpan] = (),
) -> str:
    """Write the tracers' spans, and the recorder's `host_spans`, as ONE
    Chrome trace-viewer JSON file ({"traceEvents": [...]}; gzipped when
    `path` ends in .gz). Returns `path`. Loadable by
    `obs.trace.load_trace_events`. With host spans, every wall-clock event
    counts from the earliest of them (`wall_base_ns` in the file); a
    tracer's wall-clock legs are in its tree, so their rows are left out."""
    tracers = list(tracers)
    host_spans = [s for s in host_spans
                  if not (tracers and s.name.startswith(TRACER_PREFIX))]
    base_ns = min((s.t0_ns for s in host_spans), default=None)
    events: list[dict] = []
    for tr in tracers:
        events.extend(tr.to_trace_events(
            wall_base=None if base_ns is None else base_ns * 1e-9))
    doc: dict[str, Any] = {"traceEvents": events}
    if base_ns is not None:
        events.extend(host_trace_events(host_spans, base_ns))
        doc["wall_base_ns"] = base_ns
    blob = json.dumps(doc).encode("utf-8")
    if path.endswith(".gz"):
        with gzip.open(path, "wb") as f:
            f.write(blob)
    else:
        with open(path, "wb") as f:
            f.write(blob)
    return path


def trees_from_events(events: Iterable[dict]) -> dict[str, Span]:
    """Rebuild span trees from `span` event records (obs.events JSONL) ->
    {trace_id: root Span}. Orphaned children (their root never sealed —
    a crash mid-round) are attached to a synthetic root so nothing
    recorded is dropped silently."""
    by_trace: dict[str, list[dict]] = {}
    for ev in events:
        if ev.get("event") == "span":
            by_trace.setdefault(str(ev["trace"]), []).append(ev)
    out: dict[str, Span] = {}
    for trace_id, evs in by_trace.items():
        spans: dict[int, Span] = {}
        parents: dict[int, int | None] = {}
        for ev in evs:
            spans[int(ev["id"])] = Span(
                ev["span_kind"], float(ev["t0"]), float(ev["t1"]),
                clock=ev.get("clock", "virtual"),
                args=dict(ev.get("args") or {}),
            )
            parents[int(ev["id"])] = ev.get("parent")
        root = None
        orphans = []
        for i in sorted(spans):
            pi = parents[i]
            if pi is None:
                root = spans[i]
            elif int(pi) in spans:
                spans[int(pi)].children.append(spans[i])
            else:
                orphans.append(spans[i])
        if root is None:
            root = Span("round", 0.0, 0.0, args={"unsealed": True})
        root.children.extend(orphans)
        out[trace_id] = root
    return out


def span_counts(root: Span) -> dict[str, int]:
    """Per-kind counts under `root` (root itself excluded)."""
    out: dict[str, int] = {}
    for sp in root.walk():
        if sp is root:
            continue
        out[sp.kind] = out.get(sp.kind, 0) + 1
    return out


def conservation_errors(
    counts: dict[str, int], metrics_delta: dict[str, Any]
) -> list[str]:
    """The span-count == counter-delta contract, checked: for every kind
    in COUNTER_OF, span count must equal the summed counter delta
    exactly. -> human-readable violations ([] = conserved). `counts` is
    `SpanTracer.counts()` (or summed across tracers); `metrics_delta` is
    `obs.metrics.snapshot_delta(baseline)` over the same region."""
    errs = []
    for kind, names in COUNTER_OF.items():
        want = sum(int(metrics_delta.get(n, 0) or 0) for n in names)
        got = int(counts.get(kind, 0))
        if got != want:
            errs.append(
                f"span kind {kind!r}: {got} spans but counters "
                f"{'+'.join(names)} moved {want}"
            )
    return errs


def tree_signature(
    root: Span,
    ignore: tuple[str, ...] = ("recovery_replay",),
    include_wall: bool = False,
):
    """A comparable signature of the span tree's DETERMINISTIC structure:
    (kind, virtual times, args, child signatures). Wall-clock spans are
    dropped unless `include_wall` (replay verifies journal records
    instead of re-appending, so IO spans legitimately differ between a
    replayed round and its uninterrupted twin); kinds in `ignore` are
    dropped wholesale — the replay-equals-twin gate compares with the
    defaults."""
    if root.kind in ignore or (not include_wall and root.clock == "wall"):
        return None
    times = (
        (round(root.t0, 6), round(root.t1, 6))
        if root.clock == "virtual"
        else ()
    )
    args = tuple(sorted(
        (k, v) for k, v in root.args.items()
        if isinstance(v, (str, int, float, bool, type(None)))
    ))
    kids = tuple(
        s for s in (
            tree_signature(ch, ignore, include_wall)
            for ch in root.children
        )
        if s is not None
    )
    return (root.kind, times, args, kids)


__all__ = [
    "COUNTER_OF",
    "HostSpan",
    "MAX_SPANS",
    "Recorder",
    "SPAN_KINDS",
    "Span",
    "SpanTracer",
    "WALL_KINDS",
    "activate",
    "begin_call",
    "call",
    "conservation_errors",
    "current",
    "end_call",
    "export_chrome_trace",
    "host_trace_events",
    "now_ns",
    "recorded",
    "span",
    "span_counts",
    "start",
    "stop",
    "trees_from_events",
    "tree_signature",
]
