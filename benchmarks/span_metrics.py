"""What the span and counter readers of `layer_metrics/` share: the
program's own record of what its host did (`hefl_tpu.obs.spans.recorded()`,
rows of id, parent, name, call, round, t0_ns, t1_ns on the profiler's clock)
cut to the parts of a run the benchmark speaks of.

- The measured call is the last `run_experiment` call of the process.
- Window rounds are its rounds >= 1 (round 0 is the lead-in).
- Traced rounds are its rounds 1..`trace["rounds_traced"]`.
- Set-up is every span that ended before the measured call's round 0 did:
  the data made ahead of the calls, the warm-up call, the measured call's
  start and its lead-in round.

A program without the recorder (a parent commit) has nothing to read: every
function returns None and the metric is left out of the line.
"""

from __future__ import annotations

import statistics

ROUND = "hefl.round"
SETUP_STEP = "hefl.setup."
TRAIN_STEP = "hefl.phase.train+encrypt+aggregate."
DECRYPT_STEP = "hefl.phase.decrypt."


def rows():
    """The recorder's closed spans, or None where there is no recorder."""
    try:
        from hefl_tpu.obs import spans
    except ImportError:
        return None
    read = getattr(spans, "recorded", None)
    return read() if read is not None else None


def measured_call(spans) -> int | None:
    calls = [s.call for s in spans if s.call is not None]
    return max(calls) if calls else None


def _seconds(span) -> float:
    return (span.t1_ns - span.t0_ns) * 1e-9


def per_round_s(names, last: int | None = None):
    """Seconds of the spans named in `names`, summed round by round, over
    the measured call's rounds 1..`last` (to its end by default); None
    without a recorder or where no such span was recorded."""
    spans = rows()
    if not spans:
        return None
    call = measured_call(spans)
    by_round: dict[int, float] = {}
    for s in spans:
        if (s.call == call and s.name in names and s.round is not None
                and s.round >= 1 and (last is None or s.round <= last)):
            by_round[s.round] = by_round.get(s.round, 0.0) + _seconds(s)
    return [by_round[r] for r in sorted(by_round)] or None


def window_median_s(*names):
    """Median over the window's rounds; None where that is not positive."""
    vals = per_round_s(names)
    med = float(statistics.median(vals)) if vals else 0.0
    return med if med > 0 else None


def setup_sum_s(wanted):
    """Seconds of the spans that `wanted(name)` picks, summed over set-up."""
    spans = rows()
    if not spans:
        return None
    call = measured_call(spans)
    lead_in = [s.t1_ns for s in spans
               if s.call == call and s.name == ROUND and s.round == 0]
    if not lead_in:
        return None
    total = sum(_seconds(s) for s in spans
                if wanted(s.name) and s.t1_ns <= lead_in[0])
    return total if total > 0 else None


def counter(name: str):
    """A counter of the program's `obs.metrics` registry, as it stands when
    the readers run: after the window has closed and before the checks."""
    try:
        from hefl_tpu.obs import metrics
    except ImportError:
        return None
    value = float(metrics.counter(name).value)
    return value if value > 0 else None
