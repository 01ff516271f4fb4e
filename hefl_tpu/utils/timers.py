"""Structured per-phase wall-clock timing.

The reference traces by `start=time.time(); ...; print('x time', end-start)`
around every expensive phase (/root/reference/FLPyfhelin.py:203,223-224,235,
243-248,264-267,305,326-327 and notebook cell 3's `t.append`). `PhaseTimer`
formalizes exactly that phase schema — train / encrypt / aggregate /
decrypt / evaluate — as a reusable collector whose dict output is the
benchmark record (BASELINE.md's table rows). It keeps no clock of its own:
a phase is a span of the program's one recorder (`obs.spans`), named
`hefl.phase.<name>`, and its seconds are that span's.
"""

from __future__ import annotations

import contextlib

from hefl_tpu.obs import spans as obs_spans


class PhaseTimer:
    """Collects named wall-clock phases; re-entering a phase accumulates.

    >>> t = PhaseTimer()
    >>> with t.phase("train"): ...
    >>> t.summary()            # {'train': 1.23, 'total': 1.23}
    """

    def __init__(self) -> None:
        self._elapsed: dict[str, float] = {}
        self._order: list[str] = []

    @contextlib.contextmanager
    def phase(self, name: str):
        # A host span: a row of the recorder's store and, whenever the
        # profiler is on, a TraceAnnotation beside the device-op events.
        timed = obs_spans.span(obs_spans.PHASE_PREFIX + name)
        try:
            with timed:
                yield
        finally:
            self.record(name, timed.record.seconds)

    def record(self, name: str, seconds: float) -> None:
        """Fold an externally-measured duration into the schema."""
        if name not in self._elapsed:
            self._order.append(name)
        self._elapsed[name] = self._elapsed.get(name, 0.0) + seconds

    def summary(self) -> dict[str, float]:
        out = {k: round(self._elapsed[k], 4) for k in self._order}
        out["total"] = round(sum(self._elapsed.values()), 4)
        return out

    def __repr__(self) -> str:
        parts = " | ".join(f"{k} {v:.2f}s" for k, v in self.summary().items())
        return f"PhaseTimer({parts})"
