"""The benchmark's arithmetic: the rate, the peaks table and the
reduction from a profiler trace to device seconds per scope.

Kept here, under the benchmark's own paths, so that every PR computes the
same number in the same way. Nothing in this file imports the program or
JAX (the trace reader imports `jax.profiler` only when it is called).
"""

from __future__ import annotations

import json
import os
import re

_HERE = os.path.dirname(os.path.abspath(__file__))

# Scopes the program writes with `jax.named_scope` (hefl_tpu/obs/scopes.py);
# the deepest one in an op's name path is the op's scope.
SCOPE_RE = re.compile(r"hefl\.(?!phase\.)[A-Za-z0-9_]+")
PHASE_PREFIX = "hefl.phase."
# Device lines of an xplane that hold one event per executed HLO op.
OPS_LINE = "XLA Ops"


def samples_per_s(rounds: int, samples_per_round: int, window_s: float,
                  chips: int) -> float:
    """Training samples all clients completed in the window, per second and
    per chip: every stall inside the window is paid for."""
    if window_s <= 0 or chips < 1:
        raise ValueError(f"window {window_s} s on {chips} chips")
    return rounds * samples_per_round / window_s / chips


def load_peaks(device_kind: str) -> dict:
    """Published peaks of `device_kind`; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to "
            "benchmarks/peaks.json with its source"
        )
    return table[device_kind]


def memory_peak(device_stats: list[dict], executables) -> dict:
    """Peak bytes on the fullest chip. The allocator's `peak_bytes_in_use`
    counts live arrays only (data, weights, keys, ciphertexts) and leaves out
    the temporaries a program holds while it runs (shown on the chip:
    PERF.md section 7). So the peak is the larger of that counter and the
    temporaries of the loaded program that holds most, by the compiler's own
    count (bytes per device), on top of what is still in use when the
    measured call has returned (`bytes_in_use`: weights and keys; the program
    has let go of its data by then, so this is a floor of the true peak, which
    lies about the data's size higher)."""
    live_peak = max((s.get("peak_bytes_in_use", 0) for s in device_stats),
                    default=0)
    resident = max((s.get("bytes_in_use", 0) for s in device_stats), default=0)
    temp, holder = 0, None
    for ex in executables:
        t = ex.get_compiled_memory_stats().temp_size_in_bytes
        if t > temp:
            temp, holder = t, ex
    name = holder.hlo_modules()[0].name if holder is not None else None
    return {"peak_bytes": int(max(live_peak, resident + temp)),
            "live_peak_bytes": int(live_peak), "resident_bytes": int(resident),
            "program_temp_bytes": int(temp), "program": name}


def merged(intervals) -> list[tuple[float, float]]:
    """Union of [start, end) intervals as a sorted disjoint list."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def union_s(intervals) -> float:
    return sum(hi - lo for lo, hi in merged(intervals)) * 1e-9


def scope_of(text: str) -> str | None:
    hits = SCOPE_RE.findall(text)
    return hits[-1] if hits else None


def read_xplane(path: str) -> list[dict]:
    """An `.xplane.pb` as neutral events: {plane, line, name, start_ns,
    dur_ns, scope}. Device events come from each TPU plane's op line; host
    events are the `hefl.phase.*` annotations of the driver's PhaseTimer."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = str(ev.name)
                if device:
                    text = name + " " + " ".join(
                        str(v) for _, v in ev.stats if isinstance(v, str)
                    )
                    scope = scope_of(text)
                elif name.startswith(PHASE_PREFIX):
                    scope = name
                else:
                    continue
                events.append({
                    "plane": plane.name, "line": line.name, "name": name,
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns), "scope": scope,
                })
    return events


def _self_times(events: list[dict]) -> dict[str, float]:
    """Seconds per op name not covered by ops nested inside it (a `while`
    spans its body's ops on the same line)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end_ns, name, child_ns, dur_ns]

    def close(item):
        _, name, child, dur = item
        out[name] = out.get(name, 0.0) + max(dur - child, 0.0) * 1e-9

    for ev in sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        while stack and stack[-1][0] <= ev["start_ns"]:
            close(stack.pop())
        if stack:
            stack[-1][2] += ev["dur_ns"]
        stack.append([ev["start_ns"] + ev["dur_ns"], ev["name"], 0.0,
                      ev["dur_ns"]])
    while stack:
        close(stack.pop())
    return out


def _overlap_s(spans, windows) -> float:
    """Seconds of the disjoint sorted `spans` that fall inside `windows`."""
    total = 0.0
    for w_lo, w_hi in windows:
        for lo, hi in spans:
            if hi > w_lo and lo < w_hi:
                total += min(hi, w_hi) - max(lo, w_lo)
    return total * 1e-9


def short_op(text: str, limit: int = 120) -> str:
    """An op event's name is its whole HLO instruction; keep its head."""
    return text if len(text) <= limit else text[: limit - 3] + "..."


def reduce_trace(events: list[dict], rounds_traced: int) -> dict:
    """Neutral events -> what the per-layer readers take: traced window and
    busy seconds (averaged over the device planes), device seconds per
    scope (ops named after a `hefl.*` scope: the Pallas kernels) and per
    host phase open at the time, the ten ops with most self time and the
    ten largest idle totals by host phase."""
    device = [e for e in events if e["plane"].startswith("/device:")]
    host = [e for e in events if not e["plane"].startswith("/device:")]
    if not device:
        raise ValueError("the trace holds no device operation")
    planes = sorted({e["plane"] for e in device})
    t_lo = min(e["start_ns"] for e in device)
    t_hi = max(e["start_ns"] + e["dur_ns"] for e in device)
    phases = [e for e in host if e["scope"]]
    if phases:  # the traced window is the driver's rounds, idle ends included
        t_lo = min(t_lo, min(e["start_ns"] for e in phases))
        t_hi = max(t_hi, max(e["start_ns"] + e["dur_ns"] for e in phases))
    busy, scope_s, gaps, phase_busy = 0.0, {}, {}, {}
    for plane in planes:
        evs = [e for e in device if e["plane"] == plane]
        spans = merged((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in evs)
        busy += sum(hi - lo for lo, hi in spans) * 1e-9
        for scope in {e["scope"] for e in evs if e["scope"]}:
            scope_s[scope] = scope_s.get(scope, 0.0) + union_s(
                (e["start_ns"], e["start_ns"] + e["dur_ns"])
                for e in evs if e["scope"] == scope
            )
        for name in {p["scope"] for p in phases}:
            phase_busy[name] = phase_busy.get(name, 0.0) + _overlap_s(
                spans, [(p["start_ns"], p["start_ns"] + p["dur_ns"])
                        for p in phases if p["scope"] == name])
        edges = [t_lo] + [t for span in spans for t in span] + [t_hi]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi <= lo:
                continue
            mid = (lo + hi) / 2
            owner = next(
                (p["scope"] for p in phases
                 if p["start_ns"] <= mid < p["start_ns"] + p["dur_ns"]),
                "between_phases",
            )
            gaps[owner] = gaps.get(owner, 0.0) + (hi - lo) * 1e-9
    n = len(planes)
    ops = _self_times([e for e in device if e["plane"] == planes[0]])
    top = lambda d: [  # noqa: E731
        [short_op(k), v]
        for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]
    ]
    return {
        "window_s": (t_hi - t_lo) * 1e-9,
        "busy_s": busy / n,
        "scope_s": {k: v / n for k, v in scope_s.items()},
        "phase_busy_s": {k: v / n for k, v in phase_busy.items()},
        "rounds_traced": int(rounds_traced),
        "breakdown": {
            "device_ops": top(ops),
            "idle_gaps": top({k: v / n for k, v in gaps.items()}),
        },
    }
