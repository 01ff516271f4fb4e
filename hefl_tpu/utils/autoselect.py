"""Persisted per-device-kind auto-selection winners.

The augment row-shift backend and the client-fusion training backend are
both chosen by a one-shot micro-timing at first use ("auto" mode). The
timing is cheap but not free (it compiles and runs each candidate), and a
short-lived CLI run pays it on every invocation. This module persists the
winner per *device kind* next to the XLA compilation cache — the natural
home, since both caches answer "what did we already learn about compiling
/ running on this exact device" — so the probe runs once per (device kind,
decision), not once per process.

Storage is one JSON file, ``hefl_autoselect.json``, inside the compile-cache
directory JAX resolved (``utils.device.compile_cache_dir``: the
``JAX_COMPILATION_CACHE_DIR`` environment variable, else the checkout's
fixed ``.jax_cache`` that cli.py and the drivers set). No compile-cache dir configured => no persistence
(the in-process cache still applies). ``HEFL_AUTOSELECT_CACHE=0`` disables
persistence explicitly — the test suite sets it so auto-selection tests
always exercise the live micro-timing path.

Records are {"winner": str, "timings_ms": {...}} keyed by decision name
then device kind. Corrupt or unreadable files are treated as empty: the
cache is an optimization, never a correctness dependency.
"""

from __future__ import annotations

import json
import os

_FILENAME = "hefl_autoselect.json"


def _cache_file() -> str | None:
    if os.environ.get("HEFL_AUTOSELECT_CACHE", "1") == "0":
        return None
    from hefl_tpu.utils.device import compile_cache_dir

    cache_dir = compile_cache_dir()
    if not cache_dir:
        return None
    return os.path.join(cache_dir, _FILENAME)


def _read_all(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def load_winner(
    decision: str, device_kind: str, allowed=None
) -> dict | None:
    """-> {"winner": str, "timings_ms": {...}} or None on any miss.

    `allowed` (a container of valid winner names) rejects stale entries —
    e.g. a renamed backend — HERE, before the cache hit is published to
    obs: a rejected entry must not log a 'cache' outcome the caller then
    overrides with a fresh probe."""
    path = _cache_file()
    if path is None:
        return None
    rec = _read_all(path).get(decision, {}).get(device_kind)
    if (
        isinstance(rec, dict)
        and isinstance(rec.get("winner"), str)
        and (allowed is None or rec["winner"] in allowed)
    ):
        _record_outcome(decision, device_kind, rec["winner"], "cache",
                        rec.get("timings_ms"))
        return rec
    return None


def _record_outcome(
    decision: str, device_kind: str, winner: str, source: str,
    timings_ms: dict | None,
) -> None:
    """Publish one auto-selection outcome (probe run or persisted-cache
    hit) to obs.events / obs.metrics — every backend decision a run makes
    is queryable instead of buried in a report dict."""
    from hefl_tpu.obs import events, metrics

    metrics.counter(f"autoselect.{source}").inc()
    events.emit(
        "autoselect",
        decision=decision,
        device_kind=device_kind,
        winner=winner,
        source=source,
        timings_ms=timings_ms,
    )


def store_winner(
    decision: str, device_kind: str, winner: str,
    timings_ms: dict | None = None,
) -> None:
    """Best-effort atomic upsert; failures are silent (persistence is an
    optimization — the in-process cache already holds the choice)."""
    # The probe RAN whether or not its winner can be persisted: record the
    # outcome before the cache-dir early-out.
    _record_outcome(decision, device_kind, winner, "probe", timings_ms)
    path = _cache_file()
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        data = _read_all(path)
        data.setdefault(decision, {})[device_kind] = {
            "winner": winner,
            "timings_ms": timings_ms,
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass
