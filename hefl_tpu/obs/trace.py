"""Device seconds by scope and by kernel, from the profiler's own record.

`jax.profiler.start_trace` (the `--profile` flag of the experiment CLI, the
benchmark's `--trace 1`) writes an `.xplane.pb`. On a TPU every executed HLO
op is an event of the device plane's `XLA Ops` line, and the event's
METADATA (one record an HLO instruction, shared by all its executions)
carries the stats `tf_op` (the HLO `op_name`: the JAX name stack, so every
`jax.named_scope` of `obs.scopes` around the op), `hlo_category`, `flops`,
`bytes_accessed`, `program_id` and a `display_name` (`fusion.3676`,
`splash_mqa_fwd_residuals.90`). `jax.profiler.ProfileData` shows an event's
own stats and never its metadata's, so this module reads the file's wire
format itself: seven protobuf messages (XSpace, XPlane, XLine, XEvent,
XStat, XEventMetadata, XStatMetadata), varints and length-delimited fields,
no generated module and no TensorFlow. Planes and lines nobody reads are
skipped by their length.

What is read is what ran: an executable loaded from the persistent compile
cache carries the metadata it was compiled with, so no HLO text, no second
compile and no cache bypass is needed. (The cache's key leaves metadata
out, so a cached executable of an older tree is loaded with the OLDER
scopes: a tree that adds or moves a scope is read after a cold compile.)

Times are SELF times: a `while` op's event spans its body's events on the
same line, so an op's seconds are its duration less the events nested in
it, and seconds summed over any partition of the ops add up to the busy
seconds of the device. A fusion is one op: it is attributed to the scope of
its root instruction, whatever scopes its fused producers had.

Failure policy: a truncated or malformed file, a trace with no TPU device
plane, a device plane with no `XLA Ops` line, or leaf ops that carry `tf_op`
for under half of their seconds raise `TraceParseError`. They do not read as
zeros.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gzip
import json
import os
import re
import struct
import time
from typing import Any, Iterator

import numpy as np

from hefl_tpu.obs import scopes

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"        # one event per executed HLO op
MODULES_LINE = "XLA Modules"  # one event per executed program
BACKWARD = "transpose("     # in `tf_op`: the op is part of a gradient


class TraceParseError(RuntimeError):
    """The trace is unusable."""


class NoDevicePlane(TraceParseError):
    """The trace holds no TPU device plane (a CPU run's trace)."""


class NoScopeMetadata(TraceParseError):
    """Leaf ops of under half of the leaf ops' seconds carry `tf_op`."""


@contextlib.contextmanager
def metadata_preserving_compile():
    """Turn the persistent XLA compilation cache off for the duration.

    The cache keys a program on its IR without locations, and a named scope
    is a location: a compile that hits the cache answers `as_text()` with
    the `op_name` metadata of whichever tree compiled the entry (on the CPU,
    with none). A caller that reads scopes out of HLO text
    (`analysis/coverage.py`, the scope tests) compiles afresh under this.
    The profiler's trace of a run needs no such thing: it shows what ran.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # forget that the cache was in use
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def load_trace_events(path: str) -> list[dict]:
    """Parse one trace-viewer JSON (.trace.json.gz or plain .json): -> the
    traceEvents list (the program's own span export, `obs.spans`).
    Truncated/corrupt input fails loudly."""
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


# --------------------------------------------------------------------------
# The wire format. Field numbers are those of tsl/profiler/protobuf/
# xplane.proto, named where they are used.
# --------------------------------------------------------------------------

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[tuple[int, int, Any]]:
    """(field number, wire type, value) of the message in buf[lo:hi]. A
    varint's value is the integer, a fixed field's its bytes, a
    length-delimited field's the (lo, hi) of its payload: nothing is copied
    and a field nobody asks for costs its key and its length."""
    p = lo
    try:
        while p < hi:
            key = shift = 0
            while True:
                c = buf[p]
                p += 1
                key |= (c & 0x7F) << shift
                if c < 0x80:
                    break
                shift += 7
            wire = key & 7
            if wire == _VARINT or wire == _BYTES:
                val = shift = 0
                while True:
                    c = buf[p]
                    p += 1
                    val |= (c & 0x7F) << shift
                    if c < 0x80:
                        break
                    shift += 7
                if wire == _BYTES:
                    val, p = (p, p + val), p + val
            elif wire == _FIXED64:
                val, p = buf[p:p + 8], p + 8
            elif wire == _FIXED32:
                val, p = buf[p:p + 4], p + 4
            else:
                raise TraceParseError(f"wire type {wire} at byte {p}")
            if p > hi:
                break
            yield key >> 3, wire, val
    except IndexError:
        p = hi + 1
    if p != hi:
        raise TraceParseError(f"a message of bytes {lo}-{hi} ends at {p}: "
                              "the file is truncated or not an xplane")


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, span, stat_names: dict[int, str]) -> tuple[str, Any]:
    """One XStat -> (its name, its value)."""
    name, value = "", None
    for f, _, v in _fields(buf, *span):
        if f == 1:    # metadata_id
            name = stat_names.get(v, "")
        elif f == 2:  # double_value
            value = struct.unpack("<d", v)[0]
        elif f == 3:  # uint64_value
            value = v
        elif f == 4:  # int64_value
            value = _signed(v)
        elif f == 5:  # str_value
            value = _text(buf, v)
        elif f == 7:  # ref_value: a string kept once, as a stat's name
            value = stat_names.get(v, "")
    return name, value


def _events(buf: bytes, spans) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """XEvents -> (metadata_id, offset_ps, duration_ps). The events' own
    stats (the device's copies of offset and duration) are skipped."""
    rows = []
    for span in spans:
        row = [0, 0, 0]  # an absent field reads 0, as in proto3
        for f, wire, v in _fields(buf, *span):
            if wire == _VARINT and 1 <= f <= 3:
                row[f - 1] = v
        rows.append(row)
    table = np.array(rows, np.int64).reshape(-1, 3)
    return table[:, 0], table[:, 1], table[:, 2]


def _line(buf: bytes, span) -> tuple[str, int, list]:
    """XLine -> (name, timestamp_ns, the spans of its events)."""
    name, stamp, events = "", 0, []
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            stamp = v
        elif f == 4:
            events.append(v)
    return name, stamp, events


def _map_entry(buf: bytes, span):
    """A protobuf map entry -> (key, the span of its value)."""
    key, value = 0, (span[1], span[1])
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


@dataclasses.dataclass(frozen=True)
class Op:
    """One HLO instruction as the device's event metadata describes it."""

    name: str            # the instruction's text: `ProfileData`'s event name
    display_name: str    # `fusion.3676`, `splash_mqa_fwd_residuals.90`
    hlo_category: str = ""
    tf_op: str = ""      # the HLO `op_name`: the JAX name stack and primitive
    flops: int = 0       # the compiler's counts, 0 for a Pallas call that
    bytes_accessed: int = 0  # gives no cost estimate
    program_id: int | None = None

    @property
    def family(self) -> str | None:
        """A custom call's kernel family: its display name less the
        trailing number (`splash_mqa_fwd_residuals`, `gmm`, `hefl.encrypt`)."""
        if self.hlo_category != "custom-call":
            return None
        return re.sub(r"\.\d+$", "", self.display_name)


@dataclasses.dataclass
class DeviceOps:
    """The `XLA Ops` line of one device plane."""

    plane: str
    ops: dict[int, Op]          # by metadata id
    programs: dict[int, str]    # program_id -> its name on `XLA Modules`
    op_id: np.ndarray           # per event: the metadata id
    start_ps: np.ndarray        # on the profiler's clock (line stamp + offset)
    dur_ps: np.ndarray
    self_ps: np.ndarray         # duration less the events nested in it
    leaf: np.ndarray            # no event is nested in it

    def events(self) -> Iterator[dict]:
        """Each event with its op's metadata, times in ns as `ProfileData`
        reports them."""
        for i, k in enumerate(self.op_id.tolist()):
            op = self.ops[k]
            yield {
                "plane": self.plane, "name": op.name,
                "display_name": op.display_name,
                "hlo_category": op.hlo_category, "tf_op": op.tf_op,
                "flops": op.flops, "bytes_accessed": op.bytes_accessed,
                "program_id": op.program_id,
                "program": self.programs.get(op.program_id),
                "start_ns": float(self.start_ps[i] // 1000),
                "dur_ns": float(self.dur_ps[i] // 1000),
                "self_ns": float(self.self_ps[i]) / 1000.0,
                "leaf": bool(self.leaf[i]),
            }


def _self_times(start: np.ndarray, dur: np.ndarray):
    """-> (self_ps, leaf) of events on one line. An event nested in another
    (it starts before the other ends) is taken off its parent's time."""
    n = len(start)
    self_ps, leaf = dur.copy(), np.ones(n, bool)
    order = np.lexsort((-dur, start))
    ends = (start + dur).tolist()
    starts, durs = start.tolist(), dur.tolist()
    stack: list[int] = []
    for i in order.tolist():
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            parent = stack[-1]
            self_ps[parent] -= min(durs[i], ends[parent] - starts[i])
            leaf[parent] = False
        stack.append(i)
    return np.maximum(self_ps, 0), leaf


_PROGRAM_RE = re.compile(r"^(.*)\((\d+)\)$")


def _device_plane(buf: bytes, name: str, lines, metas, stat_names) -> DeviceOps:
    found = {}
    for span in lines:
        line = _line(buf, span)
        if line[0] in (OPS_LINE, MODULES_LINE):
            found[line[0]] = line
    if OPS_LINE not in found:
        raise TraceParseError(f"plane {name} has no {OPS_LINE!r} line")
    names: dict[int, tuple[str, str, list]] = {}
    for span in metas:
        key, value = _map_entry(buf, span)
        text = display = ""
        stats = []
        for f, _, v in _fields(buf, *value):
            if f == 2:
                text = _text(buf, v)
            elif f == 4:
                display = _text(buf, v)
            elif f == 5:
                stats.append(v)
        names[key] = (text, display, stats)
    _, stamp, spans = found[OPS_LINE]
    op_id, offs, durs = _events(buf, spans)
    ops = {}
    for key in np.unique(op_id).tolist():
        text, display, stats = names.get(key, ("", "", []))
        got = dict(_stat(buf, s, stat_names) for s in stats)
        ops[key] = Op(
            name=text, display_name=display or text,
            hlo_category=str(got.get("hlo_category") or ""),
            tf_op=str(got.get("tf_op") or ""),
            flops=int(got.get("flops") or 0),
            bytes_accessed=int(got.get("bytes_accessed") or 0),
            program_id=(int(got["program_id"]) & ((1 << 64) - 1)
                        if got.get("program_id") is not None else None),
        )
    programs = {}
    if MODULES_LINE in found:
        for key in np.unique(_events(buf, found[MODULES_LINE][2])[0]).tolist():
            m = _PROGRAM_RE.match(names.get(key, ("",))[0])
            if m:
                programs[int(m.group(2))] = m.group(1)
    start = offs + stamp * 1000
    self_ps, leaf = _self_times(start, durs)
    return DeviceOps(plane=name, ops=ops, programs=programs, op_id=op_id,
                     start_ps=start, dur_ps=durs, self_ps=self_ps, leaf=leaf)


def _host_spans(buf: bytes, lines, metas) -> dict[str, list[tuple[int, int]]]:
    """The `hefl.*` annotations of a host plane (`obs.spans`), [lo, hi) ps."""
    wanted = {}
    for span in metas:
        key, value = _map_entry(buf, span)
        for f, _, v in _fields(buf, *value):
            if f == 2 and buf.startswith(scopes.PREFIX.encode(), v[0], v[1]):
                wanted[key] = _text(buf, v)
    out: dict[str, list[tuple[int, int]]] = {}
    if not wanted:
        return out
    for span in lines:
        _, stamp, spans = _line(buf, span)
        ids, offs, durs = _events(buf, spans)
        for i in np.flatnonzero(np.isin(ids, list(wanted))).tolist():
            lo = int(offs[i]) + stamp * 1000
            out.setdefault(wanted[int(ids[i])], []).append((lo, lo + int(durs[i])))
    return out


def xplane_under(path: str) -> str:
    """`path` if it is a file, else the newest `.xplane.pb` under the
    profiler's logdir (<logdir>/plugins/profile/<run>/<host>.xplane.pb)."""
    if os.path.isfile(path):
        return path
    hits = [os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith(".xplane.pb")]
    if not hits:
        raise TraceParseError(
            f"no .xplane.pb under {path!r}: did the profiler run?")
    return max(hits, key=os.path.getmtime)


def read_xplane(path: str) -> tuple[list[DeviceOps], dict[str, list]]:
    """An `.xplane.pb` (or `.xplane.pb.gz`) -> (the op line of every TPU
    device plane, the host planes' `hefl.*` annotations by name)."""
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            buf = f.read()
    except (OSError, EOFError) as e:
        raise TraceParseError(f"unreadable trace {path!r}: {e}") from e
    devices, host = [], {}
    for f, wire, span in _fields(buf, 0, len(buf)):
        if f != 1 or wire != _BYTES:  # XSpace.planes
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for f2, _, v in _fields(buf, *span):
            if f2 == 2:
                name = _text(buf, v)
                if not name.startswith((DEVICE_PLANE, HOST_PLANE)):
                    break  # e.g. /host:metadata, the programs' HLO protos
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                metas.append(v)
            elif f2 == 5:
                key, value = _map_entry(buf, v)
                for f3, _, v3 in _fields(buf, *value):
                    if f3 == 2:
                        stat_names[key] = _text(buf, v3)
        if name.startswith(DEVICE_PLANE):
            devices.append(_device_plane(buf, name, lines, metas, stat_names))
        elif name.startswith(HOST_PLANE):
            for k, v in _host_spans(buf, lines, metas).items():
                host.setdefault(k, []).extend(v)
    if not devices:
        raise NoDevicePlane(
            f"trace {path!r} holds no {DEVICE_PLANE}* plane: the profiler "
            "saw no TPU (wrong file, or a CPU run)")
    return devices, host


# --------------------------------------------------------------------------
# The record.
# --------------------------------------------------------------------------


def _union_s(intervals) -> float:
    total, end = 0, -1
    for lo, hi in sorted(intervals):
        if hi > end:
            total += hi - max(lo, end)
            end = hi
    return total * 1e-12


def trace_attribution(trace: str) -> dict[str, Any]:
    """Where a traced run's device seconds went: THE trace_attribution record.

    trace: an `.xplane.pb` or the profiler's logdir. Every number of seconds
    is SELF time (see the module's docstring), a mean over the device planes.

    -> {
      "paths": {"hefl.sgd_core/hefl.conv": {"device_seconds",
               "backward_seconds", "op_events", "flops", "bytes_accessed"}}
               by the op's whole chain of `hefl.*` scopes, outer to inner
               ("" for none); backward: the ops whose `tf_op` holds
               `transpose(`; flops and bytes are the compiler's, over leaf
               ops only (a `while`'s own stats count its body again). The
               next three are sums over it:
      "rows": {scope: {"device_seconds", "op_events"}}  by the DEEPEST scope
               (`scopes.scope_of`), in `scopes.PHASES`' order,
      "under": {scope: {"device_seconds", "backward_seconds"}}  ops with the
               scope at ANY depth (`hefl.sgd_core`, `hefl.val` and
               `hefl.evaluate` hold the model's scopes),
      "unattributed_s": ops under no `hefl.*` scope,
      "families": {family: {"device_seconds", "op_events"}}  custom calls
               (the Pallas kernels) by display name less its number,
      "modules": {program: device_seconds}  by `program_id`, named by
               `XLA Modules`,
      "backward_s": all ops whose `tf_op` holds `transpose(`,
      "device_total_s": the device's busy seconds: what each of `paths`,
               `rows` + `unattributed_s` and `modules` adds up to,
      "flops", "bytes_accessed": over all leaf ops,
      "host_rows": {span: {"seconds", "spans"}}  `hefl.*` host annotations
               (`obs.spans`: phases, steps, waits), unions, on the same clock,
      "op_events", "planes", "decode_s", "trace_file", "source": "xplane",
    }
    """
    t0 = time.perf_counter()
    path = xplane_under(trace)
    devices, host = read_xplane(path)
    n = len(devices)
    paths: dict[str, dict] = {}
    families: dict[str, dict] = {}
    modules: dict[str, float] = {}
    events, leaf_s, leaf_tf_op_s = 0, 0.0, 0.0

    def add(table, key, **fields):
        row = table.setdefault(key, dict.fromkeys(fields, 0))
        for k, v in fields.items():
            row[k] += v

    for dev in devices:
        ids, inverse = np.unique(dev.op_id, return_inverse=True)
        self_s = np.bincount(inverse, dev.self_ps, len(ids)) * 1e-12 / n
        count = np.bincount(inverse, minlength=len(ids))
        leaves = np.bincount(inverse, dev.leaf, len(ids))
        work = np.bincount(inverse, dev.self_ps * dev.leaf, len(ids)) * 1e-12
        for k, s, c, lf, w in zip(ids.tolist(), self_s.tolist(), count.tolist(),
                                  leaves.tolist(), work.tolist()):
            op = dev.ops[k]
            events += c
            leaf_s += w
            leaf_tf_op_s += w if op.tf_op else 0.0
            add(paths, "/".join(dict.fromkeys(scopes.scopes_in(op.tf_op))),
                device_seconds=s,
                backward_seconds=s if BACKWARD in op.tf_op else 0.0,
                op_events=c, flops=int(op.flops * lf),
                bytes_accessed=int(op.bytes_accessed * lf))
            if op.family:
                add(families, op.family, device_seconds=s, op_events=c)
            program = dev.programs.get(op.program_id, str(op.program_id))
            modules[program] = modules.get(program, 0.0) + s
    if events == 0:
        raise TraceParseError(f"trace {path!r}: the {OPS_LINE!r} line is empty")
    if 2 * leaf_tf_op_s < leaf_s:  # by the leaf ops' seconds: the compiler's
        # own copies and async pairs carry none and outnumber a token round's
        # ops, and a loop's own event is bookkeeping
        raise NoScopeMetadata(
            f"trace {path!r}: leaf ops of {leaf_tf_op_s:.6f} of {leaf_s:.6f} "
            "device seconds carry `tf_op`: the programs were compiled without "
            "metadata")
    rows: dict[str, dict] = {}
    under: dict[str, dict] = {}
    for chain, row in paths.items():
        held = chain.split("/") if chain else []
        if held:
            add(rows, held[-1], device_seconds=row["device_seconds"],
                op_events=row["op_events"])
        for scope in held:
            add(under, scope, device_seconds=row["device_seconds"],
                backward_seconds=row["backward_seconds"])
    order = [p for p in scopes.PHASES if p in rows] + sorted(
        set(rows) - set(scopes.PHASES))

    def total(key):
        return sum(row[key] for row in paths.values())

    def by_seconds(table):
        return dict(sorted(table.items(), key=lambda kv: -kv[1]["device_seconds"]))

    return {
        "paths": by_seconds(paths),
        "rows": {p: rows[p] for p in order},
        "under": dict(sorted(under.items())),
        "unattributed_s": paths.get("", {}).get("device_seconds", 0.0),
        "families": by_seconds(families),
        "modules": dict(sorted(modules.items())),
        "backward_s": total("backward_seconds"),
        "device_total_s": total("device_seconds"),
        "flops": total("flops"), "bytes_accessed": total("bytes_accessed"),
        "host_rows": {
            name: {"seconds": _union_s(iv), "spans": len(iv)}
            for name, iv in sorted(host.items())
        },
        "op_events": events, "planes": n,
        "decode_s": round(time.perf_counter() - t0, 3),
        "trace_file": path, "source": "xplane",
    }


def format_table(record: dict[str, Any]) -> str:
    """The record as the table `hefl-train --profile DIR` prints."""
    busy = record["device_total_s"] or 1.0
    out = [
        f"device seconds of {record['trace_file']}: {record['op_events']} op "
        f"events on {record['planes']} device plane(s), read in "
        f"{record['decode_s']} s. Self time: an op's duration less the ops "
        "nested in it; a fusion counts under the scope of its root.",
        f"{'scopes, outer/inner':<52}{'seconds':>11}{'share':>7}"
        f"{'backward':>11}{'ops':>8}"]
    for name, row in record["paths"].items():
        out.append(f"{name or '(no hefl.* scope)':<52}"
                   f"{row['device_seconds']:>11.6f}"
                   f"{100 * row['device_seconds'] / busy:>6.1f}%"
                   f"{row['backward_seconds']:>11.6f}{row['op_events']:>8}")
    out.append(f"{'busy':<52}{busy:>11.6f}{'':>7}{record['backward_s']:>11.6f}"
               f"{record['op_events']:>8}")
    out.append(f"{'under a scope, at any depth':<52}{'seconds':>11}{'':>7}"
               f"{'backward':>11}")
    for name, row in record["under"].items():
        out.append(f"{name:<52}{row['device_seconds']:>11.6f}{'':>7}"
                   f"{row['backward_seconds']:>11.6f}")
    if record["families"]:
        out.append(f"{'kernel family (custom calls)':<52}{'seconds':>11}"
                   f"{'':>18}{'calls':>8}")
        for name, row in record["families"].items():
            out.append(f"{name:<52}{row['device_seconds']:>11.6f}{'':>18}"
                       f"{row['op_events']:>8}")
    out.append(f"{'program':<52}{'seconds':>11}")
    for name, s in record["modules"].items():
        out.append(f"{name:<52}{s:>11.6f}")
    out.append(f"{'host span (obs.spans)':<52}{'seconds':>11}{'':>18}{'spans':>8}")
    for name, row in record["host_rows"].items():
        out.append(f"{name:<52}{row['seconds']:>11.6f}{'':>18}{row['spans']:>8}")
    return "\n".join(out)
