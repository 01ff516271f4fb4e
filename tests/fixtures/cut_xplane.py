"""Cut a chip's `.xplane.pb` down to a fixture of the tests.

    python tests/fixtures/cut_xplane.py IN.xplane.pb[.gz] OUT.xplane.pb [HEAD]

Keeps, of every TPU device plane: the `XLA Modules` line whole; of the
`XLA Ops` line the first HEAD events (default 700) of the program that ran
longest (the round program) and every event of the programs whose name
holds `decrypt` or `decode`; the event metadata those events name, with
the stats the reader uses (and `source`, `deduplicated_name`,
`model_flops`), without `source_stack`, `shape_with_layout` and the like;
the stat metadata whole. Of the host planes: the `hefl.*` annotations.
Everything else (the other lines, the `/host:metadata` plane's HLO protos)
goes. What is kept is copied byte for byte, so the fixture is what the
profiler wrote and `jax.profiler.ProfileData` reads it too.
"""

from __future__ import annotations

import gzip
import sys

from hefl_tpu.obs import trace
from hefl_tpu.obs.trace import _fields, _map_entry, _text

KEPT_STATS = {"hlo_category", "tf_op", "flops", "model_flops", "bytes_accessed",
              "program_id", "source", "deduplicated_name"}
DECRYPT_PROGRAMS = ("decrypt", "decode")


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def delimited(field: int, payload: bytes) -> bytes:
    return varint(field << 3 | 2) + varint(len(payload)) + payload


def raw(buf: bytes, field: int, wire: int, value) -> bytes:
    """A field as it was read, written back."""
    if wire == 0:
        return varint(field << 3) + varint(value)
    if wire == 2:
        return delimited(field, buf[value[0]:value[1]])
    return varint(field << 3 | wire) + bytes(value)


def cut_metadata(buf, span, stat_names) -> bytes:
    """An event_metadata map entry with the kept stats alone."""
    key, value = _map_entry(buf, span)
    body = b""
    for f, w, v in _fields(buf, *value):
        if f == 5:  # stats
            stat_id = next(x for g, _, x in _fields(buf, *v) if g == 1)
            if stat_names.get(stat_id) not in KEPT_STATS:
                continue
        body += raw(buf, f, w, v)
    return delimited(4, varint(1 << 3) + varint(key) + delimited(2, body))


def cut_line(buf, span, keep) -> bytes:
    """An XLine with the events `keep(index, span)` picks."""
    body, i = b"", 0
    for f, w, v in _fields(buf, *span):
        if f == 4:
            i += 1
            if not keep(i - 1, v):
                continue
        body += raw(buf, f, w, v)
    return delimited(3, body)


def event_id(buf, span) -> int:
    return next(v for f, _, v in _fields(buf, *span) if f == 1)


def cut_plane(buf, span, head: int) -> bytes | None:
    fields = list(_fields(buf, *span))
    name = next(_text(buf, v) for f, _, v in fields if f == 2)
    device = name.startswith(trace.DEVICE_PLANE)
    if not device and not name.startswith(trace.HOST_PLANE):
        return None
    stat_names = {}
    for f, _, v in fields:
        if f == 5:
            key, value = _map_entry(buf, v)
            stat_names[key] = next(
                _text(buf, x) for g, _, x in _fields(buf, *value) if g == 2)
    wanted: set[int] = set()
    body = b""
    if device:
        dev = trace._device_plane(
            buf, name, [v for f, _, v in fields if f == 3],
            [v for f, _, v in fields if f == 4], stat_names)
        seconds: dict[int, int] = {}
        for op_id, ps in zip(dev.op_id.tolist(), dev.self_ps.tolist()):
            pid = dev.ops[op_id].program_id
            seconds[pid] = seconds.get(pid, 0) + ps
        round_program = max(seconds, key=seconds.get)
        small = {pid for pid, prog in dev.programs.items()
                 if any(s in prog for s in DECRYPT_PROGRAMS)}
        taken = [0]

        def keep_op(i, ev):
            pid = dev.ops[event_id(buf, ev)].program_id
            if pid == round_program and taken[0] < head:
                taken[0] += 1
            elif pid not in small:
                return False
            wanted.add(event_id(buf, ev))
            return True

        def keep_module(i, ev):
            wanted.add(event_id(buf, ev))
            return True
    else:
        hefl = set()
        for f, _, v in fields:
            if f == 4:
                key, value = _map_entry(buf, v)
                text = next((_text(buf, x) for g, _, x in _fields(buf, *value)
                             if g == 2), "")
                if text.startswith("hefl."):
                    hefl.add(key)

        def keep_host(i, ev):
            if event_id(buf, ev) in hefl:
                wanted.add(event_id(buf, ev))
                return True
            return False
    for f, w, v in fields:
        if f == 3:
            line = trace._line(buf, v)[0]
            if device and line == trace.OPS_LINE:
                body += cut_line(buf, v, keep_op)
            elif device and line == trace.MODULES_LINE:
                body += cut_line(buf, v, keep_module)
            elif not device:
                body += cut_line(buf, v, keep_host)
        elif f == 4:
            continue  # after the lines, once `wanted` is known
        else:
            body += raw(buf, f, w, v)
    for f, _, v in fields:
        if f == 4 and _map_entry(buf, v)[0] in wanted:
            body += cut_metadata(buf, v, stat_names)
    return delimited(1, body)


def main(src: str, dst: str, head: int = 700) -> None:
    opener = gzip.open if src.endswith(".gz") else open
    with opener(src, "rb") as f:
        buf = f.read()
    out = b""
    for f, w, v in _fields(buf, 0, len(buf)):
        if f == 1:
            out += cut_plane(buf, v, head) or b""
    with open(dst, "wb") as f:
        f.write(out)
    print(f"{dst}: {len(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *map(int, sys.argv[3:4]))
