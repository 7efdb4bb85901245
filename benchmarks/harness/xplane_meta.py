"""Per-op metadata out of an `.xplane.pb`, read off the protobuf wire.

The profiler writes, for every HLO op of a device plane, event
metadata that `jax.profiler.ProfileData` does not expose: XLA's own
`hlo_category` ("convolution fusion", "data formatting", ...) and the
`source` file:line of the program that produced the op (`tf_op`, the
JAX name stack, is there too). This module reads those two, with a
wire-format walker instead of a protobuf dependency. Field numbers are
those of tsl/profiler/protobuf/xplane.proto:

    XSpace.planes = 1
    XPlane.name = 2, .lines = 3 (skipped), .event_metadata = 4,
          .stat_metadata = 5      (both map<int64, message>: key 1, value 2)
    XEventMetadata.name = 2, .stats = 5
    XStatMetadata.id = 1, .name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (a string
          interned as the name of stat_metadata[ref])
"""

from __future__ import annotations

from typing import Iterator

WANTED = ("hlo_category", "source")
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf: memoryview, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: memoryview) -> Iterator[tuple[int, int, object]]:
    """(field number, wire type, value) of one message; length-delimited
    values come back as memoryviews, nothing is copied."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, pos = _varint(buf, pos)
        elif wire == _BYTES:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == _FIXED64:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == _FIXED32:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an "
                             f"xplane protobuf")
        yield number, wire, value


def _map_values(entry: memoryview) -> memoryview | None:
    for number, wire, value in _fields(entry):
        if number == 2 and wire == _BYTES:
            return value
    return None


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def op_metadata(path: str) -> dict[str, dict[str, dict[str, str]]]:
    """-> {plane name: {op text: {stat name: value}}} for the stats in
    WANTED, for every plane that has event metadata."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: dict[str, dict[str, dict[str, str]]] = {}
    for number, wire, plane in _fields(space):
        if number != 1 or wire != _BYTES:
            continue
        name, events, stat_names = "", [], {}
        for f, w, value in _fields(plane):
            if f == 2 and w == _BYTES:
                name = _text(value)
            elif f == 4 and w == _BYTES:
                events.append(_map_values(value))
            elif f == 5 and w == _BYTES:
                meta = _map_values(value)
                sid, sname = 0, ""
                for g, _, v in _fields(meta):
                    if g == 1:
                        sid = v
                    elif g == 2:
                        sname = _text(v)
                stat_names[sid] = sname
        wanted_ids = {i for i, n in stat_names.items() if n in WANTED}
        ops: dict[str, dict[str, str]] = {}
        for meta in events:
            text, stats = "", {}
            for f, w, value in _fields(meta):
                if f == 2 and w == _BYTES:
                    text = _text(value)
                elif f == 5 and w == _BYTES:
                    sid, got = 0, None
                    for g, gw, v in _fields(value):
                        if g == 1:
                            sid = v
                        elif g == 5 and gw == _BYTES:
                            got = _text(v)
                        elif g == 7 and gw == _VARINT:
                            got = stat_names.get(v, "")
                    if sid in wanted_ids and got is not None:
                        stats[stat_names[sid]] = got
            if text and stats:
                ops[text] = stats
        if ops:
            out[name] = ops
    return out
