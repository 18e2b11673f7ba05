"""What `jax.profiler.ProfileData` does not hand out: the metadata of a
trace's events. On the chip a device operation's event is named by its HLO
line without `metadata={...}`, and its own stats are times only; the scope
path the program gave the operation (`jax.named_scope`, carried as the HLO's
`op_name`) sits in the `tf_op` stat of the event's METADATA entry, which the
Python reader skips. This reads just those entries out of the `.xplane.pb`
wire format: the planes' lines (nearly all of the file) are stepped over, not
decoded, so the cost follows the number of distinct operations, not events.

Fields, from tsl/profiler/protobuf/xplane.proto: XSpace.planes=1;
XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5 (maps: key=1,
value=2); XEventMetadata.id=1 .name=2 .stats=5; XStatMetadata.id=1 .name=2;
XStat.metadata_id=1 .str_value=5 .ref_value=7.
"""

from __future__ import annotations

from pathlib import Path
from typing import Iterator

SCOPE_STAT = "tf_op"


def _varint(buf: bytes, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf: bytes) -> Iterator[tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value comes back as a memoryview slice, undecoded."""
    view, at, end = memoryview(buf), 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = view[at : at + size], at + size
        elif wire == 1:
            value, at = view[at : at + 8], at + 8
        elif wire == 5:
            value, at = view[at : at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _map_value(entry: bytes) -> bytes:
    return next(bytes(v) for n, w, v in _fields(entry) if n == 2 and w == 2)


def op_scopes(path: Path, plane_prefix: str) -> dict[str, str]:
    """Event name -> scope path (`jit(f)/while/body/.../attention/dot_general`)
    for every operation of the planes whose name starts with `plane_prefix`.
    An operation the compiler made itself (a copy, a bitcast) has no entry."""
    out: dict[str, str] = {}
    for number, wire, plane in _fields(Path(path).read_bytes()):
        if number != 1 or wire != 2:
            continue
        plane = bytes(plane)
        name, events, stat_names = "", [], {}
        for n, w, v in _fields(plane):
            if n == 2 and w == 2:
                name = bytes(v).decode()
            elif n == 4 and w == 2:
                events.append(_map_value(bytes(v)))
            elif n == 5 and w == 2:
                meta = {fn: fv for fn, _, fv in _fields(_map_value(bytes(v)))}
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith(plane_prefix):
            continue
        for event in events:
            event_name, scope = "", None
            for n, w, v in _fields(event):
                if n == 2 and w == 2:
                    event_name = bytes(v).decode()
                elif n == 5 and w == 2:
                    stat = {fn: fv for fn, _, fv in _fields(bytes(v))}
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        scope = bytes(stat[5]).decode()
                    elif 7 in stat:
                        scope = stat_names.get(stat[7])
            if event_name and scope:
                out[event_name] = scope
    return out
