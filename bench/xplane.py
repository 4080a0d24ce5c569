"""Read the per-op metadata of a profiler trace (``.xplane.pb``).

    python bench/xplane.py <file.xplane.pb>   # print each op's tf_op

``jax.profiler.ProfileData`` gives each op event its HLO text and its
times, but not the metadata the profiler stores beside it: the op's
``tf_op`` (its op_name, where ``jax.named_scope`` lands), ``source``,
``hlo_category``, ``flops`` and ``bytes_accessed``. This module decodes
those from the protobuf wire format itself, reading only the fields it
needs of the XSpace schema (``tsl/profiler/protobuf/xplane.proto``), so
it needs no generated code and no package beyond the standard library:

    XSpace          planes = 1
    XPlane          name = 2, lines = 3, event_metadata = 4 (map),
                    stat_metadata = 5 (map)
    XEventMetadata  name = 2, stats = 5
    XStat           metadata_id = 1, uint64 = 3, int64 = 4, str = 5,
                    ref = 7 (a string interned as an XStatMetadata name)
    XStatMetadata   id = 1, name = 2

A map entry is a message with the key as field 1 and the value as
field 2. The lines, which hold the events, are skipped whole.
"""

from __future__ import annotations

import re
import sys
from typing import Dict, Iterator, Optional, Pattern, Tuple, Union

Value = Union[int, str, None]


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: memoryview) -> Iterator[Tuple[int, Union[int, memoryview]]]:
    """(field number, value) of each field of one message: an int for
    a varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _text(b: memoryview) -> str:
    return bytes(b).decode("utf-8", "replace")


def _map_value(entry: memoryview) -> memoryview:
    for number, value in fields(entry):
        if number == 2:
            return value
    return memoryview(b"")


def _stat(raw: memoryview, stat_names: Dict[int, str]) -> Tuple[str, Value]:
    name, value = "", None
    for number, v in fields(raw):
        if number == 1:
            name = stat_names.get(v, "")
        elif number == 3:
            value = v
        elif number == 4:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif number == 5:
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v)
    return name, value


def _plane(buf: memoryview) -> Dict[str, Dict[str, Value]]:
    raw_events = []
    stat_names: Dict[int, str] = {}
    for number, value in fields(buf):
        if number == 4:
            raw_events.append(_map_value(value))
        elif number == 5:
            sid, sname = 0, ""
            for n2, v2 in fields(_map_value(value)):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = _text(v2)
            stat_names[sid] = sname
    events: Dict[str, Dict[str, Value]] = {}
    for raw in raw_events:
        ename, stats = "", {}
        for number, value in fields(raw):
            if number == 2:
                ename = _text(value)
            elif number == 5:
                k, v = _stat(value, stat_names)
                stats[k] = v
        # the same op can have several metadata entries (the async line
        # repeats a copy-start's); keep a stat only where they agree
        if ename in events:
            old = events[ename]
            events[ename] = {k: v for k, v in old.items()
                             if stats.get(k) == v}
        else:
            events[ename] = stats
    return events


def event_metadata(path: str, planes: Optional[Pattern] = None
                   ) -> Dict[str, Dict[str, Dict[str, Value]]]:
    """Per plane (those whose name ``planes`` matches, or every one),
    each event name (for a device op: its HLO text, as
    ``ProfileData`` names the event) -> the stats of its metadata."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for number, value in fields(buf):
        if number != 1:
            continue
        name = ""
        for n2, v2 in fields(value):
            if n2 == 2:
                name = _text(v2)
                break
        if planes is None or planes.search(name):
            out[name] = _plane(value)
    return out


if __name__ == "__main__":
    for plane, evs in event_metadata(sys.argv[1],
                                     re.compile(r"^/device:")).items():
        print(plane)
        for ename, stats in evs.items():
            print(f"  {stats.get('tf_op')!r:60} {ename[:100]}")
