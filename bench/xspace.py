"""A reader of the profiler's ``.xplane.pb`` wire format, for what
``jax.profiler.ProfileData`` does not expose: the stats of each event's
metadata (``tf_op``, ``hlo_category``, ``source``).

The file is one ``XSpace`` protobuf message (``tsl/profiler/protobuf/
xplane.proto``). Only the fields read here are decoded; the rest are
skipped by their wire type::

    XSpace         planes=1
    XPlane         id=1 name=2 lines=3 event_metadata=4 (map) stat_metadata=5 (map)
    XLine          name=2 timestamp_ns=3 events=4
    XEvent         metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XEventMetadata id=1 name=2 stats=5
    XStatMetadata  id=1 name=2
    XStat          metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7

A line's events are decoded only for the lines asked for, so a trace with
a large host plane reads at the cost of its device ops.
"""
from __future__ import annotations

import dataclasses
import gzip
import struct
from pathlib import Path

_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes):
    """``(field number, wire type, value)`` of each field of one message;
    a length-delimited value is its bytes, a fixed one its raw 8 or 4."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == _VARINT:
            val, i = _varint(buf, i)
        elif wt == _LEN:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wt == _I64:
            val, i = buf[i:i + 8], i + 8
        elif wt == _I32:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class Event:
    metadata_id: int
    start_ns: float     # the line's timestamp plus the event's offset
    duration_ps: int


@dataclasses.dataclass
class Line:
    name: str
    events: list[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: list[Line]
    metadata: dict[int, tuple[str, dict[str, object]]]  # id -> (name, stats)


def _stat(buf: bytes, stat_names: dict[int, str]) -> tuple[str, object]:
    name, value = "", None
    for num, wt, val in fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num in (3, 4):
            value = val if num == 3 else _signed(val)
        elif num in (5, 6):
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, "")
    return name, value


def _map_entries(entries: list[bytes]):
    """``(key, value bytes)`` of each entry of a protobuf map field."""
    for entry in entries:
        key, value = 0, b""
        for num, _, val in fields(entry):
            if num == 1:
                key = val
            elif num == 2:
                value = val
        yield key, value


def _plane(buf: bytes, line_names: frozenset[str]) -> Plane:
    name, lines, ev_meta, st_meta = "", [], [], []
    for num, _, val in fields(buf):
        if num == 2:
            name = bytes(val).decode()
        elif num == 3:
            lines.append(val)
        elif num == 4:
            ev_meta.append(val)
        elif num == 5:
            st_meta.append(val)
    stat_names = {}
    for key, val in _map_entries(st_meta):
        stat_names[key] = next((bytes(v).decode() for n, _, v in fields(val)
                                if n == 2), "")
    metadata = {}
    for key, val in _map_entries(ev_meta):
        md_name, stats = "", {}
        for num, _, v in fields(val):
            if num == 2:
                md_name = bytes(v).decode("utf-8", "replace")
            elif num == 5:
                k, s = _stat(v, stat_names)
                stats[k] = s
        metadata[key] = (md_name, stats)
    out = []
    for raw in lines:
        lname, ts, events = "", 0, []
        for num, _, val in fields(raw):
            if num == 2:
                lname = bytes(val).decode()
            elif num == 3:
                ts = _signed(val)
            elif num == 4:
                events.append(val)
        if lname not in line_names:
            continue
        decoded = []
        for ev in events:
            mid = off = dur = 0
            for num, wt, val in fields(ev):
                if num == 1:
                    mid = val
                elif num == 2:
                    off = _signed(val)
                elif num == 3:
                    dur = _signed(val)
            decoded.append(Event(mid, ts + off / 1e3, dur))
        out.append(Line(lname, decoded))
    return Plane(name, out, metadata)


def read_planes(path: str | Path, plane_names, line_names) -> list[Plane]:
    """The planes of an ``.xplane.pb`` (or ``.xplane.pb.gz``) file whose
    names ``plane_names(name)`` accepts, with their event metadata and the
    lines named in ``line_names``."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for num, _, val in fields(buf):
        if num != 1:
            continue
        name = next((bytes(v).decode() for n, _, v in fields(val) if n == 2),
                    "")
        if plane_names(name):
            planes.append(_plane(val, frozenset(line_names)))
    return planes
