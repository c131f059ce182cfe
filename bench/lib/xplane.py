"""A reader for the profiler's ``*.xplane.pb`` that also gives what
``jax.profiler.ProfileData`` leaves out: the stats of an event's METADATA.
On a TPU the scope path of a device op (``tf_op``:
``jit(paged_decode_round)/while/body/closed_call/attn/dot_general:``) is
kept once per op there, not on each event.

The file is one ``XSpace`` message (tsl/profiler/protobuf/xplane.proto);
this walks the protobuf wire format directly, taking the few fields the
benchmark reads and skipping the rest by length, so it needs neither
TensorFlow's generated classes nor JAX.  Field numbers:

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5  (maps: key=1 value=2)
    XLine.name=2 .timestamp_ns=3 .events=4
    XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3 .stats=4
    XStat.metadata_id=1 .double=2 .uint64=3 .int64=4 .str=5 .bytes=6 .ref=7
    XEventMetadata.id=1 .name=2 .stats=5        XStatMetadata.id=1 .name=2
"""

from __future__ import annotations

import struct
from typing import Callable, Dict, Iterator, Optional, Tuple


def fields(buf: bytes, pos: int, end: int) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: a varint's
    value, the ``(start, end)`` of a length-delimited field, the raw 8 or
    4 bytes of a fixed one."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        num, wire = key >> 3, key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield num, 0, val
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield num, 2, (pos, pos + size)
            pos += size
        elif wire == 1:
            yield num, 1, buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            yield num, 5, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span, stat_names: Dict[int, str]):
    """One XStat -> ``(name, value)``; a ``ref`` value is a string kept in
    the stat-metadata table."""
    name, value = "", None
    for num, wire, v in fields(buf, *span):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = _text(buf, v)
        elif num == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf: bytes, span):
    key, value = 0, None
    for num, _, v in fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def read_planes(path: str, want_plane: Callable[[str], bool],
                want_line: Callable[[str, str], bool],
                want_event: Optional[Callable[[str, str], bool]] = None,
                own_stats: Callable[[str], bool] = lambda plane: True
                ) -> list:
    """The planes of an xplane file as plain lists:

        [{"name", "lines": [{"name", "events": [
            [name, start_ns, dur_ns, stats], ...]}]}]

    ``stats`` is a dict of the event's own stats over its metadata's
    (``tf_op``, ``hlo_category``, ``flops``, ``bytes_accessed``...; a
    bytes value reads None).  ``want_plane(name)``, ``want_line(plane,
    line)`` and ``want_event(plane, event name)`` choose what is decoded,
    the rest is skipped by length; where ``own_stats(plane)`` is false an
    event gets its metadata's stats alone (one shared dict per op: a device
    plane has a few hundred thousand events)."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for num, wire, span in fields(buf, 0, len(buf)):
        if num != 1 or wire != 2:
            continue
        name, lines, ev_meta, stat_meta = "", [], [], []
        for pnum, pwire, v in fields(buf, *span):
            if pnum == 2:
                name = _text(buf, v)
            elif pnum == 3:
                lines.append(v)
            elif pnum == 4:
                ev_meta.append(v)
            elif pnum == 5:
                stat_meta.append(v)
        if not want_plane(name):
            continue
        stat_names: Dict[int, str] = {}
        for entry in stat_meta:
            key, value = _map_entry(buf, entry)
            for mnum, _, v in fields(buf, *value):
                if mnum == 2:
                    stat_names[key] = _text(buf, v)
        meta: Dict[int, tuple] = {}          # id -> (name, stats spans)
        for entry in ev_meta:
            key, value = _map_entry(buf, entry)
            mname, mstats = "", []
            for mnum, _, v in fields(buf, *value):
                if mnum == 2:
                    mname = _text(buf, v)
                elif mnum == 5:
                    mstats.append(v)
            meta[key] = (mname, mstats)
        meta_stats: Dict[int, dict] = {}     # decoded on first use
        with_own = own_stats(name)
        out_lines = []
        for lspan in lines:
            lname, ts_ns, events = "", 0, []
            for lnum, _, v in fields(buf, *lspan):
                if lnum == 2:
                    lname = _text(buf, v)
                elif lnum == 3:
                    ts_ns = _signed(v)
                elif lnum == 4:
                    events.append(v)
            if not want_line(name, lname):
                continue
            decoded = []
            for espan in events:
                mid = off_ps = dur_ps = 0
                own = []
                for enum, _, v in fields(buf, *espan):
                    if enum == 1:
                        mid = v
                    elif enum == 2:
                        off_ps = v
                    elif enum == 3:
                        dur_ps = v
                    elif enum == 4 and with_own:
                        own.append(v)
                ename, mstats = meta.get(mid, (str(mid), []))
                if want_event is not None and not want_event(name, ename):
                    continue
                if mid not in meta_stats:
                    meta_stats[mid] = dict(
                        _stat(buf, s, stat_names) for s in mstats)
                stats = meta_stats[mid]
                if own:
                    stats = {**stats, **dict(
                        _stat(buf, s, stat_names) for s in own)}
                decoded.append([ename, ts_ns + off_ps / 1000.0,
                                dur_ps / 1000.0, stats])
            out_lines.append({"name": lname, "events": decoded})
        planes.append({"name": name, "lines": out_lines})
    return planes
