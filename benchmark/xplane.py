"""A reader of the profiler's .xplane.pb that needs nothing but the
file: the protobuf wire format, decoded for the few messages of
tsl/profiler/protobuf/xplane.proto that the reduction uses.

jax.profiler.ProfileData shows an event's own stats only; the stats
that say what an op IS (`tf_op`, `hlo_category`) sit on the event's
metadata, so the planes are read here.

    XSpace.planes=1
    XPlane: name=2 lines=3 event_metadata=4(map) stat_metadata=5(map)
    XLine: name=2 timestamp_ns=3 events=4
    XEvent: metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XStat: metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
    XEventMetadata: id=1 name=2 display_name=4 stats=5
    XStatMetadata: id=1 name=2
"""
from __future__ import annotations

import struct


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val = struct.unpack_from("<d", buf, i)[0]
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 5:
            val = struct.unpack_from("<f", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, val


def _text(v):
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names):
    name, value = None, None
    for num, wt, v in fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num in (2, 3, 4):
            value = v
        elif num in (5, 6):
            value = _text(v)
        elif num == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key, val = None, None
    for num, _, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def read_planes(path):
    """[{name, lines: [{name, events: [(name, display_name, start_s,
    dur_s, stats dict)]}]}] of every plane; an event's stats are its
    metadata's with its own on top."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _, pbuf in fields(space):
        if num != 1:
            continue
        name, lines, emeta, smeta = "", [], {}, {}
        for n2, _, v in fields(pbuf):
            if n2 == 2:
                name = _text(v)
            elif n2 == 3:
                lines.append(v)
            elif n2 == 4:
                k, m = _map_entry(v)
                emeta[k] = m
            elif n2 == 5:
                k, m = _map_entry(v)
                smeta[k] = next((_text(x) for n3, _, x in fields(m)
                                 if n3 == 2), "")
        meta = {}
        for k, m in emeta.items():
            mname = disp = ""
            stats = {}
            for n3, _, x in fields(m):
                if n3 == 2:
                    mname = _text(x)
                elif n3 == 4:
                    disp = _text(x)
                elif n3 == 5:
                    s, val = _stat(x, smeta)
                    stats[s] = val
            meta[k] = (mname, disp, stats)
        out_lines = []
        for lbuf in lines:
            lname, t0_ns, events = "", 0, []
            for n3, _, x in fields(lbuf):
                if n3 == 2:
                    lname = _text(x)
                elif n3 == 3:
                    t0_ns = x
                elif n3 == 4:
                    events.append(x)
            evs = []
            for ebuf in events:
                mid = off = dur = 0
                own = None
                for n4, _, x in fields(ebuf):
                    if n4 == 1:
                        mid = x
                    elif n4 == 2:
                        off = x
                    elif n4 == 3:
                        dur = x
                    elif n4 == 4:
                        s, val = _stat(x, smeta)
                        own = own or {}
                        own[s] = val
                mname, disp, stats = meta.get(mid, ("", "", {}))
                evs.append((mname, disp, t0_ns * 1e-9 + off * 1e-12,
                            dur * 1e-12, {**stats, **own} if own else stats))
            out_lines.append({"name": lname, "events": evs})
        planes.append({"name": name, "lines": out_lines})
    return planes
