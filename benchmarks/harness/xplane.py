"""A minimal reader of the profiler's ``.xplane.pb`` (protobuf wire
format, ``tsl/profiler/protobuf/xplane.proto``), with no dependency.

``jax.profiler.ProfileData`` gives events and their own stats, but not
the stats of an event's METADATA, and that is where a TPU trace keeps an
op's ``tf_op`` (the ``jit(...)/L.<layer>/...`` scope path).  So the file
is decoded here: planes -> lines -> events, each event with its
metadata's name and every stat of event and metadata resolved to
``{stat name: value}``.  Times are nanoseconds from the plane's origin.
"""

from __future__ import annotations

import struct


def _varint(buf, i):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; nested messages
    and strings come as memoryview slices."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val = bytes(buf[i:i + 8])
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 5:
            val = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wt} at {i}")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names):
    name, value = None, None
    for num, wt, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num in (5, 6):
            value = _text(v)
        elif num == 7:
            value = stat_names.get(v, str(v))  # a string held by reference
    return name, value


def _map_entry(buf):
    key, val = None, None
    for num, wt, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def read(path: str, want_line=None) -> list[dict]:
    """[{name, lines: [{name, events: [{name, start_ns, dur_ns, stats}]}]}].

    ``want_line(plane_name, line_name) -> bool`` skips the events of
    lines nobody reads (a trace holds hundreds of thousands)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, wt, pbuf in _fields(space):
        if num != 1:
            continue
        pname, raw_lines, raw_emeta, stat_names = "", [], [], {}
        for n2, _, v in _fields(pbuf):
            if n2 == 2:
                pname = _text(v)
            elif n2 == 3:
                raw_lines.append(v)
            elif n2 == 4:
                raw_emeta.append(v)
            elif n2 == 5:
                k, mv = _map_entry(v)
                for n3, _, v3 in _fields(mv):
                    if n3 == 2:
                        stat_names[k] = _text(v3)
        emeta = {}
        for entry in raw_emeta:
            k, mv = _map_entry(entry)
            name, display, stats = "", "", {}
            for n3, _, v3 in _fields(mv):
                if n3 == 2:
                    name = _text(v3)
                elif n3 == 4:
                    display = _text(v3)
                elif n3 == 5:
                    sk, sv = _stat(v3, stat_names)
                    stats[sk] = sv
            emeta[k] = (name, display, stats)
        lines = []
        for lbuf in raw_lines:
            lname, t0_ns, raw_events = "", 0, []
            for n3, _, v3 in _fields(lbuf):
                if n3 == 2:
                    lname = _text(v3)
                elif n3 == 3:
                    t0_ns = _signed(v3)
                elif n3 == 4:
                    raw_events.append(v3)
            events = []
            if want_line is None or want_line(pname, lname):
                for ebuf in raw_events:
                    mid, off_ps, dur_ps, stats = 0, 0, 0, {}
                    for n4, _, v4 in _fields(ebuf):
                        if n4 == 1:
                            mid = v4
                        elif n4 == 2:
                            off_ps = _signed(v4)
                        elif n4 == 3:
                            dur_ps = _signed(v4)
                        elif n4 == 4:
                            sk, sv = _stat(v4, stat_names)
                            stats[sk] = sv
                    name, display, mstats = emeta.get(mid, (str(mid), "", {}))
                    events.append({
                        "name": name, "display": display,
                        "start_ns": t0_ns + off_ps / 1000.0,
                        "dur_ns": dur_ps / 1000.0,
                        "stats": {**mstats, **stats}})
            lines.append({"name": lname, "events": events})
        planes.append({"name": pname, "lines": lines})
    return planes
