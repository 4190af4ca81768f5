"""A reader for the profiler's ``.xplane.pb`` that needs nothing but
the standard library: the protobuf wire format of ``XSpace`` (tsl's
``xplane.proto``), decoded as far as the reduction needs it.

``jax.profiler.ProfileData`` gives events and their own stats, but not
the stats of an event's *metadata*, which is where the profiler puts
what an operation is (``hlo_category``, ``tf_op``, ``flops``,
``bytes_accessed``); the only other reader installed comes with an
import of all of TensorFlow.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, List, Tuple


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message; a
    length-delimited value is a ``memoryview`` slice."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 1:
            value, at = buf[at:at + 8], at + 8
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    name, value = "", None
    for number, _, v in fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf) -> Tuple[int, bytes]:
    key, value = 0, b""
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def read(path: str) -> List[dict]:
    """The planes of an xplane file:

    ``{"name", "lines": [{"name", "events": [(name, start_s, end_s, stats)]}]}``

    ``stats`` joins the event's own stats with its metadata's, by name;
    times are seconds on the trace's one clock."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    planes = []
    for number, _, plane_buf in fields(space):
        if number != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for n, _, v in fields(plane_buf):
            if n == 2:
                name = _text(v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                key, value = _map_entry(v)
                event_meta[key] = value
            elif n == 5:
                key, value = _map_entry(v)
                stat_names[key] = next(
                    (_text(x) for m, _, x in fields(value) if m == 2), "")
        meta: Dict[int, Tuple[str, dict]] = {}
        for key, buf in event_meta.items():
            label, stats = "", {}
            for n, _, v in fields(buf):
                if n == 2:
                    label = _text(v)
                elif n == 5:
                    k, value = _stat(v, stat_names)
                    stats[k] = value
            meta[key] = (label, stats)
        out_lines = []
        for line_buf in lines:
            line_name, t0_ns, events = "", 0, []
            for n, _, v in fields(line_buf):
                if n == 2:
                    line_name = _text(v)
                elif n == 3:
                    t0_ns = _signed(v)
                elif n == 4:
                    events.append(v)
            decoded = []
            for ev_buf in events:
                mid = offset_ps = duration_ps = 0
                own = {}
                for n, _, v in fields(ev_buf):
                    if n == 1:
                        mid = v
                    elif n == 2:
                        offset_ps = _signed(v)
                    elif n == 3:
                        duration_ps = _signed(v)
                    elif n == 4:
                        k, value = _stat(v, stat_names)
                        own[k] = value
                label, shared = meta.get(mid, (str(mid), {}))
                start = t0_ns * 1e-9 + offset_ps * 1e-12
                decoded.append((label, start, start + duration_ps * 1e-12,
                                {**shared, **own}))
            out_lines.append({"name": line_name, "events": decoded})
        planes.append({"name": name, "lines": out_lines})
    return planes
