"""A standard-library reader of the profiler's raw `.xplane.pb` (XSpace).

`jax.profiler.ProfileData` gives an XLA operation's name, start and
duration, but not its event metadata, where XLA keeps the JAX name stack of
the operation (`tf_op`, e.g. `jit(run_many)/while/body/jit(sweep_many)/
gather/vmap()/gather`) and its Python source (`source`, `file:line`). This
module decodes the protobuf wire format directly, so the benchmark needs no
TensorFlow.

The messages read (field numbers of `tsl/profiler/protobuf/xplane.proto`):

    XSpace          1 planes
    XPlane          1 id, 2 name, 3 lines, 4 event_metadata (map), 5
                    stat_metadata (map)
    XLine           1 id, 2 name, 3 timestamp_ns, 4 events
    XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps
    XEventMetadata  1 id, 2 name, 5 stats
    XStatMetadata   1 id, 2 name
    XStat           1 metadata_id, 5 str_value, 7 ref_value (the id of an
                    XStatMetadata whose name is the value)
"""

from __future__ import annotations

import dataclasses

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


def _fields(buf: bytes):
    """(field number, wire type, value) of each field of one message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == _VARINT:
            v, i = _varint(buf, i)
        elif wt == _LEN:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif wt == _I64:
            v = bytes(buf[i:i + 8])
            i += 8
        elif wt == _I32:
            v = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield num, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


@dataclasses.dataclass
class Line:
    name: str
    timestamp_ns: int
    # (metadata id, offset ps from timestamp_ns, duration ps) per event
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list
    # metadata id -> (name, {stat name: value}) of each event metadata
    event_metadata: dict


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, _wt, v in _fields(buf):
        if num == 1:
            key = _signed(v)
        elif num == 2:
            value = v
    return key, value


def _plane(buf) -> Plane:
    name, lines, ev_md_raw, stat_names = "", [], [], {}
    for num, _wt, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            ev_md_raw.append(_map_entry(v))
        elif num == 5:
            key, md = _map_entry(v)
            for n2, _w2, v2 in _fields(md):
                if n2 == 2:
                    stat_names[key] = _text(v2)
    event_metadata = {}
    for key, md in ev_md_raw:
        md_name, stats = "", {}
        for num, _wt, v in _fields(md):
            if num == 2:
                md_name = _text(v)
            elif num == 5:
                stat_id, value = 0, None
                for n2, _w2, v2 in _fields(v):
                    if n2 == 1:
                        stat_id = v2
                    elif n2 == 5:
                        value = _text(v2)
                    elif n2 == 7:
                        value = stat_names.get(v2, "")
                if value is not None:
                    stats[stat_names.get(stat_id, str(stat_id))] = value
        event_metadata[key] = (md_name, stats)
    return Plane(name=name, lines=[_line(b) for b in lines],
                 event_metadata=event_metadata)


def _line(buf) -> Line:
    name, ts, events = "", 0, []
    for num, _wt, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            ts = _signed(v)
        elif num == 4:
            md = off = dur = 0
            for n2, _w2, v2 in _fields(v):
                if n2 == 1:
                    md = _signed(v2)
                elif n2 == 2:
                    off = _signed(v2)
                elif n2 == 3:
                    dur = _signed(v2)
            events.append((md, off, dur))
    return Line(name=name, timestamp_ns=ts, events=events)


def read(path: str) -> list[Plane]:
    """Every plane of one `.xplane.pb`, with its lines and event metadata."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(v) for num, _wt, v in _fields(buf) if num == 1]


def _key(num: int, wire_type: int) -> bytes:
    return _encode_varint(num << 3 | wire_type)


def _encode_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def drop_planes(path: str, out: str, names=("/host:metadata",)) -> None:
    """Copy a trace without the named planes; the rest is copied byte for
    byte. `/host:metadata` holds the compiled programs' HLO for the
    profiler's viewers, which nothing here reads: without it a short
    window's trace is small enough to keep as a test fixture."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    with open(out, "wb") as f:
        for num, wt, v in _fields(buf):
            if num == 1 and wt == _LEN:
                name = next((_text(v2) for n2, _w, v2 in _fields(v)
                             if n2 == 2), "")
                if name in names:
                    continue
            if wt == _LEN:
                f.write(_key(num, wt) + _encode_varint(len(v)) + bytes(v))
            elif wt == _VARINT:
                f.write(_key(num, wt) + _encode_varint(v))
            else:
                f.write(_key(num, wt) + v)


def op_metadata(path: str) -> dict:
    """For each device plane, event-metadata name -> (tf_op, source); an
    operation XLA made up itself (a copy, a layout change) has no `tf_op`
    and maps to empty strings."""
    out = {}
    for plane in read(path):
        if not plane.name.startswith("/device:"):
            continue
        out[plane.name] = {
            name: (stats.get("tf_op", ""), stats.get("source", ""))
            for name, stats in plane.event_metadata.values()}
    return out
