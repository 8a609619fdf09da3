"""A reader of the profiler's ``*.xplane.pb`` that reaches each event's
metadata, which ``jax.profiler.ProfileData`` does not expose.

The file is one ``XSpace`` protobuf (``tsl/profiler/protobuf/xplane.proto``);
this module decodes its wire format with the standard library alone.  A
plane holds lines of events; each event points at an entry of its plane's
``event_metadata`` by id, and that entry carries the op's name and stats.
On a device plane one of those stats is ``tf_op``: JAX's name stack for
the op (``jit(step_fn)/while/body/attn/q/dequant/...``), which is where
the program's ``jax.named_scope`` names land.

Ops are keyed by metadata id, never by name: two executables in one trace
(the serve cell's rank-16 and rank-64 buckets) each hold a ``fusion.9``.
"""
from __future__ import annotations

import dataclasses
import re
import struct

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_ID, _META_NAME, _META_DISPLAY_NAME, _META_STATS = 1, 2, 4, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7

TF_OP = "tf_op"
CATEGORY = "hlo_category"
# HLO ops that enclose others: their time is their body's, counted there
ENCLOSING = ("while", "call", "conditional")
_WRAPPED = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*\((.*)\)$")


@dataclasses.dataclass
class Event:
    metadata_id: int
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list
    names: dict                 # metadata id -> event name
    stats: dict                 # metadata id -> {stat name: str value}


def _varint(buf, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one message: an int for
    a varint or fixed-width field, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 1:
            v, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wire == 5:
            v, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield tag >> 3, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_value(buf):
    """The value of one protobuf map entry (``key = 1, value = 2``)."""
    for f, v in _fields(buf):
        if f == 2:
            return v
    return b""


def _stat_names(raw: list) -> dict:
    out = {}
    for entry in raw:
        sid, name = 0, ""
        for f, v in _fields(_map_value(entry)):
            if f == _META_ID:
                sid = v
            elif f == _META_NAME:
                name = _str(v)
        out[sid] = name
    return out


def _event_meta(raw: list, stat_names: dict):
    names, stats = {}, {}
    for entry in raw:
        mid, name, shown, st = 0, "", "", {}
        for f, v in _fields(_map_value(entry)):
            if f == _META_ID:
                mid = v
            elif f == _META_NAME:
                name = _str(v)
            elif f == _META_DISPLAY_NAME:
                shown = _str(v)
            elif f == _META_STATS:
                key = val = None
                for sf, sv in _fields(v):
                    if sf == _STAT_META_ID:
                        key = stat_names.get(sv)
                    elif sf == _STAT_STR:
                        val = _str(sv)
                    elif sf == _STAT_REF:
                        val = stat_names.get(sv)
                if key is not None and val is not None:
                    st[key] = val
        names[mid] = name or shown
        if st:
            stats[mid] = st
    return names, stats


def _line(buf) -> Line:
    name, t0, raw = "", 0, []
    for f, v in _fields(buf):
        if f == _LINE_NAME:
            name = _str(v)
        elif f == _LINE_TIMESTAMP_NS:
            t0 = _signed(v)
        elif f == _LINE_EVENTS:
            raw.append(v)
    events = []
    for ev in raw:
        mid = off = dur = 0
        for f, v in _fields(ev):
            if f == _EVENT_META_ID:
                mid = v
            elif f == _EVENT_OFFSET_PS:
                off = _signed(v)
            elif f == _EVENT_DURATION_PS:
                dur = _signed(v)
        events.append(Event(mid, t0 + off / 1e3, dur / 1e3))
    return Line(name, events)


def _plane(buf, ops_line: str) -> Plane:
    name, lines, emeta, smeta = "", [], [], []
    for f, v in _fields(buf):
        if f == _PLANE_NAME:
            name = _str(v)
        elif f == _PLANE_LINES:
            lines.append(v)
        elif f == _PLANE_EVENT_META:
            emeta.append(v)
        elif f == _PLANE_STAT_META:
            smeta.append(v)
    names, stats = _event_meta(emeta, _stat_names(smeta))
    parsed = [_line(raw) for raw in lines]
    ops = [ln for ln in parsed if ln.name == ops_line] or parsed
    return Plane(name, ops, names, stats)


def _plane_name(buf) -> str:
    for f, v in _fields(buf):
        if f == _PLANE_NAME:
            return _str(v)
    return ""


def read(path: str, device_prefix: str, ops_line: str) -> list:
    """The device planes of ``path`` (name starting with
    ``device_prefix``), each with its ``ops_line`` line (every line, where
    it has none) and every event's metadata.  Other planes are skipped
    unread."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    return [_plane(v, ops_line) for field, v in _fields(data)
            if field == _SPACE_PLANES
            and _plane_name(v).startswith(device_prefix)]


def components(tf_op: str) -> list:
    """The scope names of a ``tf_op`` path, each unwrapped from the
    transforms JAX writes around it (``jvp(attn)``,
    ``transpose(jvp(mlp))``).  The last component is the primitive."""
    out = []
    for part in tf_op.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        out.append(part)
    return out
