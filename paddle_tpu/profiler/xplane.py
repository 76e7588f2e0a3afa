"""The profiler's ``.xplane.pb`` read with nothing but Python.

``jax.profiler.ProfileData`` shows an event's own stats and not the stats of
its METADATA, which is where the TPU's trace keeps what names an operation:
``tf_op`` (the HLO ``op_name``: JAX's name stack, so the program's stamps),
``hlo_category``, ``flops``, ``bytes_accessed``, ``source``. This file walks
the protobuf's wire format (varints and length-delimited fields; the schema
is tsl/profiler/protobuf/xplane.proto, whose field numbers stand below) and
returns a plain structure::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops", "timestamp_ns": 0,
                            "events": [(metadata_id, offset_ps, dur_ps)]}],
                 "event_metadata": {id: {"name": ..., "display_name": ...,
                                         "stats": {"tf_op": ..., ...}}}}]}

Only planes that ``want(name)`` accepts have their lines and metadata read:
a host plane's events are of no use to a reduction by device operation.
"""
from __future__ import annotations

import glob
import os
import struct

__all__ = ["find_xplane", "read_xspace"]

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def find_xplane(path):
    """The newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def _fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, the bytes for a fixed field, a memoryview for a
    length-delimited one (a string, bytes, or a message to walk again)."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        num, wire = key >> 3, key & 7
        if wire == _VARINT or wire == _BYTES:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == _VARINT:
                yield num, wire, v
            else:
                yield num, wire, buf[i:i + v]
                i += v
        elif wire == _FIXED64:
            yield num, wire, bytes(buf[i:i + 8])
            i += 8
        elif wire == _FIXED32:
            yield num, wire, bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")


def _signed(v):
    """An int64 from its varint (two's complement in 64 bits)."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v):
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """``(name, value)`` of one XStat: metadata_id = 1, then one of
    double = 2, uint64 = 3, int64 = 4, str = 5, bytes = 6, ref = 7 (the
    name of another stat metadata, used as a string)."""
    name = value = None
    for num, wire, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = _text(v)
        elif num == 6:
            value = bytes(v)
        elif num == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_value(entry):
    """The value (field 2) of one entry of a protobuf map."""
    for num, _, v in _fields(entry):
        if num == 2:
            return v
    return b""


def _event_metadata(buf, stat_names):
    """XEventMetadata: id = 1, name = 2, display_name = 4, stats = 5."""
    out = {"id": 0, "name": "", "display_name": "", "stats": {}}
    for num, _, v in _fields(buf):
        if num == 1:
            out["id"] = v
        elif num == 2:
            out["name"] = _text(v)
        elif num == 4:
            out["display_name"] = _text(v)
        elif num == 5:
            name, value = _stat(v, stat_names)
            out["stats"][name] = value
    return out


def _line(buf):
    """XLine: name = 2, timestamp_ns = 3, events = 4; XEvent: metadata_id =
    1, offset_ps = 2, duration_ps = 3 (its own stats are not read)."""
    out = {"name": "", "timestamp_ns": 0, "events": []}
    events = out["events"]
    for num, _, v in _fields(buf):
        if num == 2:
            out["name"] = _text(v)
        elif num == 3:
            out["timestamp_ns"] = v
        elif num == 4:
            mid = off = dur = 0
            for n2, _, v2 in _fields(v):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    off = v2
                elif n2 == 3:
                    dur = v2
            events.append((mid, off, dur))
    return out


def _plane(buf, want):
    """XPlane: name = 2, lines = 3, event_metadata = 4 (a map), stat_metadata
    = 5 (a map; XStatMetadata: id = 1, name = 2)."""
    name, lines, emeta, smeta = "", [], [], []
    for num, _, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            emeta.append(v)
        elif num == 5:
            smeta.append(v)
    out = {"name": name, "lines": [], "event_metadata": {}}
    if not want(name):
        return out
    stat_names = {}
    for entry in smeta:
        sid, sname = 0, ""
        for num, _, v in _fields(_map_value(entry)):
            if num == 1:
                sid = v
            elif num == 2:
                sname = _text(v)
        stat_names[sid] = sname
    for entry in emeta:
        md = _event_metadata(_map_value(entry), stat_names)
        out["event_metadata"][md["id"]] = md
    out["lines"] = [_line(ln) for ln in lines]
    return out


def read_xspace(path_or_bytes, want=lambda name: True):
    """The XSpace at ``path_or_bytes`` (a file, a directory to search, or the
    serialised bytes) as the plain structure of the module docstring."""
    if isinstance(path_or_bytes, (bytes, bytearray, memoryview)):
        data = path_or_bytes
    else:
        with open(find_xplane(path_or_bytes), "rb") as f:
            data = f.read()
    planes = [_plane(v, want) for num, _, v in _fields(memoryview(data))
              if num == 1]
    return {"planes": planes}
