"""The stats of each device plane's event metadata in an ``.xplane.pb``.

``jax.profiler.ProfileData`` gives an event's own stats only.  On a TPU an
``XLA Ops`` event carries its timing there, while what the compiler knows
of the op sits in the stats of the event's metadata, one per HLO
instruction: ``tf_op`` (its ``op_name`` path, ``jit(f)/scope/op:``),
``bytes_accessed``, ``flops``, ``hlo_category``, ``shape_with_layout``.
This reads them from the file's protobuf wire format (the ``XSpace`` /
``XPlane`` messages of ``tsl/profiler/protobuf/xplane.proto``):

    {plane name: {event metadata name: {stat name: value}}}

for the planes whose name starts with ``prefix``.  An event's name in
``ProfileData`` is its metadata's name, the instruction's HLO text.
"""
from __future__ import annotations

import struct

# XSpace.planes; XPlane.name, .event_metadata, .stat_metadata; map entry
# key/value; XEventMetadata.name, .stats; XStatMetadata.name; XStat
_PLANES, _NAME, _EVENT_META, _STAT_META = 1, 2, 4, 5
_KEY, _VALUE = 1, 2
_EM_NAME, _EM_STATS = 2, 5
_STAT_ID, _DOUBLE, _UINT, _INT, _STR, _BYTES, _REF = 1, 2, 3, 4, 5, 6, 7


def _varint(b, i: int) -> tuple[int, int]:
    shift = out = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b, start: int, end: int):
    """``(field number, value)`` of one message: an int for a varint,
    raw bytes for a fixed width, a ``(start, end)`` slice for a
    length-delimited field."""
    i = start
    while i < end:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 1:
            value, i = bytes(b[i:i + 8]), i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = bytes(b[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _str(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(b, span):
    for field, value in _fields(b, *span):
        if field == _VALUE:
            return value
    return None


def _stat(b, span, names: dict):
    key = value = None
    for field, v in _fields(b, *span):
        if field == _STAT_ID:
            key = names.get(v, v)
        elif field == _DOUBLE:
            value = struct.unpack("<d", v)[0]
        elif field in (_UINT, _INT):
            value = v
        elif field == _STR:
            value = _str(b, v)
        elif field == _BYTES:
            value = bytes(b[v[0]:v[1]])
        elif field == _REF:
            value = names.get(v, v)
    return key, value


def _plane(b, span) -> tuple[str, dict]:
    name, metas, names = "", [], {}
    for field, value in _fields(b, *span):
        if field == _NAME:
            name = _str(b, value)
        elif field == _EVENT_META:
            metas.append(_map_value(b, value))
        elif field == _STAT_META:
            sm = _map_value(b, value)
            sid, sname = None, ""
            for f, v in _fields(b, *sm):
                if f == 1:
                    sid = v
                elif f == _NAME:
                    sname = _str(b, v)
            names[sid] = sname
    out = {}
    for meta in metas:
        ename, stats = "", {}
        for field, value in _fields(b, *meta):
            if field == _EM_NAME:
                ename = _str(b, value)
            elif field == _EM_STATS:
                k, v = _stat(b, value, names)
                stats[k] = v
        out[ename] = stats
    return name, out


def metadata_stats(data: bytes, prefix: str = "/device:") -> dict:
    """``{plane: {event metadata name: {stat: value}}}`` of the planes
    whose name starts with ``prefix``, from a serialized ``XSpace``."""
    b = memoryview(data)
    out = {}
    for field, value in _fields(b, 0, len(b)):
        if field != _PLANES:
            continue
        # a plane's name comes before its lines and metadata: skip the
        # rest of a plane that is not wanted without decoding it
        for f, v in _fields(b, *value):
            if f == _NAME:
                wanted = _str(b, v).startswith(prefix)
                break
        else:
            wanted = False
        if wanted:
            name, stats = _plane(b, value)
            out[name] = stats
    return out


# the host plane where the profiler keeps each program it saw, and the
# stat that holds the program as an ``HloProto`` (its field 1: the
# compiled ``HloModuleProto``)
HLO_PLANE, HLO_STAT, _HLO_MODULE = "/host:metadata", "Hlo Proto", 1


def hlo_modules(data: bytes) -> dict[str, str]:
    """``{program name: compiled HLO text}`` of the programs the trace
    saw, the names as ``XLA Modules`` events carry them, so that a device
    op's name is an instruction of its program's text.  Empty where the
    trace keeps no programs."""
    from jax._src.lib import _jax      # no public API parses the proto
    out = {}
    for plane in metadata_stats(data, HLO_PLANE).values():
        for name, stats in plane.items():
            raw = stats.get(HLO_STAT)
            if not isinstance(raw, bytes):
                continue
            b = memoryview(raw)
            for field, value in _fields(b, 0, len(b)):
                if field == _HLO_MODULE:
                    mod = _jax.HloModule.from_serialized_hlo_module_proto(
                        bytes(b[value[0]:value[1]]))
                    out[name] = mod.to_string()
    return out
