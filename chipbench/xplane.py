"""The op metadata of a profiler trace's device planes (``.xplane.pb``).

``jax.profiler.ProfileData`` gives each device op's start, duration and
name (its HLO text), but not the op's metadata: ``tf_op``, the op's named
scope path (``jit(decode_step)/while/body/moe/ffn/dot_general:``), and
``program_id``, the XLA module it belongs to. This reads them from the
protobuf wire format with the standard library alone: for each device
plane (``XPlane``: name 2, lines 3, event_metadata 4, stat_metadata 5) it
decodes the two metadata maps and steps over the lines, which hold the
events, by their length.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, Iterator, Tuple

__all__ = ["OTHER", "op_scopes"]

#: the path of an op whose name the metadata gives two different paths
OTHER = "other"

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_META_NAME, _META_STATS = 2, 5                   # XEventMetadata
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7    # XStat
_VARINT_STATS = (3, 4)                           # uint64_value, int64_value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: int = -1) -> Iterator[tuple]:
    """(field, value) of one message in ``buf[i:end]``: an int for varint
    and fixed fields, a (start, stop) pair for length-delimited ones."""
    end = len(buf) if end < 0 else end
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = int.from_bytes(buf[i:i + n], "little"), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, val


def _str(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, span) -> Tuple[int, Tuple[int, int]]:
    """(key, value span) of one map<int64, message> entry."""
    key, val = 0, (span[0], span[0])
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf: bytes, span) -> Tuple[str, Dict[Tuple[int, str], str]]:
    name, events, stat_names = "", [], {}
    for f, v in _fields(buf, *span):
        if f == _PLANE_NAME:
            name = _str(buf, v)
        elif f == _PLANE_EVENT_META:
            events.append(v)
        elif f == _PLANE_STAT_META:
            key, val = _map_entries(buf, v)
            stat_names[key] = next((_str(buf, s) for g, s in
                                    _fields(buf, *val) if g == _META_NAME), "")
    ops: Dict[Tuple[int, str], str] = {}
    if not name.startswith("/device:"):
        return name, ops
    for entry in events:
        _, val = _map_entries(buf, entry)
        op, program, tf_op = "", 0, ""
        for f, v in _fields(buf, *val):
            if f == _META_NAME:
                op = _str(buf, v)
            elif f == _META_STATS:
                stat, sval = "", None
                for g, w in _fields(buf, *v):
                    if g == _STAT_META_ID:
                        stat = stat_names.get(w, "")
                    elif g == _STAT_STR:
                        sval = _str(buf, w)
                    elif g == _STAT_REF:
                        sval = stat_names.get(w, "")
                    elif g in _VARINT_STATS:
                        sval = w
                if stat == "tf_op" and isinstance(sval, str):
                    tf_op = sval
                elif stat == "program_id" and isinstance(sval, int):
                    program = sval
        if op:
            # one name with two different paths cannot be told apart
            seen = ops.setdefault((program, op), tf_op)
            if seen != tf_op:
                ops[(program, op)] = OTHER
    return name, ops


def op_scopes(path: Path) -> Dict[str, Dict[Tuple[int, str], str]]:
    """Per device plane, ``(program_id, op name) -> tf_op`` for every op
    the trace describes: ``""`` where the op has no ``tf_op`` (copies the
    compiler inserts), :data:`OTHER` where it has two."""
    buf = Path(path).read_bytes()
    out = {}
    for f, v in _fields(buf):
        if f == _SPACE_PLANES:
            name, ops = _plane(buf, v)
            if ops:
                out[name] = ops
    return out
