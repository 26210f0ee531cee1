"""What ``jax.profiler.ProfileData`` does not hand out: an operation's
*metadata*.

``ProfileData`` gives an event its own stats only (``device_offset_ps``,
``device_duration_ps``).  The scope a ``jax.named_scope`` puts around a
region of a compiled program lives one level up, in the plane's
``event_metadata`` table (``XEventMetadata.stats``: the HLO
instruction's ``op_name`` among them), which ``ProfileData`` does not
expose.  This module reads it from the ``.xplane.pb`` itself: a reader
of the protobuf wire format, a page long, for the few messages of
``xplane.proto`` that matter here.  Nothing but the standard library.

    XSpace.planes = 1
    XPlane: name = 2, lines = 3, event_metadata = 4 (map), stat_metadata = 5 (map)
    XLine: name = 2, events = 4
    XEvent: metadata_id = 1, offset_ps = 2 (from the line's start),
            duration_ps = 3
    XEventMetadata: id = 1, name = 2, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, then one of double = 2, uint64 = 3,
           int64 = 4, str = 5, bytes = 6, ref = 7 (a stat_metadata id
           whose *name* is the value)

A file that is not an XSpace, or is cut short, raises ``ValueError``;
the readers that call this catch it and report nothing.
"""

from __future__ import annotations

import re
import struct
from typing import Dict, Iterator, List, Optional, Tuple

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        try:
            byte = buf[pos]
        except IndexError:
            raise ValueError("cut short inside a varint") from None
        pos += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, pos
        shift += 7
        if shift > 63:
            raise ValueError("a varint longer than 64 bits")


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an int for a
    varint, the raw 4 or 8 bytes for a fixed, a memoryview for bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, kind = key >> 3, key & 7
        if kind == _VARINT:
            value, pos = _varint(buf, pos)
        elif kind == _BYTES:
            size, pos = _varint(buf, pos)
            if pos + size > end:
                raise ValueError("a field runs past its message")
            value, pos = buf[pos:pos + size], pos + size
        elif kind == _FIXED64:
            value, pos = buf[pos:pos + 8], pos + 8
        elif kind == _FIXED32:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {kind} is not in an XSpace")
        yield number, kind, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view) -> Tuple[int, Optional[memoryview]]:
    key, value = 0, None
    for number, _, v in _fields(view):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(view, stat_names: Dict[int, str]):
    """``(name, value)`` of one XStat."""
    name, value = None, None
    for number, kind, v in _fields(view):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number in (3, 4):
            value = v
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def planes(path: str) -> Iterator[Tuple[str, memoryview]]:
    """``(name, the plane's bytes)`` for each plane of the file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for number, kind, plane in _fields(buf):
        if number != 1 or kind != _BYTES:
            continue
        name = ""
        for n, k, v in _fields(plane):
            if n == 2 and k == _BYTES:
                name = _text(v)
                break
        yield name, plane


def read_plane(plane, line_name: str) -> dict:
    """One plane's metadata tables and the events of one of its lines:
    ``{"stat_names": {id: name}, "events_meta": {id: {"name": ...,
    "stats": {name: value}}}, "events": [(metadata id, offset_ps,
    duration_ps)]}``."""
    stat_names: Dict[int, str] = {}
    meta_raw: List[memoryview] = []
    lines: List[memoryview] = []
    for number, kind, v in _fields(plane):
        if kind != _BYTES:
            continue
        if number == 5:
            key, value = _map_entry(v)
            if value is not None:
                for n, _, x in _fields(value):
                    if n == 2:
                        stat_names[key] = _text(x)
        elif number == 4:
            meta_raw.append(v)
        elif number == 3:
            lines.append(v)
    events_meta: Dict[int, dict] = {}
    for entry in meta_raw:
        key, value = _map_entry(entry)
        if value is None:
            continue
        record = {"name": "", "stats": {}}
        for n, k, x in _fields(value):
            if n == 2 and k == _BYTES:
                record["name"] = _text(x)
            elif n == 5 and k == _BYTES:
                name, val = _stat(x, stat_names)
                if name is not None:
                    record["stats"][name] = val
        events_meta[key] = record
    events: List[Tuple[int, int, int]] = []
    for line in lines:
        name, raw_events = "", []
        for n, k, x in _fields(line):
            if n == 2 and k == _BYTES:
                name = _text(x)
            elif n == 4 and k == _BYTES:
                raw_events.append(x)
        if name != line_name:
            continue
        for ev in raw_events:
            meta_id = offset = dur = 0
            for n, k, x in _fields(ev):
                if k != _VARINT:
                    continue
                if n == 1:
                    meta_id = x
                elif n == 2:
                    offset = x
                elif n == 3:
                    dur = x
            events.append((meta_id, offset, dur))
    return {"stat_names": stat_names, "events_meta": events_meta,
            "events": events}


# The stats of an operation's metadata that may carry the HLO
# instruction's op_name (the name stack with the program's scopes in
# it), in the order they are tried.
OP_NAME_STATS = ("tf_op", "op_name", "hlo_op_name", "name_scope")
# ``%copy-start.146 = (f32[...`` -> ``copy-start``
_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.clone)? = ")


def exclusive(events: List[Tuple[int, int, int]]
              ) -> List[Tuple[int, int, Optional[int]]]:
    """The events of one line in the order of their starts, each as
    ``(metadata id, own picoseconds, position of the enclosing event in
    this list or None)``.  A line may nest: on a TPU's ``XLA Ops`` a
    ``while`` is one event and the operations of its body are events
    inside it.  An event's own time is its duration less that of the
    events directly inside it, so the own times of a line add up to the
    time the line was busy."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in ordered]
    parent: List[Optional[int]] = [None] * len(ordered)
    stack: List[int] = []
    for k, (_, start, dur) in enumerate(ordered):
        while stack and sum(ordered[stack[-1]][1:]) <= start:
            stack.pop()
        if stack:
            top = parent[k] = stack[-1]
            own[top] -= min(start + dur, sum(ordered[top][1:])) - start
        stack.append(k)
    return [(e[0], max(0, own[k]), parent[k])
            for k, e in enumerate(ordered)]


def seconds_by_scope(path: str, plane_name: str, line_name: str,
                     scope: "re.Pattern") -> Optional[dict]:
    """Device time of one plane's operations, by the program's scope
    each lies under: ``{"by_scope": {scope: seconds}, "other_s": ...,
    "other_top": [[operation, seconds], ...] (the five largest of
    ``other_s``, by the instruction's name without its number),
    "ops": events counted, "named": events whose metadata carried an
    op_name}``.  Each event counts with its own time
    (:func:`exclusive`).  An operation under nested scopes is charged
    to the innermost (the last match of ``scope`` in its op_name); one
    whose op_name names no scope, or that has none, goes with the event
    that encloses it (the body of a ``while``), and to ``other_s`` if
    there is none.  None when the file has no such plane."""
    for name, plane in planes(path):
        if name != plane_name:
            continue
        data = read_plane(plane, line_name)
        innermost: Dict[int, Optional[str]] = {}
        named = set()
        for meta_id, record in data["events_meta"].items():
            op_name = next((record["stats"][k] for k in OP_NAME_STATS
                            if isinstance(record["stats"].get(k), str)),
                           None)
            if op_name is not None:
                named.add(meta_id)
            found = scope.findall(op_name) if op_name else []
            innermost[meta_id] = found[-1] if found else None
        rows = exclusive(data["events"])
        keys: List[Optional[str]] = []
        by_scope: Dict[str, float] = {}
        other: Dict[str, float] = {}
        n_named = 0
        for meta_id, own_ps, parent in rows:     # parents come first
            key = innermost.get(meta_id)
            if key is None and parent is not None:
                key = keys[parent]
            keys.append(key)
            n_named += meta_id in named
            if key is None:
                text = data["events_meta"].get(meta_id, {}).get("name", "")
                label = _INSTRUCTION.match(text)
                label = label.group(1) if label else text[:40]
                other[label] = other.get(label, 0.0) + own_ps / 1e12
            else:
                by_scope[key] = by_scope.get(key, 0.0) + own_ps / 1e12
        top = sorted(other.items(), key=lambda kv: -kv[1])[:5]
        return {"by_scope": by_scope, "other_s": sum(other.values()),
                "other_top": [[k, v] for k, v in top],
                "ops": len(rows), "named": n_named}
    return None
