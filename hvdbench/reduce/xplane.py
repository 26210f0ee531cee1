"""From a profiler trace to numbers.

Two stages.  :func:`load_events` reads an ``.xplane.pb`` (with nothing
but JAX) into plain event rows; everything else works on those rows, so
the small recorded trace beside this file (``sample_events.jsonl``)
pins the arithmetic in ``tests/test_reduce.py`` without a chip.

A row is ``{"plane", "line", "name", "start_ns", "dur_ns"}``.  Device
planes are named ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event for each operation that ran, named by its whole HLO text
(``%copy.12 = bf16[1025,16,25,64]{...} copy(...)``); operations on it do
not overlap, and their union is the device's busy time.  ``Async XLA
Ops`` holds what is in flight beside them (copies, collectives between
their ``-start`` and ``-done``).  Host spans are the benchmark's own
``TraceAnnotation`` events on the host plane, on the same clock.
"""

from __future__ import annotations

import json
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute")
SHORT_GAP_NS = 2000
_LABEL = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)*(?:\.clone)? = \(?(\w+\[[\d,]*\])?")

Interval = Tuple[float, float]


def load_events(path: str, host_spans: Iterable[str]) -> List[dict]:
    """Device operations of every TPU plane and the named host spans."""
    import jax

    wanted = set(host_spans)
    rows: List[dict] = []
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if not on_device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if on_device and line.name not in (OP_LINE, ASYNC_LINE):
                continue
            for ev in line.events:
                if not on_device and ev.name not in wanted:
                    continue
                row = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                rows.append(row)
    return rows


def read_jsonl(path: str) -> List[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def device_planes(rows: List[dict]) -> List[str]:
    return sorted({r["plane"] for r in rows if DEVICE_PLANE.match(r["plane"])},
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def ops_of(rows: List[dict], plane: str) -> List[dict]:
    return [r for r in rows if r["plane"] == plane and r["line"] == OP_LINE]


def spans_of(rows: List[dict], name: str) -> List[dict]:
    return sorted((r for r in rows
                   if r["plane"] == HOST_PLANE and r["name"] == name),
                  key=lambda r: r["start_ns"])


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _ivals(ops: List[dict]) -> List[Interval]:
    return [(r["start_ns"], r["start_ns"] + r["dur_ns"]) for r in ops]


def _overlap(a: Interval, merged: List[Interval]) -> float:
    return sum(max(0.0, min(a[1], y) - max(a[0], x)) for x, y in merged)


def busy(rows: List[dict], window: Optional[Interval] = None) -> dict:
    """Seconds in which an operation ran, averaged over the device
    planes, and the traced window: from the first operation's start to
    the last one's end over all planes unless ``window`` is given."""
    planes = device_planes(rows)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    per_plane = [union(_ivals(ops_of(rows, p))) for p in planes]
    if window is None:
        starts = [u[0][0] for u in per_plane if u]
        ends = [u[-1][1] for u in per_plane if u]
        if not starts:
            raise ValueError("no operation ran on any device")
        window = (min(starts), max(ends))
    clipped = [[(max(a, window[0]), min(b, window[1])) for a, b in u
                if b > window[0] and a < window[1]] for u in per_plane]
    busy_ns = sum(_length(u) for u in clipped) / len(planes)
    return {"busy_s": busy_ns / 1e9,
            "window_s": (window[1] - window[0]) / 1e9,
            "window_ns": window, "planes": len(planes)}


def op_label(row: dict) -> str:
    """A stable name for an operation: its HLO name without the
    instance number, and the (first) shape it produces
    (``%copy.12 = bf16[1025,16,25,64]{...} copy(...)`` ->
    ``copy_bf16_1025_16_25_64_``)."""
    m = _LABEL.match(row["name"])
    if not m:
        return row["name"][:64]
    label = m.group(1)
    if m.group(2):
        label += "_" + re.sub(r"[^\w]", "_", m.group(2))
    return label


def top_ops(rows: List[dict], n: int = 10) -> List[List]:
    """The operations that took most time on device 0, by label."""
    totals: Dict[str, float] = {}
    for r in ops_of(rows, device_planes(rows)[0]):
        label = op_label(r)
        totals[label] = totals.get(label, 0.0) + r["dur_ns"] / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]


def time_of(rows: List[dict], pattern: str) -> Tuple[float, int]:
    """Seconds and count of device-0 operations whose HLO text matches
    ``pattern``."""
    rx = re.compile(pattern)
    hits = [r for r in ops_of(rows, device_planes(rows)[0])
            if rx.search(r["name"])]
    return sum(r["dur_ns"] for r in hits) / 1e9, len(hits)


def device_time_under(rows: List[dict], span_name: str) -> List[float]:
    """For each host span of that name, the seconds of device-0
    operations that started inside it."""
    ops = sorted(ops_of(rows, device_planes(rows)[0]),
                 key=lambda r: r["start_ns"])
    out = []
    i = 0
    for span in spans_of(rows, span_name):
        a, b = span["start_ns"], span["start_ns"] + span["dur_ns"]
        while i < len(ops) and ops[i]["start_ns"] < a:
            i += 1
        j, inside = i, []
        while j < len(ops) and ops[j]["start_ns"] < b:
            inside.append((ops[j]["start_ns"],
                           ops[j]["start_ns"] + ops[j]["dur_ns"]))
            j += 1
        out.append(_length(union(inside)) / 1e9)
    return out


def _is_collective(row: dict) -> bool:
    head = row["name"].split(" = ", 1)
    # The operation's own kind: the first word that opens the operand
    # list, after the result type.
    kind = re.search(r"\s([\w\-]+)\(", head[1]) if len(head) == 2 else None
    return bool(COLLECTIVE.search(head[0])
                or (kind and COLLECTIVE.search(kind.group(1))))


def collectives(rows: List[dict]) -> dict:
    """On device 0: seconds during which a collective operation was
    running or in flight (``XLA Ops`` and ``Async XLA Ops``), and the
    part of that during which no other operation ran on ``XLA Ops``."""
    plane = device_planes(rows)[0]
    ops = ops_of(rows, plane)
    in_flight = [r for r in rows if r["plane"] == plane
                 and r["line"] == ASYNC_LINE]
    coll = [r for r in ops + in_flight if _is_collective(r)]
    others = union(_ivals([r for r in ops if not _is_collective(r)]))
    merged = union(_ivals(coll))
    total = _length(merged)
    hidden = sum(_overlap(iv, others) for iv in merged)
    return {"total_s": total / 1e9, "exposed_s": (total - hidden) / 1e9,
            "count": len(coll)}


def idle_gaps(rows: List[dict], span_names: Iterable[str],
              window: Interval, n: int = 10) -> List[List]:
    """The idle time of device 0 inside ``window``, by what the host
    was doing: each gap between operations goes to the benchmark's host
    span that covers its middle, or to ``no_span``; gaps under 2 us are
    summed apart."""
    merged = union(_ivals(ops_of(rows, device_planes(rows)[0])))
    edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [(r["start_ns"], r["start_ns"] + r["dur_ns"], r["name"])
             for name in span_names for r in spans_of(rows, name)]
    totals: Dict[str, float] = {}
    for a, b in gaps:
        if b - a < SHORT_GAP_NS:
            key = "gaps_under_2us"
        else:
            mid = (a + b) / 2
            key = next((name for s, e, name in spans if s <= mid < e),
                       "no_span")
        totals[key] = totals.get(key, 0.0) + (b - a) / 1e9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]
