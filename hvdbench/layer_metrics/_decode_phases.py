"""What the readers of a decode step's phases share.  Since PR 40 the
program's ``hvd_tpu_engine_decode`` span says where the step's host
time went, in two places:

* **the span ring** — ``args.prepare_us``, ``args.dispatch_us`` and
  ``args.fence_us`` beside ``args.uploads``, ``args.sampling``,
  ``args.live_blocks`` and ``args.poked``, and ``args.stalled`` on a
  step that one phase held up: :func:`window_decodes`, over the whole
  measured window (``program_spans.ring_window``); a prefill's
  ``args.dispatch_us``, ``args.fence_us`` and ``args.stalled``:
  :func:`window_prefills`;
* **the run's trace** — the annotations ``hvd_tpu_decode_dispatch`` and
  ``hvd_tpu_decode_fence`` inside the span (and the prefill's two), on
  the clock of ``XLA Ops``: :func:`rows` loads the trace with these
  names beside ``program_spans.HOST_SPANS`` (which stays as it is),
  :func:`steps` lays each decode span against device 0's operations,
  :func:`idle_by_phase` the device's idle time against all of them.

Nothing is returned, and nothing raised, where the run was not traced
or the program has no such args or annotations (the parent of PR 40).
"""

from __future__ import annotations

from bisect import bisect_left, bisect_right
from typing import Dict, List, Optional

from hvdbench.reduce import program_spans as ps
from hvdbench.reduce import xplane

DECODE_DISPATCH = "hvd_tpu_decode_dispatch"
DECODE_FENCE = "hvd_tpu_decode_fence"
PREFILL_DISPATCH = "hvd_tpu_prefill_dispatch"
PREFILL_FENCE = "hvd_tpu_prefill_fence"
NAMES = ps.HOST_SPANS + (DECODE_DISPATCH, DECODE_FENCE, PREFILL_DISPATCH,
                         PREFILL_FENCE)
# A piece of a gap goes to the first of these that covers it: innermost
# first.  ``rest`` names what is left of a span outside its children.
INNERMOST_FIRST = (
    (DECODE_DISPATCH, DECODE_DISPATCH), (DECODE_FENCE, DECODE_FENCE),
    (PREFILL_DISPATCH, PREFILL_DISPATCH), (PREFILL_FENCE, PREFILL_FENCE),
    (ps.ENGINE_DECODE, "rest_of_" + ps.ENGINE_DECODE),
    (ps.ENGINE_PREFILL, "rest_of_" + ps.ENGINE_PREFILL),
    (ps.SERVE_STEP, "own_time_of_" + ps.SERVE_STEP))
OUTSIDE = "outside_program"
SHORT = "gaps_under_2us"

_rows_cache: Dict[str, List[dict]] = {}
_ring_last: list = [None, []]      # the view last read, and its reading
_steps_last: list = [None, []]     # the rows last laid out, and the steps


def rows(view) -> List[dict]:
    """Device operations, the program's host spans and the phases'
    annotations of this run's trace; empty where there is no trace."""
    path = ps.trace_file(view)
    if path is None:
        return []
    if path not in _rows_cache:
        try:
            _rows_cache[path] = xplane.load_events(path, NAMES)
        except Exception as e:
            ps.say(decode_phases=f"trace not read: {type(e).__name__}: {e}")
            _rows_cache[path] = []
    return _rows_cache[path]


def _window_spans(view) -> List[dict]:
    """The ring's engine spans that ended inside the measured window,
    oldest first; empty where the ring holds none, or no longer holds
    the window's opening."""
    if _ring_last[0] is not view:
        found: List[dict] = []
        elapsed = view.facts.get("elapsed_s")
        spans = ps.ring() if elapsed else []
        window = ps.ring_window(spans, elapsed) if spans else None
        if window is not None:
            found = sorted(
                (s for s in spans
                 if s["name"] in (ps.ENGINE_DECODE, ps.ENGINE_PREFILL)
                 and window[0] < s["start_us"] + s["dur_us"] <= window[1]),
                key=lambda s: s["start_us"])
        _ring_last[:] = [view, found]
    return _ring_last[1]


def window_decodes(view) -> List[dict]:
    """The window's ``hvd_tpu_engine_decode`` spans, from the ring."""
    return [s for s in _window_spans(view) if s["name"] == ps.ENGINE_DECODE]


def window_prefills(view) -> List[dict]:
    """The window's ``hvd_tpu_engine_prefill`` spans, from the ring."""
    return [s for s in _window_spans(view)
            if s["name"] == ps.ENGINE_PREFILL]


def stamped(spans: List[dict]) -> List[dict]:
    """Those of the decode spans that carry the phases (a speculative
    step carries none; the parent of PR 40 none at all)."""
    return [s for s in spans if "dispatch_us" in s.get("args", {})]


def _within(kids: List[dict], starts: List[float], span: dict):
    """The first of ``kids`` (sorted by start) that lies inside
    ``span`` on its thread, or None."""
    a, b = span["start_ns"], span["start_ns"] + span["dur_ns"]
    for k in kids[bisect_left(starts, a):bisect_left(starts, b)]:
        if k["line"] == span["line"] and k["start_ns"] + k["dur_ns"] <= b:
            return k
    return None


def steps(all_rows: List[dict]) -> List[dict]:
    """Each ``hvd_tpu_engine_decode`` span of the trace against device
    0: ``launch_ns`` (from the span's start to the start of the first
    operation that begins after its dispatch annotation opens),
    ``readback_ns`` (from the end of the last operation that began
    inside the span to the end of its fence annotation), ``busy_ns``
    (the device's busy time inside the span) and ``start_ns`` /
    ``dur_ns``.  A number is None where the span lacks what it is read
    from: no annotation (a program without them; a span the trace's
    start cut), no operation under it."""
    if _steps_last[0] is all_rows:      # three readers, one trace
        return _steps_last[1]
    planes = xplane.device_planes(all_rows)
    if not planes:
        return []
    ops = sorted(xplane.ops_of(all_rows, planes[0]),
                 key=lambda r: r["start_ns"])
    op_starts = [o["start_ns"] for o in ops]
    dispatches = xplane.spans_of(all_rows, DECODE_DISPATCH)
    fences = xplane.spans_of(all_rows, DECODE_FENCE)
    d_starts = [r["start_ns"] for r in dispatches]
    f_starts = [r["start_ns"] for r in fences]
    out = []
    for span in xplane.spans_of(all_rows, ps.ENGINE_DECODE):
        a, b = span["start_ns"], span["start_ns"] + span["dur_ns"]
        i, j = bisect_left(op_starts, a), bisect_left(op_starts, b)
        dispatch = _within(dispatches, d_starts, span)
        fence = _within(fences, f_starts, span)
        launch = readback = None
        if dispatch is not None:
            k = bisect_left(op_starts, dispatch["start_ns"])
            if k < j:
                launch = op_starts[k] - a
        if fence is not None and j > i:
            last_end = max(o["start_ns"] + o["dur_ns"] for o in ops[i:j])
            readback = fence["start_ns"] + fence["dur_ns"] - last_end
        busy = sum(y - x for x, y in xplane.union(
            (o["start_ns"], min(b, o["start_ns"] + o["dur_ns"]))
            for o in ops[i:j]))
        out.append({"start_ns": a, "dur_ns": span["dur_ns"],
                    "launch_ns": launch, "readback_ns": readback,
                    "busy_ns": busy})
    _steps_last[:] = [all_rows, out]
    return out


def _cut(pieces, starts: List[float], ends: List[float]):
    """``pieces`` (disjoint intervals) as the part that the disjoint
    intervals ``(starts[k], ends[k])`` cover and the part they leave."""
    inside, left = [], []
    for a, b in pieces:
        k, at = max(0, bisect_right(starts, a) - 1), a
        while k < len(starts) and starts[k] < b:
            x, y = max(starts[k], a), min(ends[k], b)
            if y > x:
                if x > at:
                    left.append((at, x))
                inside.append((x, y))
                at = y
            k += 1
        if at < b:
            left.append((at, b))
    return inside, left


def idle_by_phase(all_rows: List[dict], window) -> Optional[Dict[str, float]]:
    """Seconds of device 0's idle time inside ``window`` by what the
    program was doing.  Each gap between operations is cut where the
    program's annotations and spans begin and end, and every piece
    goes to the innermost over it (``INNERMOST_FIRST``), or to
    ``outside_program``: a gap of 2 ms between two steps is a fence's
    end, a step's bookkeeping, the harness's loop and the next step's
    dispatch, and its middle would name one of them (which one changes
    from process to process: PERF.md section 6, PR 40).  Gaps under
    2 us are summed apart.  The parts add up to the window less the
    device's busy time.  None where the trace holds no device plane."""
    planes = xplane.device_planes(all_rows)
    if not planes:
        return None
    merged = xplane.union(
        (r["start_ns"], r["start_ns"] + r["dur_ns"])
        for r in xplane.ops_of(all_rows, planes[0]))
    merged = [(max(a, window[0]), min(b, window[1])) for a, b in merged
              if b > window[0] and a < window[1]]
    edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
    covers = []
    for name, label in INNERMOST_FIRST:
        # Merged, so that two threads' spans of one name cannot overlap.
        spans = xplane.union((s["start_ns"], s["start_ns"] + s["dur_ns"])
                             for s in xplane.spans_of(all_rows, name))
        covers.append((label, [a for a, _ in spans], [b for _, b in spans]))
    parts = {label: 0.0 for _, label in INNERMOST_FIRST}
    parts[OUTSIDE] = parts[SHORT] = 0.0
    for n in range(0, len(edges), 2):
        a, b = edges[n], edges[n + 1]
        if b <= a:
            continue
        if b - a < xplane.SHORT_GAP_NS:
            parts[SHORT] += (b - a) / 1e9
            continue
        left = [(a, b)]
        for label, starts, ends in covers:
            inside, left = _cut(left, starts, ends)
            parts[label] += sum(y - x for x, y in inside) / 1e9
        parts[OUTSIDE] += sum(y - x for x, y in left) / 1e9
    return parts


def trace_step_of(ring_span: dict, ring_spans: List[dict],
                  traced: List[dict]) -> Optional[dict]:
    """The traced step (:func:`steps`) that is ``ring_span``: the
    ring's and the trace's clocks differ by a constant, read from the
    newest decode span of each — the trace stops after the last step —
    and trusted only if the two agree on how long that span took."""
    if not traced or not ring_spans:
        return None
    last_ring, last_trace = ring_spans[-1], traced[-1]
    if abs(last_ring["dur_us"] * 1e3 - last_trace["dur_ns"]) > 50_000:
        return None
    offset = last_trace["start_ns"] - last_ring["start_us"] * 1e3
    at = ring_span["start_us"] * 1e3 + offset
    starts = [t["start_ns"] for t in traced]
    k = bisect_left(starts, at - 100_000)
    if k < len(traced) and abs(starts[k] - at) <= 100_000:
        return traced[k]
    return None
