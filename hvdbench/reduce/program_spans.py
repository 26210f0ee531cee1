"""The program's own spans, as a per-layer reader finds them.

``run.py`` loads only the driver's span names into ``view.rows``, so a
reader of the *program's* spans (``horovod_tpu/obs/trace.py``; the
catalog is ``docs/tracing.md``) goes through this module instead.
There are three places to look, and a reader never looks anywhere else:

* **the run's trace** — ``device.start_trace`` writes the one
  ``.xplane.pb`` of a traced run under
  ``device.OUT_DIR/trace/<cell name>/`` (emptied first), so the file is
  found by the cell's name: :func:`trace_file`.  :func:`rows` loads it
  with the existing ``xplane.load_events`` and the program's span names;
  the program enters each span as a ``jax.profiler.TraceAnnotation``, so
  they lie on ``/host:CPU`` on the same clock as ``XLA Ops``.
* **the span ring** — ``horovod_tpu.obs.trace.snapshot()``: request-level
  spans (``hvd_tpu_serve_queued`` …) are recorded after the fact and
  never reach the profiler.  The ring belongs to the process, not to the
  engine, so it is still there after the driver has freed its objects:
  :func:`ring`.
* **the operations' metadata** — the scopes inside a compiled step
  (``hvd_tpu_fwd_bwd`` …) are in each operation's ``op_name``, which
  ``reduce/xspace.py`` reads from the same file: :func:`scope_seconds`.

Every function here returns nothing (``None`` or an empty list) where
there is nothing to read — a program that lacks the spans, a run that
was not traced — and never raises: a reader that raises takes the
cell's result line with it.  What went wrong is said on a line of its
own (:func:`say`).
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional

from hvdbench import device
from hvdbench.reduce import xplane, xspace

SERVE_STEP = "hvd_tpu_serve_step"
ENGINE_PREFILL = "hvd_tpu_engine_prefill"
ENGINE_DECODE = "hvd_tpu_engine_decode"
TRAIN_STEP = "hvd_tpu_step"
QUEUED = "hvd_tpu_serve_queued"
HOST_SPANS = (SERVE_STEP, ENGINE_PREFILL, ENGINE_DECODE, TRAIN_STEP)
# The scopes the program puts inside its compiled train step.
SCOPE = re.compile(
    r"hvd_tpu_(?:fwd_bwd|optimizer|wire_pack|wire_unpack|wire_bucket_\d+)")

# Two readers read the host spans of one trace; it is loaded once.
_rows_cache: Dict[str, List[dict]] = {}


def say(**fields) -> None:
    """One earlier line of the run's output."""
    print(json.dumps(fields), flush=True)


def trace_file(view) -> Optional[str]:
    """This run's ``.xplane.pb``, or None if the run was not traced
    (``view.rows`` is None then, and a file of an earlier run may lie
    in the directory)."""
    if view.rows is None:
        return None
    found = glob.glob(os.path.join(
        device.OUT_DIR, "trace", view.cell["name"], "plugins", "profile",
        "*", "*.xplane.pb"))
    return found[0] if len(found) == 1 else None


def rows(view) -> List[dict]:
    """Device operations and the program's host spans of this run's
    trace, as ``xplane`` rows; empty where there is no trace."""
    path = trace_file(view)
    if path is None:
        return []
    if path not in _rows_cache:
        try:
            _rows_cache[path] = xplane.load_events(path, HOST_SPANS)
        except Exception as e:
            say(program_spans=f"trace not read: {type(e).__name__}: {e}")
            _rows_cache[path] = []
    return _rows_cache[path]


def ring() -> List[dict]:
    """The program's span ring, oldest first; empty where the program
    has none."""
    try:
        from horovod_tpu.obs import trace

        return list(trace.snapshot())
    except Exception as e:
        say(program_spans=f"ring not read: {type(e).__name__}: {e}")
        return []


def ring_window(spans: List[dict], elapsed_s: float):
    """The measured window on the ring's clock: the ``elapsed_s`` that
    end with the newest ``hvd_tpu_serve_step``.  None where the ring
    holds no step, or no longer holds the window's opening (its oldest
    span ended inside the window: older ones may have been washed out,
    and a part of the window is not reported from)."""
    ends = [s["start_us"] + s["dur_us"] for s in spans
            if s["name"] == SERVE_STEP]
    if not ends:
        return None
    close = max(ends)
    opening = close - elapsed_s * 1e6
    oldest = min(s["start_us"] + s["dur_us"] for s in spans)
    if oldest > opening:
        say(program_spans="the span ring no longer holds the window's "
            "opening; nothing is read from it", spans=len(spans),
            oldest_s_after_opening=(oldest - opening) / 1e6)
        return None
    return opening, close


def self_times(all_rows: List[dict], parent: str,
               children: tuple) -> List[float]:
    """Seconds of each ``parent`` host span not covered by the
    ``children`` spans that lie inside it on the same thread."""
    kids = sorted((r for name in children
                   for r in xplane.spans_of(all_rows, name)),
                  key=lambda r: r["start_ns"])
    out = []
    for span in xplane.spans_of(all_rows, parent):
        a, b = span["start_ns"], span["start_ns"] + span["dur_ns"]
        inside = [(max(a, k["start_ns"]),
                   min(b, k["start_ns"] + k["dur_ns"]))
                  for k in kids if k["line"] == span["line"]
                  and k["start_ns"] < b and k["start_ns"] + k["dur_ns"] > a]
        covered = sum(y - x for x, y in xplane.union(inside))
        out.append(max(0.0, span["dur_ns"] - covered) / 1e9)
    return out


def host_times_under(all_rows: List[dict], span_name: str) -> List[float]:
    """For each host span of that name under which the device ran
    something: the span's seconds minus device 0's busy seconds inside
    it — what the host spent around the device's work."""
    if not xplane.device_planes(all_rows):
        return []
    spans = xplane.spans_of(all_rows, span_name)
    under = xplane.device_time_under(all_rows, span_name)
    return [max(0.0, s["dur_ns"] / 1e9 - busy)
            for s, busy in zip(spans, under) if busy > 0]


def scope_seconds(view) -> Optional[dict]:
    """Device-0 seconds of this run's trace by the program's scope
    (``xspace.seconds_by_scope``); None where there is no trace, no
    device plane, or no operation under any scope."""
    path = trace_file(view)
    planes = xplane.device_planes(view.rows) if path else []
    if not planes:
        return None
    try:
        result = xspace.seconds_by_scope(path, planes[0], xplane.OP_LINE,
                                         SCOPE)
    except Exception as e:
        say(program_spans=f"scopes not read: {type(e).__name__}: {e}")
        return None
    if result is not None and not result["by_scope"]:
        say(program_spans="no operation of the trace lies under a scope "
            "of the program", ops=result["ops"],
            ops_with_op_name=result["named"])
        return None
    return result
