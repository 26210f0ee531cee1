"""Cross-rank distributed tracing: W3C-style span contexts.

PR 5 gave every layer aggregate gauges; this module gives every *step*
and every *serve request* an identity that survives process boundaries.
The design follows the W3C Trace Context shape (the "Collective
Communication for 100k+ GPUs" fleet-debugging direction in PAPERS.md
needs causal traces, not just counters):

* a **trace** is one step (``make_train_step``/``make_spmd_train_step``
  — rooted by ``obs.instrument.wrap_step``) or one serve request
  (rooted at router admission, ``serve/router.py``);
* a **span** is one timed hop/phase inside it — an RPC client/server
  frame (``runner/common/network.py`` injects/extracts the context on
  every ``BasicClient._call``/``BasicService`` exchange), a checkpoint
  save/restore, a serving queue/prefill/decode phase;
* the context on the wire is ``(trace_id, span_id)`` hex strings
  (W3C ``traceparent`` minus flags), attached to the pickled request as
  ``_hvd_trace`` so the HMAC frame format is untouched.

Finished spans land in a **bounded per-process ring** (the crash flight
recorder ``obs/flight.py`` dumps it postmortem) and, when a framework
``Timeline`` is live, are mirrored into it as Chrome-trace slices; RPC
client/server spans additionally emit flow (``"s"``/``"f"``) events
keyed by the client span id, so Perfetto draws the cross-process arrow.

**One clock.**  Every span is timed on ``time.monotonic_ns`` — the
clock the serve batcher stamps requests with — and the ring's
``start_us`` is that reading plus ONE wall-clock anchor taken when the
module is imported, so ``start_us`` still reads as unix microseconds
(:func:`mono_us` converts a ``time.monotonic()`` stamp; nothing
re-anchors span by span).  Across processes,
:func:`estimate_clock_offset` (Cristian's algorithm over
``PingRequest`` RTTs — the minimum-RTT sample bounds the error by
RTT/2) corrects residual skew when ``scripts/trace_merge.py`` merges
per-process span sets into ONE Perfetto file, and
:func:`critical_path` reports which hop/phase dominated a trace's wall
time (TTFT or step time).

**The profiler bridge.**  :func:`span` also enters a
``jax.profiler.TraceAnnotation`` of the same name for its duration.
With no profiler session live that is one flag test; with one live the
span lands on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
device's ``XLA Ops``, so the program's spans and the device's
operations share the profiler's clock by construction.
:func:`record_span` (after-the-fact spans) stays ring-only, and
:func:`annotate` (a phase inside a span) is the annotation alone.
:func:`scope` is the same idea inside a compiled program: a
``jax.named_scope`` that names a region of the step in every
operation's metadata (docs/tracing.md).

Hot-path contract (the ``faults``/``metrics`` convention): one
:func:`enabled` check per call site; ``HVD_TPU_TRACE=0`` turns every
span into a single boolean test.
"""

from __future__ import annotations

import contextlib
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "enabled", "configure", "span", "record_span", "instant", "current",
    "new_context", "use_context", "process_rank", "scope", "annotate",
    "now_us", "mono_us", "inject", "extract", "snapshot", "recent",
    "clear",
    "estimate_clock_offset", "merge_traces", "unresolved_parents",
    "critical_path", "trace_ids", "dump_merged",
]

_TRUE = {"1", "true", "yes", "on"}

_lock = threading.Lock()
_enabled: Optional[bool] = None          # guarded-by: _lock (lazy env gate)
_ring: "deque" = deque(maxlen=16384)     # guarded-by: _lock
_tls = threading.local()                 # .ctx = (trace_id, span_id) or None
_UNSET = object()
# Pinned by configure() (hvd.init / start_timeline / shutdown) so that a
# finished span looks neither up: this process's rank in the live world,
# and the framework Timeline that spans are mirrored into.  Written
# under _lock; a reader takes the one reference as it stands.
_rank: Optional[int] = None
_timeline = None
# The one wall-clock anchor: unix microseconds at monotonic zero.
_ANCHOR_US = (time.time_ns() - time.monotonic_ns()) / 1e3


def enabled() -> bool:
    """The per-call-site gate.  Resolved lazily from ``HVD_TPU_TRACE``
    (default on, like ``HVD_TPU_METRICS``) so pre-init layers — the
    launcher's RPC clients, the elastic driver — agree with the
    post-init Config; :func:`configure` (``hvd.init``) pins it."""
    global _enabled
    if _enabled is None:
        with _lock:
            if _enabled is None:
                raw = os.environ.get("HOROVOD_TRACE") \
                    or os.environ.get("HVD_TPU_TRACE")
                _enabled = True if raw is None \
                    else raw.strip().lower() in _TRUE
    return _enabled


def configure(enabled: Optional[bool] = None,
              ring: Optional[int] = None, *,
              rank: Any = _UNSET, timeline: Any = _UNSET) -> None:
    """Pin the gate / resize the span ring from the resolved Config
    (``hvd.init``).  Resizing keeps the newest spans — never clears
    recorded history across elastic re-inits.  ``rank`` is this
    process's rank in the live world (None after shutdown: the launch
    env answers again) and ``timeline`` the framework Timeline to
    mirror finished spans into (None: no mirror)."""
    global _enabled, _ring, _rank, _timeline
    with _lock:
        if enabled is not None:
            _enabled = bool(enabled)
        if ring is not None and int(ring) != _ring.maxlen:
            _ring = deque(_ring, maxlen=max(1, int(ring)))
        if rank is not _UNSET:
            _rank = None if rank is None else int(rank)
        if timeline is not _UNSET:
            _timeline = timeline


def now_us() -> float:
    """The span clock *now*: monotonic time on the process's wall-clock
    anchor, in unix microseconds (the merge step corrects per-process
    skew; see module docstring)."""
    return _ANCHOR_US + time.monotonic_ns() / 1e3


def mono_us(t_mono: float) -> float:
    """A ``time.monotonic()`` stamp (seconds) on the span clock."""
    return _ANCHOR_US + t_mono * 1e6


# Span ids have to be unique, not secret: a generator seeded once from
# the OS, and the pid read once — a span on the hot path (three a
# serving step) makes no system call.  A forked child reseeds and
# re-reads, or it would repeat its parent's ids under its parent's pid.
_ids = random.Random(os.urandom(16))
_pid = os.getpid()


def _after_fork_in_child() -> None:
    global _pid
    _ids.seed(os.urandom(16))
    _pid = os.getpid()


os.register_at_fork(after_in_child=_after_fork_in_child)


def _new_id(nbytes: int) -> str:
    return "%0*x" % (2 * nbytes, _ids.getrandbits(8 * nbytes))


def process_rank() -> Optional[int]:
    """This process's rank for span/scrape tagging: the live world's
    (pinned by ``hvd.init`` through :func:`configure`), else the launch
    env (``HVD_TPU_PROCESS_ID`` — launcher and agent RPC is traced
    too), else None.  The one lookup every tagging site (spans,
    ``TraceRequest``, flight dumps) shares."""
    rank = _rank
    if rank is not None:
        return rank
    raw = os.environ.get("HVD_TPU_PROCESS_ID")
    try:
        return int(raw) if raw is not None else None
    except ValueError:
        return None


def current() -> Optional[Tuple[str, str]]:
    """The calling thread's live ``(trace_id, span_id)`` context, or
    None outside any span."""
    return getattr(_tls, "ctx", None)


def new_context() -> Tuple[str, str]:
    """Mint a fresh root ``(trace_id, span_id)`` identity without
    recording anything — for a span whose interval is only known after
    the fact (record it at completion with ``record_span(ctx=...)``);
    install it with :func:`use_context` so work done meanwhile parents
    under it."""
    return (_new_id(16), _new_id(8))


@contextlib.contextmanager
def use_context(ctx: Optional[Tuple[str, str]]):
    """Install ``ctx`` as the calling thread's current context for the
    block (no span is recorded — pair with :func:`new_context` /
    ``record_span(ctx=...)`` for deferred spans)."""
    prev = current()
    _tls.ctx = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev


def _append(rec: Dict[str, Any]) -> None:
    with _lock:
        _ring.append(rec)


def _scalars(args: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The args a Timeline slice or a profiler event can carry."""
    return {k: v for k, v in (args or {}).items()
            if isinstance(v, (bool, int, float, str))}


def _emit_timeline(rec: Dict[str, Any]) -> None:
    """Mirror one finished span onto the live framework Timeline (slice
    + flow endpoints for RPC spans) — the one mirror.  Timeline
    timestamps are relative to ITS clock, so the slice is anchored by
    how long ago the span *ended* on the span clock — a reconstructed
    span (``record_span`` with historical timing, e.g. the batcher's
    queued window recorded after prefill) lands where it happened, not
    ending at "now"."""
    tl = _timeline
    if tl is None:
        return
    try:
        if not tl.enabled:
            return
        lag = max(0.0, now_us() - (rec["start_us"] + rec["dur_us"]))
        end = tl._now_us() - lag
        start = max(0.0, end - rec["dur_us"])
        tl.record(rec["trace_id"][:8], rec["name"], start, rec["dur_us"],
                  {"trace_id": rec["trace_id"], "span_id": rec["span_id"],
                   "parent_id": rec["parent_id"], **_scalars(rec["args"])})
        if rec["kind"] == "client":
            tl.flow(rec["name"], rec["span_id"], "s", ts_us=start)
        elif rec["kind"] == "server" and rec["parent_id"]:
            tl.flow(rec["name"], rec["parent_id"], "f", ts_us=start)
    except Exception:
        pass   # observability never takes down the path being observed


def _annotation(name: str, args: Optional[Dict[str, Any]]):
    """The profiler's view of a span: a ``TraceAnnotation`` carrying the
    span's scalar args — or nothing, in a process that never imported
    JAX (a launcher's RPC client: no profiler session can be live
    there, and importing JAX for it would cost a second)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    try:
        return jax.profiler.TraceAnnotation(name, **_scalars(args))
    except Exception:
        return contextlib.nullcontext()


def annotate(name: str):
    """A phase inside a span, for the profiler alone: a
    ``TraceAnnotation`` and nothing else — no ids, no ring entry, no
    Timeline slice.  A decode step's dispatch and fence are entered so:
    with a profiler session live they lie on ``/host:CPU`` inside their
    span, on the clock of ``XLA Ops``; the ring holds their lengths as
    the span's args, where three more entries a step would wash a
    window's opening out of it (docs/tracing.md).  The annotation
    begins where it is made, not where it is entered: make it in the
    ``with`` statement, the call's arguments built beforehand."""
    if not enabled():
        return contextlib.nullcontext()
    return _annotation(name, None)


def scope(name: str):
    """A named region INSIDE a compiled program: ``jax.named_scope``,
    so every operation traced in the block carries ``name`` in its
    metadata (``op_name``) — what xprof groups by and what a reader of
    the device trace attributes device time with.  Metadata only: no
    operation, shape or fusion changes."""
    import jax

    return jax.named_scope(name)


def record_span(name: str, *, parent: Optional[Tuple[str, str]],
                start_us: float, dur_us: float, kind: str = "internal",
                args: Optional[Dict[str, Any]] = None,
                ctx: Optional[Tuple[str, str]] = None) -> Optional[str]:
    """Record one finished span with explicit timing (reconstructed
    phases — the batcher's queued/decode windows — where a context
    manager cannot wrap the interval).  ``parent=None`` roots a fresh
    trace.  ``ctx`` records the span AS a pre-minted
    :func:`new_context` identity — how a deferred root (a request whose
    total latency is only known at completion, with child phases
    already recorded against the context) joins its own trace.  Returns
    the span id (None when tracing is off)."""
    if not enabled():
        return None
    if parent is not None:
        trace_id, parent_id = parent
    else:
        trace_id, parent_id = _new_id(16), None
    if ctx is not None:
        trace_id = str(ctx[0])
    rec = {
        "name": name,
        "trace_id": trace_id,
        "span_id": str(ctx[1]) if ctx is not None else _new_id(8),
        "parent_id": parent_id,
        "kind": kind,
        "start_us": float(start_us),
        "dur_us": max(0.0, float(dur_us)),
        "rank": process_rank(),
        "pid": _pid,
        "args": dict(args) if args else {},
    }
    _append(rec)
    _emit_timeline(rec)
    return rec["span_id"]


def instant(name: str, args: Optional[Dict[str, Any]] = None) -> Optional[str]:
    """Zero-duration span at *now*, parented to the calling thread's
    context (a point event that must survive in the flight ring — fault
    firings use this)."""
    if not enabled():
        return None
    return record_span(name, parent=current(), start_us=now_us(),
                       dur_us=0.0, kind="instant", args=args)


@contextlib.contextmanager
def span(name: str, *, root: bool = False,
         parent: Optional[Tuple[str, str]] = None, kind: str = "internal",
         args: Optional[Dict[str, Any]] = None):
    """Context manager timing one span; yields the new ``(trace_id,
    span_id)`` context (None when tracing is off) and installs it as the
    thread's current context for the duration, so nested spans and RPC
    clients parent correctly without plumbing.

    ``root=True`` forces a fresh trace (the step loop / router
    admission); ``parent`` grafts onto an explicit remote context (the
    server side of an RPC).  An escaping exception is recorded in the
    span's args as ``error`` and re-raised.  The same interval is
    entered as a ``jax.profiler.TraceAnnotation`` (module docstring:
    the profiler bridge)."""
    if not enabled():
        yield None
        return
    if root:
        ctx_parent: Optional[Tuple[str, str]] = None
    elif parent is not None:
        ctx_parent = (str(parent[0]), str(parent[1]))
    else:
        ctx_parent = current()
    if ctx_parent is not None:
        trace_id, parent_id = ctx_parent
    else:
        trace_id, parent_id = _new_id(16), None
    ctx = (trace_id, _new_id(8))
    prev = current()
    _tls.ctx = ctx
    error = None
    annotation = _annotation(name, args)
    annotation.__enter__()
    start = now_us()
    try:
        yield ctx
    except BaseException as e:
        error = type(e).__name__
        raise
    finally:
        dur = max(0.0, now_us() - start)
        annotation.__exit__(None, None, None)
        _tls.ctx = prev
        # ``args`` is read here, not at entry: a caller that learns a
        # span's counts while it runs (the batcher's step) fills the
        # dict it passed in.
        span_args = dict(args) if args else {}
        if error is not None:
            span_args["error"] = error
        rec = {
            "name": name,
            "trace_id": trace_id,
            "span_id": ctx[1],
            "parent_id": parent_id,
            "kind": kind,
            "start_us": start,
            "dur_us": dur,
            "rank": process_rank(),
            "pid": _pid,
            "args": span_args,
        }
        _append(rec)
        _emit_timeline(rec)


# --- wire propagation --------------------------------------------------------

def inject(obj: Any, ctx: Optional[Tuple[str, str]] = None) -> Any:
    """Attach the context to an outbound request object (instance
    attribute — the pickled payload carries it, the HMAC frame format
    doesn't change).  No-op without a context."""
    ctx = ctx if ctx is not None else current()
    if ctx is not None:
        try:
            obj._hvd_trace = (str(ctx[0]), str(ctx[1]))
        except AttributeError:
            pass   # __slots__ classes opt out of propagation
    return obj


def extract(obj: Any) -> Optional[Tuple[str, str]]:
    """Read a propagated context off an inbound request (None when the
    peer didn't trace, or predates tracing)."""
    ctx = getattr(obj, "_hvd_trace", None)
    if (isinstance(ctx, (tuple, list)) and len(ctx) == 2
            and all(isinstance(x, str) for x in ctx)):
        return (ctx[0], ctx[1])
    return None


# --- ring access -------------------------------------------------------------

def snapshot(clear: bool = False) -> List[Dict[str, Any]]:
    """Copy of the span ring, oldest first (the ``TraceRequest`` payload
    and the flight recorder's span section).  ``clear=True`` drains it
    (a collector that owns the spans it fetched)."""
    with _lock:
        out = [dict(r) for r in _ring]
        if clear:
            _ring.clear()
    return out


def recent(name: str, last: int,
           since_us: float = 0.0) -> List[Dict[str, Any]]:
    """The newest ``last`` spans called ``name`` that began at or after
    ``since_us``, oldest first.  The ring is walked from its newest end
    until they are found and only they are copied: ``snapshot()``
    copies 16,384 records, some 10 ms under the lock that every span's
    exit takes, which a reader polled beside a serving step
    (``InferenceEngine.kv_stats``) must not hold for so long."""
    out: List[Dict[str, Any]] = []
    with _lock:
        for rec in reversed(_ring):
            if len(out) == last:
                break
            if rec["name"] == name and rec["start_us"] >= since_us:
                out.append(dict(rec))
    out.reverse()
    return out


def clear() -> None:
    with _lock:
        _ring.clear()


# --- clock-offset estimation (Cristian over ping RTTs) -----------------------

def estimate_clock_offset(
        samples: Sequence[Tuple[float, float, float]]) -> Tuple[float, float]:
    """Estimate a peer's clock offset from RTT samples.

    Each sample is ``(send_us, recv_us, peer_us)`` on the local clock /
    the peer's clock: the local process sent a ping at ``send_us``, got
    the answer at ``recv_us``, and the answer carried the peer's clock
    reading ``peer_us`` (``PingResponse.clock_us``).  Assuming the wire
    is roughly symmetric, the peer stamped at the local midpoint, so
    ``offset = peer_us - (send_us + recv_us) / 2`` with error bounded by
    RTT/2 — the **minimum-RTT** sample gives the tightest bound
    (Cristian's algorithm).  Returns ``(offset_us, error_bound_us)``;
    ``local + offset ≈ peer``.
    """
    if not samples:
        raise ValueError("estimate_clock_offset needs at least one sample")
    best = None
    for send_us, recv_us, peer_us in samples:
        rtt = recv_us - send_us
        if rtt < 0:
            raise ValueError(f"negative RTT sample: send={send_us} "
                             f"recv={recv_us}")
        off = peer_us - (send_us + recv_us) / 2.0
        if best is None or rtt < best[1]:
            best = (off, rtt)
    return best[0], best[1] / 2.0


# --- merge + critical path ---------------------------------------------------

def _span_tid(rec: Dict[str, Any]) -> int:
    """Stable per-trace lane so each trace renders as its own row.
    Our ids are hex, but merged files may carry foreign ones — fall
    back to a stable string hash."""
    tid = str(rec["trace_id"])
    try:
        return int(tid[:8], 16) & 0x7FFFFFFF
    except ValueError:
        import zlib

        return zlib.crc32(tid.encode()) & 0x7FFFFFFF


def merge_traces(groups: Dict[str, Tuple[float, List[Dict[str, Any]]]]
                 ) -> List[Dict[str, Any]]:
    """Merge per-process span sets into ONE Chrome-trace event list.

    ``groups`` maps a process label (e.g. ``rank0`` / ``router``) to
    ``(offset_us, spans)`` where ``offset_us`` converts that process's
    clock onto the reference clock (``ref + offset = theirs``, i.e. the
    :func:`estimate_clock_offset` output against the reference process
    — each span's ``start_us`` has the offset *subtracted*).  Emits
    process-name metadata, one ``"X"`` slice per span (args carry the
    span identity), and ``"s"``/``"f"`` flow pairs for every
    parent→child edge that crosses processes, so Perfetto draws the
    causal arrow between ranks."""
    events: List[Dict[str, Any]] = []
    where: Dict[str, Tuple[int, int, float]] = {}  # span_id -> (pid, tid, ts)
    spans_flat: List[Tuple[int, Dict[str, Any], float]] = []
    for pid, (label, (offset_us, spans)) in enumerate(sorted(groups.items()),
                                                     start=1):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": label}})
        for rec in spans:
            ts = float(rec["start_us"]) - float(offset_us)
            spans_flat.append((pid, rec, ts))
            where[rec["span_id"]] = (pid, _span_tid(rec), ts)
    for pid, rec, ts in spans_flat:
        events.append({
            "name": rec["name"], "cat": "trace", "ph": "X",
            "ts": ts, "dur": rec["dur_us"], "pid": pid,
            "tid": _span_tid(rec),
            "args": {"trace_id": rec["trace_id"],
                     "span_id": rec["span_id"],
                     "parent_id": rec["parent_id"],
                     "rank": rec.get("rank"), **rec.get("args", {})},
        })
    for pid, rec, ts in spans_flat:
        parent = rec.get("parent_id")
        if not parent or parent not in where:
            continue
        ppid, ptid, pts = where[parent]
        if ppid == pid:
            continue   # in-process nesting needs no arrow
        fid = rec["span_id"]
        events.append({"name": rec["name"], "cat": "trace", "ph": "s",
                       "id": fid, "ts": pts, "pid": ppid, "tid": ptid})
        events.append({"name": rec["name"], "cat": "trace", "ph": "f",
                       "bp": "e", "id": fid, "ts": ts, "pid": pid,
                       "tid": _span_tid(rec)})
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events


def unresolved_parents(spans: Iterable[Dict[str, Any]]) -> List[str]:
    """Parent ids referenced by some span but present in none — the
    merge-completeness check (a trace whose every parent resolves was
    collected whole)."""
    ids = {r["span_id"] for r in spans}
    return sorted({r["parent_id"] for r in spans
                   if r.get("parent_id") and r["parent_id"] not in ids})


def trace_ids(spans: Iterable[Dict[str, Any]]) -> List[str]:
    """Distinct trace ids, by first appearance."""
    seen: List[str] = []
    for r in spans:
        if r["trace_id"] not in seen:
            seen.append(r["trace_id"])
    return seen


def dump_merged(path: str, label: Optional[str] = None,
                report: bool = True) -> Optional[Dict[str, Any]]:
    """Write this process's span ring as a self-contained merged trace
    artifact (the single-process degenerate of ``scripts/trace_merge.py``
    — offset 0; benches use this for ``--trace DIR``).  Returns the
    headline critical-path report (largest trace), or None when the
    ring is empty."""
    import json

    spans = snapshot()
    if label is None:
        rank = process_rank()
        label = f"rank{rank}" if rank is not None else f"pid{os.getpid()}"
    reports: List[Dict[str, Any]] = []
    if spans and report:
        reports = sorted((critical_path(spans, tid)
                          for tid in trace_ids(spans)),
                         key=lambda r: -r["total_us"])
    doc = {
        "traceEvents": merge_traces({label: (0.0, spans)}),
        "displayTimeUnit": "ms",
        "metadata": {
            "tool": "horovod_tpu obs.trace.dump_merged",
            "processes": {label: {"spans": len(spans),
                                  "clock_offset_us": 0.0}},
            "traces": len(trace_ids(spans)),
            "spans": len(spans),
            "unresolved_parents": unresolved_parents(spans),
            **({"critical_paths": reports} if reports else {}),
        },
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    return reports[0] if reports else None


def critical_path(spans: Sequence[Dict[str, Any]],
                  trace_id: Optional[str] = None) -> Dict[str, Any]:
    """Per-trace critical-path report: which hop/phase dominated.

    Picks ``trace_id`` (default: the trace with the longest root span),
    builds the parent tree, and charges each span its **self time**
    (duration minus its direct children's durations, clamped at 0 —
    time spent in that hop itself, not delegated further).  The
    ``dominant`` entry names the span family with the largest summed
    self time: for a serve trace that is the phase that dominated TTFT
    or total latency; for a step trace, the hop that dominated step
    time.  ``path`` is the greedy longest-child walk from the root."""
    spans = [r for r in spans]
    if not spans:
        raise ValueError("critical_path needs at least one span")
    if trace_id is None:
        roots = [r for r in spans if not r.get("parent_id")]
        pick = max(roots or spans, key=lambda r: r["dur_us"])
        trace_id = pick["trace_id"]
    trace = [r for r in spans if r["trace_id"] == trace_id]
    by_id = {r["span_id"]: r for r in trace}
    children: Dict[Optional[str], List[Dict[str, Any]]] = {}
    for r in trace:
        parent = r.get("parent_id")
        children.setdefault(parent if parent in by_id else None,
                            []).append(r)
    self_us: Dict[str, float] = {}
    for r in trace:
        kids = children.get(r["span_id"], [])
        own = max(0.0, r["dur_us"] - sum(k["dur_us"] for k in kids))
        self_us[r["name"]] = self_us.get(r["name"], 0.0) + own
    roots = children.get(None, [])
    root = max(roots, key=lambda r: r["dur_us"]) if roots \
        else max(trace, key=lambda r: r["dur_us"])
    path = [root["name"]]
    node = root
    while True:
        kids = children.get(node["span_id"], [])
        if not kids:
            break
        node = max(kids, key=lambda k: k["dur_us"])
        path.append(node["name"])
    dominant = max(self_us.items(), key=lambda kv: kv[1])
    return {
        "trace_id": trace_id,
        "root": root["name"],
        "total_us": root["dur_us"],
        "dominant": dominant[0],
        "dominant_self_us": dominant[1],
        "path": path,
        "self_us": dict(sorted(self_us.items(),
                               key=lambda kv: -kv[1])),
        "unresolved_parents": unresolved_parents(trace),
    }
