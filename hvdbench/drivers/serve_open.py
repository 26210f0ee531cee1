"""Traffic kind ``serve-open``: an open loop.  A feeder thread submits
each request when it is due (one arrival inside every ``1 / rate``
slot), whatever the system is doing; the main thread drives
``batcher.step()``.  Every latency is timed from when the request was
due.  A pre-roll fills the slots before the window opens; the window
closes on the clock and nothing is drained.

``run`` takes the harness class: ``serve-open-state`` hands it its own
and shares this loop.  Whatever the harness, the heap is frozen between
warm-up and the stream (``_serve.freeze_heap``)."""

from __future__ import annotations

import json
import queue
import threading
import time

from hvdbench import device, generator, stats, window
from hvdbench.drivers._serve import SPANS, ServeHarness, freeze_heap


def _feeder(h, traffic, seed, vocab, t_stream0, stop, out) -> None:
    block = 0
    while not stop.is_set():
        for spec in generator.request_block(traffic, seed, block, vocab):
            due = t_stream0 + spec.due_s
            while True:
                wait = due - time.monotonic()
                if wait <= 0 or stop.is_set():
                    break
                stop.wait(min(wait, 0.05))
            if stop.is_set():
                return
            try:
                out.put(h.submit(spec, due))
            except Exception as e:   # reported by the main thread
                out.put(e)
                return
        block += 1


def run(ctx, harness=ServeHarness) -> dict:
    traffic, seed = ctx.traffic, ctx.seed
    vocab = ctx.config["vocab_size"]
    h = harness(ctx)
    h.warm([p for p, _ in generator.block_multiset(traffic)])
    freeze_heap(ctx)

    stop = threading.Event()
    arrivals: "queue.SimpleQueue" = queue.SimpleQueue()
    t_stream0 = time.monotonic() + 0.05
    t_open = t_stream0 + float(traffic["preroll_s"])
    t_close = t_open + ctx.seconds
    feeder = threading.Thread(
        target=_feeder, name="hvdbench-feeder", daemon=True,
        args=(h, traffic, seed, vocab, t_stream0, stop, arrivals))
    feeder.start()
    everyone = []
    counter = device.CompileCounter()
    open_snap = None
    trace_dir = trace_path = t_trace = None
    try:
        while True:
            while True:
                try:
                    item = arrivals.get_nowait()
                except queue.Empty:
                    break
                if isinstance(item, Exception):
                    raise item
                h.follow(item)
                everyone.append(item)
            now = time.monotonic()
            if open_snap is None and now >= t_open:
                open_snap = (counter.count,
                             sum(h.engine.trace_counts.values()))
            if (ctx.trace and trace_dir is None and now >= t_close
                    - min(ctx.seconds, float(traffic["trace_seconds"]))):
                trace_dir = device.start_trace(ctx.cell["name"])
                t_trace = time.monotonic()
            if now >= t_close:
                break
            if not h.live:
                time.sleep(0.002)
                continue
            h.step()
    finally:
        stop.set()
        feeder.join(timeout=10)
    t_stop = time.monotonic()
    if feeder.is_alive():
        raise RuntimeError("the feeder thread did not stop")
    if trace_dir is not None:
        trace_path = device.stop_trace(trace_dir)

    t0, t1 = t_open, t_close
    finished = [tr for tr in h.done
                if tr.req.error is None and t0 < tr.req.finished_at <= t1
                and len(tr.req.tokens) > 1]
    tpot = [(tr.req.finished_at - tr.req.first_token_at)
            / (len(tr.req.tokens) - 1) * 1e3 for tr in finished]
    gaps = [g * 1e3 for tr in everyone
            for g in window.token_gaps(tr.token_times, t0, t1)]
    due_in = [tr for tr in everyone if t0 < tr.due <= t1]
    late = [(tr.submitted - tr.due) * 1e3 for tr in due_in]
    print(json.dumps({"host_pauses": h.host_pauses(t0, t1)}), flush=True)
    print(json.dumps({"gap_populations": h.gap_populations(
        everyone, t0, t1)}), flush=True)
    compilations = ((counter.count - open_snap[0])
                    + sum(h.engine.trace_counts.values()) - open_snap[1])
    t_half = (t0 + t1) / 2
    waits = [(tr.token_times[0], (tr.token_times[0] - tr.due) * 1e3)
             for tr in everyone if tr.token_times
             and t0 < tr.token_times[0] <= t1]
    ttft = [w for _, w in waits]
    halves = [[w for t, w in waits if t <= t_half],
              [w for t, w in waits if t > t_half]]
    # On an earlier line: is a backlog growing?  Waiting requests at the
    # window's close, and the first token's wait in each half.
    print(json.dumps({"backlog": {
        "rate_per_s": traffic["rate_per_s"],
        "waiting_at_close": h.batcher.queue_depth(),
        "in_flight_at_close": len(h.live),
        "ttft_p50_ms_by_half": [stats.median(x) if x else None
                                for x in halves],
        "ttft_max_ms_by_half": [max(x) if x else None for x in halves]}}),
        flush=True)
    facts = {"requests_finished": len(finished), "gaps": len(gaps),
             "requests_due": len(due_in),
             "ttft_ms": ttft, "generator_late_ms": late,
             "slot_occupancy": h.occupancy(t0, t1),
             "queue_at_close": h.batcher.queue_depth(),
             "window_compilations": compilations,
             "warmed_buckets": h.warmed_buckets,
             "tokens": window.credited_tokens(h.steps, t0, t1),
             "elapsed_s": t1 - t0,
             "trace_window_s": (t_stop - t_trace) if t_trace else None}
    failed = sum(1 for tr in due_in
                 if tr.req.error is not None)
    end_to_end = {}
    if tpot:
        end_to_end["tpot_p50_ms"] = stats.median(tpot)
    if gaps:
        end_to_end["itl_p95_ms"] = stats.percentile(gaps, 95)
    attempted = len(due_in)
    checks = h.close_and_check()
    facts["trace_counts"] = h.trace_counts
    facts.update(h.extra_facts)
    return {
        "t_window_open": t_open,
        "attempted": attempted, "failed": failed,
        "end_to_end": end_to_end,
        "checks": checks, "facts": facts, "devices": h.devices,
        "memory": h.memory, "trace_path": trace_path, "spans": SPANS,
    }
