"""Scheduler: median wait in the admission queue, from the program's own
``hvd_tpu_serve_queued`` spans (submit to the pop into a slot) that
ended inside the measured window.  The spans are recorded after the
fact and live in the program's span ring only
(``reduce/program_spans.py``); the window is the ``elapsed_s`` that end
with the newest ``hvd_tpu_serve_step``.  Beside ``ttft_p50_ms`` it
splits the wait for the first token into queue and prefill."""
from hvdbench import stats
from hvdbench.layers import named
from hvdbench.reduce import program_spans


def read(wanted, view):
    names = named(wanted, "queue_wait_ms")
    elapsed = view.facts.get("elapsed_s")
    if not names or not elapsed:
        return {}
    try:
        spans = program_spans.ring()
        window = program_spans.ring_window(spans, elapsed) if spans else None
        if window is None:
            return {}
        waits = [s["dur_us"] / 1e3 for s in spans
                 if s["name"] == program_spans.QUEUED
                 and window[0] < s["start_us"] + s["dur_us"] <= window[1]]
        if not waits:
            return {}
        program_spans.say(queue_wait={"requests": len(waits),
                                      "p95_ms": stats.percentile(waits, 95),
                                      "max_ms": max(waits)})
        return {n: stats.median(waits) for n in names}
    except Exception as e:   # a reader never takes the result line down
        program_spans.say(queue_wait_ms=f"not read: {type(e).__name__}: {e}")
        return {}
