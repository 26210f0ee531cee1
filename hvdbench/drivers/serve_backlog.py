"""Traffic kind ``serve-backlog``: a closed loop with a standing
backlog, fed through ``ContinuousBatcher.submit`` a whole block at a
time.  The measured interval runs from one block boundary to another
(``window.whole_block_window``): it never waits for a request to
finish and holds no drain."""

from __future__ import annotations

import json
import statistics
import time

from hvdbench import device, generator, window
from hvdbench.drivers._serve import SPANS, ServeHarness, freeze_heap


def run(ctx) -> dict:
    traffic, seed = ctx.traffic, ctx.seed
    vocab = ctx.config["vocab_size"]
    block = int(traffic["block"])
    first = int(traffic["preroll_blocks"])
    ahead = int(traffic["backlog_blocks"])
    h = ServeHarness(ctx)
    h.warm([p for p, _ in generator.block_multiset(traffic)])
    freeze_heap(ctx)

    submitted_blocks = 0

    def top_up() -> None:
        """Keep ``ahead`` whole blocks waiting."""
        nonlocal submitted_blocks
        while h.batcher.queue_depth() <= (ahead - 1) * block:
            for spec in generator.request_block(traffic, seed,
                                                submitted_blocks, vocab):
                h.follow(h.submit(spec, due=0.0))
            submitted_blocks += 1

    counter = device.CompileCounter()
    open_snap = None
    t_open = None
    trace_dir = trace_path = None
    t_trace = None
    seen_bounds = {}
    while True:
        top_up()
        snap = (counter.count, sum(h.engine.trace_counts.values()))
        rec = h.step()
        for index in rec.admitted:
            if index % block == 0:
                seen_bounds[index // block] = rec.t_before
        if t_open is None:
            if first in seen_bounds:
                # The step just made was the window's first: the
                # counts read before it are the window's zero.
                t_open = seen_bounds[first]
                open_snap = snap
            continue
        elapsed = rec.t_after - t_open
        if (ctx.trace and trace_dir is None
                and elapsed >= ctx.seconds - float(traffic["trace_seconds"])):
            trace_dir = device.start_trace(ctx.cell["name"])
            t_trace = time.monotonic()
        last = max(seen_bounds)
        if last > first:
            per_block = (seen_bounds[last] - t_open) / (last - first)
            if (seen_bounds[last] - t_open) + per_block > ctx.seconds \
                    and rec.admitted and rec.admitted[0] % block == 0:
                break
        if elapsed > ctx.seconds:
            break
    t_stop = time.monotonic()
    if trace_dir is not None:
        trace_path = device.stop_trace(trace_dir)
    win = window.whole_block_window(h.steps, block, first, ctx.seconds)
    if win is None:
        raise RuntimeError(f"not one whole block of {block} requests fits "
                           f"into {ctx.seconds} s")
    t0, t1, blocks = win
    tokens = window.credited_tokens(h.steps, t0, t1)
    print(json.dumps({"host_pauses": h.host_pauses(t0, t1)}), flush=True)
    compilations = ((counter.count - open_snap[0])
                    + sum(h.engine.trace_counts.values()) - open_snap[1])
    facts = {"blocks": blocks, "elapsed_s": t1 - t0, "tokens": tokens,
             "steps": sum(1 for s in h.steps if t0 < s.t_after <= t1),
             "slot_occupancy": h.occupancy(t0, t1),
             "window_compilations": compilations,
             "warmed_buckets": h.warmed_buckets,
             "trace_window_s": (t_stop - t_trace) if t_trace else None}
    # On an earlier line: how long a step took, by the prompt bucket of
    # the request it admitted and by the slots busy after it.
    prompt_len = {tr.spec.index: len(tr.spec.prompt)
                  for tr in h.done + list(h.live.values())}
    groups = {}
    for s in h.steps:
        if not (t0 < s.t_after <= t1):
            continue
        bucket = (h.engine.bucket_for(prompt_len[s.admitted[0]])
                  if s.admitted else 0)
        groups.setdefault(f"bucket{bucket}_active{s.active}", []).append(
            s.t_after - s.t_before)
    print(json.dumps({"step_seconds": {
        k: [len(v), round(min(v), 4), round(statistics.median(v), 4),
            round(max(v), 4)] for k, v in sorted(groups.items())}}),
        flush=True)
    attempted = blocks * block
    failed = h.failed
    checks = h.close_and_check()
    facts["trace_counts"] = h.trace_counts
    return {
        "t_window_open": t0,
        "attempted": attempted, "failed": failed,
        "end_to_end": {"serve_tokens_per_s": tokens / (t1 - t0)},
        "checks": checks, "facts": facts, "devices": h.devices,
        "memory": h.memory, "trace_path": trace_path, "spans": SPANS,
    }
