"""What the two serve kinds share: the program's ``InferenceEngine`` and
``ContinuousBatcher`` built as a user who sets nothing gets them, the
benchmark's own loop around the public ``batcher.step()`` (the five
lines of ``ContinuousBatcher.start``'s loop), a clock reading after each
step, and the check of served tokens against the reference.

``ServeRequest`` keeps only ``first_token_at`` and ``finished_at``, so
per-token times are rebuilt here: ``engine.step()`` has fenced on its
tokens before ``batcher.step()`` returns, and every token that a request
gained in a step is stamped with the clock after that step.  The first
token keeps the program's own ``first_token_at``.
"""

from __future__ import annotations

import gc
import json
import math
import time
from typing import Dict, List

from hvdbench import check, device, generator, stats
from hvdbench.window import StepRecord

SPANS = ("engine_prefill", "engine_decode")


class Tracked:
    """One request of the stream as the benchmark follows it."""

    def __init__(self, spec: generator.Request, req, due: float,
                 submitted: float):
        self.spec, self.req = spec, req
        self.due, self.submitted = due, submitted
        self.seen = 0
        self.token_times: List[float] = []


def _annotated(fn, name):
    import jax

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    return wrapped


def _timed(fn, into: list):
    """``fn`` with the wall time of each call added to ``into[0]``: two
    clock readings a call, so that a long step can be split into
    prefill, decode and the scheduler's own time."""
    def wrapped(*args, **kwargs):
        t = time.monotonic()
        try:
            return fn(*args, **kwargs)
        finally:
            into[0] += time.monotonic() - t

    return wrapped


class GcWatch:
    """Python's own collections, timed: a full collection in the middle
    of a synchronous serving loop is a pause the device sits through."""

    def __init__(self):
        self.pauses = []          # (clock at end, seconds, generation)
        self._t = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            now = time.monotonic()
            self.pauses.append((now, now - self._t, info["generation"]))

    def close(self):
        gc.callbacks.remove(self._on_gc)

    def report(self, t0: float, t1: float) -> dict:
        inside = [(s, g) for t, s, g in self.pauses if t0 < t <= t1]
        return {"collections": len(inside),
                "full_collections": sum(g == 2 for _, g in inside),
                "seconds": sum(s for s, _ in inside),
                "longest": sorted(((round(s, 4), g) for s, g in inside),
                                  reverse=True)[:3]}


def freeze_heap(ctx) -> None:
    """What a server does once it has started: collect, then move every
    object that start-up and warm-up left into the permanent
    generation.  A full collection inside the window then walks what
    the stream made and not the model's and JAX's half a million
    objects (0.10-0.12 s, once in every process's first window)."""
    gc.collect()
    gc.freeze()
    ctx.setup_split["frozen_objects"] = gc.get_freeze_count()


class ServeHarness:
    extra_facts: dict = {}    # what a kind's own harness adds to ``facts``

    def __init__(self, ctx):
        import jax

        from horovod_tpu.serve import (ContinuousBatcher, InferenceEngine,
                                       SamplingParams)

        self.ctx = ctx
        self.sampling = SamplingParams
        cfg, split = ctx.config, ctx.setup_split
        t = time.monotonic()
        self.devices = device.require_chips(ctx.cell["chips"], ctx.rehearsal)
        split["init_s"] = time.monotonic() - t

        t = time.monotonic()
        model = ctx.family.build_model(cfg, cfg["run"]["attention"])
        params = ctx.family.make_params(cfg, ctx.seed)
        jax.block_until_ready(params)
        self.engine = InferenceEngine(model, params, seed=ctx.seed % 2**31)
        del params
        want = cfg["run"]["engine"]
        got = {"max_slots": self.engine.max_slots,
               "prefill_buckets": list(self.engine.prefill_buckets),
               "max_seq_len": self.engine.max_seq_len,
               "kv_cache": self.engine.kv_mode,
               "kv_block": self.engine.kv_block,
               "kv_blocks": self.engine.kv_blocks}
        if got != want:
            raise RuntimeError(
                f"the engine a user gets by default is {got}; the "
                f"configuration file states {want}")
        self.batcher = ContinuousBatcher(self.engine)
        if (self.batcher.max_prefill_per_step
                != cfg["run"]["batcher"]["max_prefill_per_step"]):
            raise RuntimeError("the batcher's default prefills per step "
                               "are not what the configuration states")
        if ctx.trace:
            self.engine.start = _annotated(self.engine.start,
                                           "engine_prefill")
            self.engine.step = _annotated(self.engine.step, "engine_decode")
        self._in_prefill, self._in_decode = [0.0], [0.0]
        self.engine.start = _timed(self.engine.start, self._in_prefill)
        self.engine.step = _timed(self.engine.step, self._in_decode)
        split["state_s"] = time.monotonic() - t

        self.gc_watch = GcWatch()
        self.live: Dict[int, Tracked] = {}
        self.done: List[Tracked] = []
        self.steps: List[StepRecord] = []
        # stream index -> (prefill bucket, clock after the admitting step)
        self.admissions: Dict[int, tuple] = {}
        self.failed = 0
        self.deadline_s = float(ctx.traffic["deadline_s"])

    # --- warm-up -------------------------------------------------------------

    def warm(self, prompt_lens) -> None:
        """One request through the public path for each prefill bucket
        that this cell's traffic uses, and no other; the decode program
        rides along."""
        t = time.monotonic()
        engine = self.engine
        by_bucket = {}
        for n in prompt_lens:
            by_bucket.setdefault(engine.bucket_for(n), n)
        prompts = generator.warmup_prompts(
            by_bucket.values(), self.ctx.seed, self.ctx.config["vocab_size"])
        # Two seeded prompts now and then begin with the same token, and
        # the engine then copies one KV block (partial-prefix
        # admission): that program is warmed here too, by a prompt that
        # shares its first tokens with the first warm-up prompt.
        smallest = min(by_bucket.values())
        prompts.append(prompts[0][:4] + generator.warmup_prompts(
            [smallest], self.ctx.seed + 1,
            self.ctx.config["vocab_size"])[0][4:smallest])
        reqs = []
        for p in prompts:    # one at a time: the last must find the first
            reqs.append(self.batcher.submit(
                p, self.sampling(max_new_tokens=3), deadline_s=0))
            while not reqs[-1].done.is_set():
                self.batcher.step()
        if not self.engine.trace_counts.get("kv_copy"):
            raise RuntimeError("warm-up did not reach the KV block copy")
        bad = [r.error for r in reqs if r.error]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad}")
        self.warmed_buckets = sorted(by_bucket)
        self.ctx.setup_split["warm_up_s"] = time.monotonic() - t

    # --- the stream ----------------------------------------------------------

    def submit(self, spec: generator.Request, due: float) -> Tracked:
        now = time.monotonic()
        req = self.batcher.submit(
            list(spec.prompt),
            self.sampling(max_new_tokens=spec.max_new_tokens),
            deadline_s=self.deadline_s)
        return Tracked(spec, req, due, now)

    def follow(self, tracked: Tracked) -> None:
        self.live[tracked.spec.index] = tracked

    def step(self) -> StepRecord:
        """One scheduling step and what it produced."""
        self._in_prefill[0] = self._in_decode[0] = 0.0
        t_before, c_before = time.monotonic(), time.thread_time()
        self.batcher.step()
        t_after, c_after = time.monotonic(), time.thread_time()
        prompt_tokens = new_tokens = 0
        admitted = []
        for index in list(self.live):
            tr = self.live[index]
            n = len(tr.req.tokens)
            if n > tr.seen:
                if tr.seen == 0:
                    admitted.append(index)
                    self.admissions[index] = (self.engine.bucket_for(
                        len(tr.spec.prompt)), t_after)
                    prompt_tokens += len(tr.spec.prompt)
                    tr.token_times.append(tr.req.first_token_at)
                    tr.token_times.extend([t_after] * (n - 1))
                else:
                    tr.token_times.extend([t_after] * (n - tr.seen))
                new_tokens += n - tr.seen
                tr.seen = n
            if tr.req.done.is_set() and tr.seen == len(tr.req.tokens):
                del self.live[index]
                if tr.req.error is not None:
                    self.failed += 1
                self.done.append(tr)
        rec = StepRecord(t_before, t_after, prompt_tokens, new_tokens,
                         tuple(sorted(admitted)),
                         len(self.engine.active_slots()),
                         c_after - c_before, self._in_prefill[0],
                         self._in_decode[0])
        self.steps.append(rec)
        return rec

    # --- after the window ----------------------------------------------------

    def occupancy(self, t0: float, t1: float) -> float:
        """Mean slots holding a request after a step, over the window's
        steps: the count ``ServingStats.record_step`` is given, read
        from the engine's public ``active_slots()``."""
        inside = [s.active for s in self.steps if t0 < s.t_after <= t1]
        return sum(inside) / len(inside)

    def host_pauses(self, t0: float, t1: float) -> dict:
        """On an earlier line: the window's longest steps with the CPU
        time the driving thread spent in each (a step that is long on
        the wall clock and short in CPU time was waiting, not working),
        and Python's collections inside the window."""
        inside = [s for s in self.steps if t0 < s.t_after <= t1]
        longest = sorted(inside, key=lambda s: s.t_before - s.t_after)[:3]
        return {"longest_steps_wall_cpu_prefill_decode": [
            [round(x, 4) for x in (s.t_after - s.t_before, s.cpu_s,
                                   s.prefill_s, s.decode_s)]
            for s in longest], "gc": self.gc_watch.report(t0, t1)}

    def gap_populations(self, everyone, t0: float, t1: float) -> dict:
        """On an earlier line: the window's token gaps by what the step
        that ended each one did, so that a reader sees whether the 95th
        percentile lies inside one population or between two.  A step
        that admitted a prompt stalls the other rows by a prefill of
        that bucket; the step after it binds the new row; of the rest
        the program's own ``hvd_tpu_engine_decode`` spans (the ring)
        say which uploaded something (the block table, as a rule)."""
        uploads = self._decode_uploads()
        label, after_bind = {}, False
        for s in self.steps:
            if s.admitted:
                label[s.t_after] = "stalled_by_prefill_%d" % max(
                    self.admissions[i][0] for i in s.admitted)
            elif after_bind:
                label[s.t_after] = "post_bind"
            elif uploads.get(s.t_after):
                label[s.t_after] = "upload"
            else:
                label[s.t_after] = "steady"
            after_bind = bool(s.admitted)
        gaps = []
        for tr in everyone:
            times = tr.token_times
            for k in range(1, len(times)):
                if t0 < times[k] <= t1:
                    # An admitted row's own second token comes a decode
                    # step after its first, inside the admitting step.
                    own = k == 1 and times[1] == self.admissions[
                        tr.spec.index][1]
                    gaps.append(((times[k] - times[k - 1]) * 1e3,
                                 "own_first_decode" if own
                                 else label.get(times[k], "unknown")))
        if not gaps:
            return {}
        gaps.sort()
        out = {}
        for name in sorted({g[1] for g in gaps}):
            mine = [g[0] for g in gaps if g[1] == name]
            out[name] = {"share": round(len(mine) / len(gaps), 4),
                         "p50_ms": round(stats.median(mine), 3),
                         "p95_ms": round(stats.percentile(mine, 95), 3)}
        rank = max(1, math.ceil(0.95 * len(gaps)))
        around = [g[1] for g in gaps[max(0, rank - 1 - len(gaps) // 100):
                                     rank + len(gaps) // 100]]
        return {"gaps": len(gaps), "by_population": out,
                "p95_ms": gaps[rank - 1][0], "p95_in": gaps[rank - 1][1],
                "p94_to_p96": {n: around.count(n) for n in sorted(set(around))},
                "uploads_read": bool(uploads)}

    def _decode_uploads(self) -> Dict[float, int]:
        """``t_after`` of each step -> ``args.uploads`` of the program's
        decode span inside it; empty where the ring cannot be read."""
        try:
            from horovod_tpu.obs import trace

            spans = sorted((s["start_us"], int(s["args"].get("uploads", 0)))
                           for s in trace.snapshot()
                           if s["name"] == "hvd_tpu_engine_decode")
            out, i = {}, 0
            for step in self.steps:
                lo, hi = trace.mono_us(step.t_before), trace.mono_us(
                    step.t_after)
                while i < len(spans) and spans[i][0] < lo:
                    i += 1
                if i < len(spans) and spans[i][0] <= hi:
                    out[step.t_after] = spans[i][1]
            return out
        except Exception:     # a diagnostic never takes the run down
            return {}

    def close_and_check(self) -> List[dict]:
        """Free the program's state, then hold a seeded sample of the
        finished requests, the longest among them, to the reference."""
        ctx = self.ctx
        finished = [tr for tr in self.done
                    if tr.req.error is None and tr.req.tokens]
        memory = device.memory_record(self.devices)
        self.memory = memory
        self.gc_watch.close()
        self.trace_counts = dict(self.engine.trace_counts)
        sample = []
        if finished:
            longest = max(range(len(finished)), key=lambda i: (
                len(finished[i].spec.prompt) + len(finished[i].req.tokens)))
            picked = generator.sample_indices(
                ctx.seed, list(range(len(finished))),
                int(ctx.traffic["check_requests"]), longest)
            sample = [(list(finished[i].spec.prompt),
                       list(finished[i].req.tokens)) for i in picked]
        self.engine = self.batcher = None
        for tr in self.done + list(self.live.values()):
            tr.req = None
        # What ``freeze_heap`` put out of the collector's reach comes
        # back into it, or a cycle through the engine would keep its
        # device memory under the reference.
        gc.unfreeze()
        gc.collect()

        import jax

        t = time.monotonic()
        ref = ctx.reference
        s = ref.sizes(ctx.config)
        params = jax.jit(lambda k: ref.init_params(k, s))(
            ref.seed_key(ctx.seed))
        pad_to = int(ctx.config["check"]["pad_to"])
        gaps = None
        for precision in ctx.control_precisions:
            # The control: at the same positions, the gap of the token
            # that the lower precision puts first.
            gaps, low = ref.served_token_gaps(
                params, sample, s, pad_to=pad_to,
                control_precision=precision)
            print(json.dumps({
                "control": precision, "seed": ctx.seed,
                "workload": ctx.cell["name"], "tokens": len(low),
                "widest_gap": max(low), "program_widest_gap": max(gaps),
                "gaps_over_0.05": sum(g > 0.05 for g in low)}), flush=True)
        if gaps is None:
            gaps, _ = ref.served_token_gaps(params, sample, s, pad_to=pad_to)
        del params
        ctx.setup_split["reference_after_window_s"] = time.monotonic() - t
        return check.serve_checks(gaps, len(sample),
                                  ctx.config["check"]["limits"])
