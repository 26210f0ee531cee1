"""Traffic kind ``serve-open-blocks``: ``serve-open``'s open loop, window
and metrics to the letter, for a model that generates by diffusion over
blocks — a prefill yields no token, a step yields none to a block's
worth a row, and every served token was chosen at one of its block's
denoising steps.

Five things differ, all in the harness and none in the loop:

* **the deployment's settings** — as ``serve_open_mixed.MixedHarness``
  sets them: the slots, the buckets, the block and the batcher's cap in
  the process's environment while the engine and the batcher are built
  (``HVD_TPU_SERVE_MAX_BATCH``, ``_PREFILL_BUCKETS``, ``_KV_BLOCK``,
  ``_MAX_TOKENS``), refusing to run if the engine reports other values
  than the configuration states.  A request's schedule
  (``run.generation``: denoising steps a block, the transfer rule, its
  threshold, the temperature) rides its ``SamplingParams``.
* **warm-up** — a cache that shares no prefix never copies a block:
  one request through the public path for each prefill bucket the
  traffic uses, the block step riding along (``StateHarness.warm``).
* **an admission** is learned from ``req.admitted_at``, not from a
  first token: the step that prefilled a request gave it none.
* **the cache's and the block steps' counters** — ``engine.kv_stats()``
  is read when the window opens and again before the engine is freed;
  both go into the run's ``facts`` (``kv_at_open``, ``kv``), and the
  per-layer readers take a counter's growth between the two.
* **the check** holds every served token to the reference at the
  denoising step it was chosen at (``served_logit_gap``), and every
  step's choice of positions to the reference's confidences
  (``served_order_gap``): ``reference.sdar.served_token_gaps`` is handed
  each sampled request's tokens with their steps.
"""

from __future__ import annotations

import gc
import json
import time

from hvdbench import check, device, generator
from hvdbench.drivers import serve_open
from hvdbench.drivers._serve import ServeHarness, Tracked
from hvdbench.drivers.serve_open_mixed import _environment
from hvdbench.drivers.serve_open_state import StateHarness
from hvdbench.window import StepRecord


class BlocksHarness(ServeHarness):
    def __init__(self, ctx):
        run = ctx.config["run"]
        stated = run["engine"]
        with _environment(
                HVD_TPU_SERVE_MAX_BATCH=int(stated["max_slots"]),
                HVD_TPU_SERVE_PREFILL_BUCKETS=",".join(
                    str(int(b)) for b in stated["prefill_buckets"]),
                HVD_TPU_SERVE_KV_BLOCK=int(stated["kv_block"]),
                HVD_TPU_SERVE_MAX_TOKENS=int(
                    run["batcher"]["max_new_tokens"])):
            super().__init__(ctx)
        cap = self.batcher.max_new_tokens_cap
        if cap != int(run["batcher"]["max_new_tokens"]):
            raise RuntimeError(
                f"the batcher cuts answers at {cap}; the configuration "
                f"file states {run['batcher']['max_new_tokens']}")
        if int(ctx.traffic["output_len"]["max"]) > cap:
            raise RuntimeError(
                f"the traffic's answers reach "
                f"{ctx.traffic['output_len']['max']} tokens and the "
                f"batcher cuts them at {cap}")
        if "block_steps" not in self.engine.kv_stats():
            raise RuntimeError(
                f"traffic kind serve-open-blocks drives a model that "
                f"generates by blocks; the engine built for "
                f"{ctx.config['name']!r} counts no block step")
        gen = run["generation"]
        for key in ("block_length", "denoising_steps", "transfer"):
            if gen[key] != ctx.traffic["generation"][key]:
                raise RuntimeError(
                    f"the traffic states {key} "
                    f"{ctx.traffic['generation'][key]!r}; the "
                    f"configuration {gen[key]!r}")
        self._schedule = dict(
            denoising_steps=int(gen["denoising_steps"]),
            transfer=gen["transfer"], threshold=float(gen["threshold"]),
            temperature=float(gen["temperature"]))
        self._t_open = None       # on the loop's clock, from the stream
        self._kv_at_open = None

    warm = StateHarness.warm

    def submit(self, spec, due):
        if self._t_open is None:
            self._t_open = (due - spec.due_s
                            + float(self.ctx.traffic["preroll_s"]))
        now = time.monotonic()
        req = self.batcher.submit(
            list(spec.prompt),
            self.sampling(max_new_tokens=spec.max_new_tokens,
                          **self._schedule),
            deadline_s=self.deadline_s)
        return Tracked(spec, req, due, now)

    def step(self) -> StepRecord:
        """``ServeHarness.step``, an admission learned from the
        request's own ``admitted_at``."""
        if (self._kv_at_open is None and self._t_open is not None
                and time.monotonic() >= self._t_open):
            self._kv_at_open = dict(self.engine.kv_stats())
        self._in_prefill[0] = self._in_decode[0] = 0.0
        t_before, c_before = time.monotonic(), time.thread_time()
        self.batcher.step()
        t_after, c_after = time.monotonic(), time.thread_time()
        prompt_tokens = new_tokens = 0
        admitted = []
        for index in list(self.live):
            tr = self.live[index]
            if (index not in self.admissions
                    and tr.req.admitted_at is not None):
                admitted.append(index)
                self.admissions[index] = (self.engine.bucket_for(
                    len(tr.spec.prompt)), t_after)
                prompt_tokens += len(tr.spec.prompt)
            n = len(tr.req.tokens)
            if n > tr.seen:
                first = [tr.req.first_token_at] if tr.seen == 0 else []
                tr.token_times.extend(
                    first + [t_after] * (n - tr.seen - len(first)))
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

    def close_and_check(self):
        """Free the program's state, then hold a seeded sample of the
        finished requests, the longest among them, to the reference:
        each served token at the denoising step it was chosen at."""
        ctx = self.ctx
        kv, at_open = dict(self.engine.kv_stats()), self._kv_at_open or {}
        self.extra_facts = {"kv": kv, "kv_at_open": at_open}
        steps = kv.get("block_steps", 0) - at_open.get("block_steps", 0)
        print(json.dumps({"kv_counters": dict(
            {"block_steps_at_open": at_open.get("block_steps"),
             "block_steps_at_close": kv.get("block_steps")},
            **{f"{key}_a_step": (kv[key] - at_open.get(key, 0)) / steps
               for key in ("paged_live_rows", "tokens_final",
                           "experts_touched", "expert_pairs_held")
               if steps > 0 and key in kv})}), flush=True)
        finished = [tr for tr in self.done
                    if tr.req.error is None and tr.req.tokens]
        self.memory = device.memory_record(self.devices)
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
                       list(finished[i].req.tokens),
                       list(finished[i].req.token_steps)) for i in picked]
        self.engine = self.batcher = None
        for tr in self.done + list(self.live.values()):
            tr.req = None
        gc.unfreeze()
        gc.collect()

        import jax

        t = time.monotonic()
        ref = ctx.reference
        s = ref.sizes(ctx.config)
        params = jax.jit(lambda k: ref.init_params(k, s))(
            ref.seed_key(ctx.seed))
        kw = dict(pad_to=int(ctx.config["check"]["pad_to"]),
                  denoising_steps=self._schedule["denoising_steps"])
        found = None
        for precision in ctx.control_precisions:
            # The control: at the same positions, what the lower
            # precision would have served, held to the same two gaps.
            found = ref.served_token_gaps(params, sample, s,
                                          control_precision=precision, **kw)
            print(json.dumps({
                "control": precision, "seed": ctx.seed,
                "workload": ctx.cell["name"],
                "tokens": len(found["control_logit_gaps"]),
                "widest_gap": max(found["control_logit_gaps"]),
                "widest_order_gap": max(found["control_order_gaps"]),
                "program_widest_gap": max(found["logit_gaps"]),
                "program_widest_order_gap": max(found["order_gaps"]),
                "order_gaps_over_0": sum(
                    g > 0 for g in found["control_order_gaps"]),
                "program_order_gaps_over_0": sum(
                    g > 0 for g in found["order_gaps"]),
                "steps": len(found["order_gaps"])}), flush=True)
        if found is None:
            found = ref.served_token_gaps(params, sample, s, **kw)
        del params
        ctx.setup_split["reference_after_window_s"] = time.monotonic() - t
        limits = ctx.config["check"]["limits"]
        order = found["order_gaps"]
        e = check._entry("served_order_gap",
                         max(order) if order else float("inf"),
                         limits.get("served_order_gap"))
        e.update(steps=len(order), gaps_over_0=sum(g > 0 for g in order))
        return check.serve_checks(found["logit_gaps"], len(sample),
                                  limits) + [e]


def run(ctx) -> dict:
    return serve_open.run(ctx, harness=BlocksHarness)
