"""Traffic kind ``serve-open-state``: ``serve-open``'s open loop, window
and metrics to the letter, for an engine whose cache is a fixed-size
state per slot and not blocks of keys and values.

Three things differ, all in the harness and none in the loop:

* **warm-up** — ``ServeHarness.warm`` ends by demanding that the paged
  pool's block copy was compiled, and sends a prompt that shares its
  first tokens with another to reach it.  A state cache has no blocks
  and shares no prefix; here warm-up is one request through the public
  path for each prefill bucket the traffic uses, the decode program
  riding along, and nothing else.
* **the answers' cap** — ``ContinuousBatcher`` cuts every answer at
  ``HVD_TPU_SERVE_MAX_TOKENS`` (256 where nothing is set), and this
  kind's traffic is answers longer than that.  The configuration states
  the cap its deployment sets (``run.batcher.max_new_tokens``); the
  harness gives the batcher it built that cap, as the documented option
  does, and refuses traffic that asks for a longer answer than the
  batcher serves.
* **the cache's own counters** — ``engine.kv_stats()`` is read before the
  engine is freed and goes into the run's ``facts`` under ``state``
  (``state_bytes``, ``state_slots_touched``, ``state_resets``), where the
  per-layer readers of the retention metrics find it.

``serve_open.run`` takes the harness class as an argument, so the loop,
the freeze of the heap after warm-up and the metrics are one piece of
code for both kinds.
"""

from __future__ import annotations

import time

from hvdbench import generator
from hvdbench.drivers import serve_open
from hvdbench.drivers._serve import ServeHarness


class StateHarness(ServeHarness):
    def __init__(self, ctx):
        super().__init__(ctx)
        stated = ctx.config["run"]["batcher"].get("max_new_tokens")
        if stated is not None:
            # What HVD_TPU_SERVE_MAX_TOKENS sets, whichever way this
            # process resolves its configuration.
            self.batcher.max_new_tokens_cap = int(stated)
        cap = self.batcher.max_new_tokens_cap
        if int(ctx.traffic["output_len"]["max"]) > cap:
            raise RuntimeError(
                f"the traffic's answers reach "
                f"{ctx.traffic['output_len']['max']} tokens and the "
                f"batcher cuts them at {cap}")
        if self.engine.kv_mode != "state":
            raise RuntimeError(
                f"traffic kind serve-open-state drives a state cache; the "
                f"engine built for {ctx.config['name']!r} holds "
                f"{self.engine.kv_mode!r}")

    def warm(self, prompt_lens) -> None:
        t = time.monotonic()
        by_bucket = {}
        for n in prompt_lens:
            by_bucket.setdefault(self.engine.bucket_for(n), n)
        prompts = generator.warmup_prompts(
            by_bucket.values(), self.ctx.seed, self.ctx.config["vocab_size"])
        reqs = [self.batcher.submit(p, self.sampling(max_new_tokens=3),
                                    deadline_s=0) for p in prompts]
        while not all(r.done.is_set() for r in reqs):
            self.batcher.step()
        bad = [r.error for r in reqs if r.error]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad}")
        self.warmed_buckets = sorted(by_bucket)
        self.ctx.setup_split["warm_up_s"] = time.monotonic() - t

    def close_and_check(self):
        self.extra_facts = {"state": dict(self.engine.kv_stats())}
        return super().close_and_check()


def run(ctx) -> dict:
    return serve_open.run(ctx, harness=StateHarness)
