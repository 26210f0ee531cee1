"""Traffic kind ``serve-open-mixed``: ``serve-open``'s open loop, window
and metrics to the letter, for an engine whose paged cache holds layers
of two kinds — full layers that keep every position and window layers
that keep a ring — and whose deployment makes settings of its own.

Four things differ, all in the harness and none in the loop:

* **the deployment's settings** — ``ServeHarness`` builds the engine a
  user who sets nothing gets.  This kind's configuration states what
  its deployment sets (``run.engine``: the slots, and the buckets and
  the block, which the deployment leaves at their defaults;
  ``run.batcher.max_new_tokens``), and the harness sets each the way a
  deployment does, in the process's environment, while the engine and
  the batcher are built: ``HVD_TPU_SERVE_MAX_BATCH``,
  ``HVD_TPU_SERVE_PREFILL_BUCKETS``, ``HVD_TPU_SERVE_KV_BLOCK`` and
  ``HVD_TPU_SERVE_MAX_TOKENS`` (docs/serving.md).  As every harness
  does, ``ServeHarness`` then refuses to run if the engine it built
  reports other values than the file states — which is also what
  happens in a process that resolved its options before (``hvd.init``)
  and so does not read them again.  The reach (``max_seq_len``) has no
  option: it is the model's own, as its family builds it.
* **warm-up** — ``ServeHarness.warm`` demands that the pool's block
  copy was compiled, and sends a prompt that shares its first tokens
  with another to reach it.  A cache with a ring shares no prefix and
  never copies a block; here warm-up is one request through the public
  path for each prefill bucket the traffic uses, the decode program
  riding along (``StateHarness.warm``, the same words).
* **the answers' cap** — the harness refuses traffic that asks for a
  longer answer than the batcher serves.
* **the cache's own counters** — ``engine.kv_stats()`` is read when
  the window opens and again before the engine is freed; both go into
  the run's ``facts`` (``kv_at_open``, ``kv``), and the per-layer
  readers of the window, full-attention and expert metrics take a
  counter's growth between the two, so that the pre-roll is in none of
  them.  The window's opening is known from the stream itself: a
  request is due ``due_s`` after the stream began, and the window opens
  ``preroll_s`` after that.
"""

from __future__ import annotations

import contextlib
import json
import os
import time

from hvdbench.drivers import serve_open
from hvdbench.drivers._serve import ServeHarness
from hvdbench.drivers.serve_open_state import StateHarness


@contextlib.contextmanager
def _environment(**options):
    """The process's environment with ``options`` set, and as it was
    afterwards (a rehearsal shares its process with other tests)."""
    before = {name: os.environ.get(name) for name in options}
    os.environ.update({k: str(v) for k, v in options.items()})
    try:
        yield
    finally:
        for name, value in before.items():
            if value is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = value


class MixedHarness(ServeHarness):
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
        kinds = self.engine.kv_stats()
        if "kv_window_blocks_total" not in kinds:
            raise RuntimeError(
                f"traffic kind serve-open-mixed drives a cache of full and "
                f"window layers; the engine built for "
                f"{ctx.config['name']!r} holds no ring")
        self._t_open = None       # on the loop's clock, from the stream
        self._kv_at_open = None

    warm = StateHarness.warm

    def submit(self, spec, due):
        if self._t_open is None:
            self._t_open = (due - spec.due_s
                            + float(self.ctx.traffic["preroll_s"]))
        return super().submit(spec, due)

    def step(self):
        if (self._kv_at_open is None and self._t_open is not None
                and time.monotonic() >= self._t_open):
            self._kv_at_open = dict(self.engine.kv_stats())
        return super().step()

    def close_and_check(self):
        kv, at_open = dict(self.engine.kv_stats()), self._kv_at_open or {}
        self.extra_facts = {"kv": kv, "kv_at_open": at_open}
        # On an earlier line: the steps the counters' growth is over, and
        # what a step of the window carried (rows that hold a request,
        # held experts touched and pairs held over the expert layers).
        steps = (kv.get("paged_decode_steps", 0)
                 - at_open.get("paged_decode_steps", 0))
        print(json.dumps({"kv_counters": dict(
            {"decode_steps_at_open": at_open.get("paged_decode_steps"),
             "decode_steps_at_close": kv.get("paged_decode_steps")},
            **{f"{key}_a_step": (kv[key] - at_open.get(key, 0)) / steps
               for key in ("paged_live_rows", "experts_touched",
                           "expert_pairs_held")
               if steps > 0 and key in kv})}),
            flush=True)
        return super().close_and_check()


def run(ctx) -> dict:
    return serve_open.run(ctx, harness=MixedHarness)
