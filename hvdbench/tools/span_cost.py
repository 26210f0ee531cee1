"""What the program's tracing costs the host, with the settings a user
who sets nothing gets (span ring on, no profiler session).  By hand, on
the machine whose host is to be judged; never by a check.

    python3 hvdbench/tools/span_cost.py [--steps 3000]

Two readings, both per ``batcher.step()``:

* the spans alone: the exact sequence a busy step records (one
  ``hvd_tpu_serve_step`` with an ``hvd_tpu_engine_prefill`` and an
  ``hvd_tpu_engine_decode`` inside it, and eight token stamps), timed
  in a loop with tracing on and with ``HVD_TPU_TRACE=0``;
* the whole step of a small engine (2 layers, 8 slots, every slot
  busy), tracing on and off in alternating blocks, medians compared.
"""

import argparse
import json
import os
import statistics
import sys
import time

# A request may run for the whole reading (the default cap is 256).
os.environ.setdefault("HVD_TPU_SERVE_MAX_TOKENS", "4000")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def spans_alone(n: int) -> float:
    from horovod_tpu.obs import trace

    stamps = []
    t = time.perf_counter()
    for _ in range(n):
        counts = {}
        if trace.enabled():
            with trace.span("hvd_tpu_serve_step", args=counts):
                with trace.span("hvd_tpu_engine_prefill",
                                args={"slot": 0, "prompt_len": 100}):
                    pass
                with trace.span("hvd_tpu_engine_decode",
                                args={"active": 8}):
                    pass
                for _ in range(8):
                    stamps.append(time.monotonic())
                counts.update(active=8, queued=0, admitted=1, emitted=9)
        del stamps[:]
    return (time.perf_counter() - t) / n


def whole_steps(n: int) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import GPT, GPTConfig
    from horovod_tpu.obs import trace
    from horovod_tpu.serve import (ContinuousBatcher, InferenceEngine,
                                   SamplingParams)

    cfg = GPTConfig(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                    d_ff=512, max_seq_len=4096)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(model, params, max_slots=8,
                             prefill_buckets=(64,), max_seq_len=4096)
    batcher = ContinuousBatcher(engine)
    # Every slot stays busy for the whole reading.
    n = min(n, batcher.max_new_tokens_cap - 32)
    reqs = [batcher.submit([1 + i, 2, 3], SamplingParams(
        max_new_tokens=n + 32), deadline_s=0) for i in range(8)]
    for _ in range(16):                       # admit all, compile all
        batcher.step()
    times = {True: [], False: []}
    block = 50
    for i in range(n // block):
        on = i % 2 == 0
        trace.configure(enabled=on)
        for _ in range(block):
            t = time.perf_counter()
            batcher.step()
            times[on].append(time.perf_counter() - t)
    trace.configure(enabled=True)
    assert all(not r.done.is_set() for r in reqs), "a request ran out"
    on, off = (statistics.median(times[k]) * 1e3 for k in (True, False))
    return {"step_ms_tracing_on": on, "step_ms_tracing_off": off,
            "added_us_a_step": (on - off) * 1e3,
            "steps_each": len(times[True]),
            "platform": jax.devices()[0].platform}


def main() -> None:
    from horovod_tpu.obs import trace

    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=3000)
    args = parser.parse_args()
    out = {}
    for on in (True, False, True, False):
        trace.configure(enabled=on)
        out.setdefault("on" if on else "off", []).append(
            spans_alone(20000) * 1e6)
    trace.configure(enabled=True)
    trace.clear()
    result = {"spans_alone_us_a_step": {k: min(v) for k, v in out.items()}}
    result["added_us_a_step"] = (result["spans_alone_us_a_step"]["on"]
                                 - result["spans_alone_us_a_step"]["off"])
    result["whole_step"] = whole_steps(args.steps)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
