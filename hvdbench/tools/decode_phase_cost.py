"""What the decode step's phase stamps cost, on the chip at a cell's own
size: the cell's engine as a user who sets nothing gets it, a few rows
bound, and the public ``engine.step()`` in a loop — blocks of steps
with ``HVD_TPU_TRACE=0``, with tracing on (the default: the cost every
run of the benchmark sees) and with tracing on under a live profiler
session, in turn, the median step of each.  Run on the parent's tree it
gives the parent's step for the same three, so the difference between
two trees is what the stamps, the annotations and the followers cost.
Beside it: what the pieces a traced step adds cost on this machine
(one reading of the span clock, an annotation with no session live,
``_note_phases``), and the phases' medians from the ring.  By hand, on the chip.

    python3 hvdbench/tools/decode_phase_cost.py --workload <name> [--rows 5] [--steps 300]
"""

import argparse
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

MODES = ("trace_off", "trace_on", "trace_on_profiler_live")


def main() -> None:
    import jax

    from horovod_tpu.obs import trace
    from horovod_tpu.serve import InferenceEngine, SamplingParams
    from hvdbench import device, generator, run

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rows", type=int, default=5)
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--block", type=int, default=50)
    parser.add_argument("--seed", type=int, default=4000000131)
    args = parser.parse_args()
    _, cell, cfg, _ = run.load_cell(args.workload)
    devices = device.require_chips(cell["chips"])
    device.place_compile_cache()
    family = importlib.import_module(f"hvdbench.models.{cfg['family']}")
    model = family.build_model(cfg, cfg["run"]["attention"])
    params = family.make_params(cfg, args.seed)
    jax.block_until_ready(params)
    engine = InferenceEngine(model, params, seed=args.seed % 2**31)
    del params
    prompts = generator.warmup_prompts([40 + 7 * i for i in range(args.rows)],
                                       args.seed, cfg["vocab_size"])
    rounds = max(1, args.steps // args.block)
    reach = (max(len(p) for p in prompts) + 20
             + rounds * len(MODES) * (args.block + 1))
    if reach > engine.max_seq_len:
        raise SystemExit(f"the rows would reach position {reach} of "
                         f"{engine.max_seq_len}: fewer --steps")
    for slot, prompt in enumerate(prompts):
        engine.start(slot, prompt, SamplingParams(max_new_tokens=10**6))
    for _ in range(20):
        engine.step()
    out = tempfile.mkdtemp(prefix="decode_phase_cost_")
    times = {m: [] for m in MODES}
    try:
        for _ in range(rounds):
            for mode in MODES:
                trace.configure(enabled=mode != "trace_off")
                live = mode == "trace_on_profiler_live"
                if live:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    options.host_tracer_level = 2
                    jax.profiler.start_trace(out, profiler_options=options)
                try:
                    engine.step()       # the mode's first is not read
                    for _ in range(args.block):
                        t = time.perf_counter()
                        engine.step()
                        times[mode].append(time.perf_counter() - t)
                finally:
                    if live:
                        jax.profiler.stop_trace()
                        shutil.rmtree(out, ignore_errors=True)
    finally:
        trace.configure(enabled=True)
    phases = {}
    spans = [s for s in trace.snapshot()
             if s["name"] == "hvd_tpu_engine_decode"
             and "dispatch_us" in s["args"]]
    for key in ("prepare_us", "dispatch_us", "fence_us"):
        if spans:
            phases[key] = statistics.median(s["args"][key] for s in spans)
    if spans:
        phases["span_us"] = statistics.median(s["dur_us"] for s in spans)
    def least_ns(fn):
        return min(timeit.repeat(fn, number=20000, repeat=5)) / 20000 * 1e9

    # What a traced step adds, piece by piece, on this machine; the
    # parent of PR 40 has neither of the last two.
    pieces = {"monotonic_ns": least_ns(time.monotonic_ns)}
    if hasattr(trace, "annotate"):
        def annotated():
            with trace.annotate("hvd_tpu_decode_fence"):
                pass
        pieces["annotate_no_session"] = least_ns(annotated)
    if hasattr(engine, "_note_phases"):
        took = {"prepare": 150.0, "dispatch": 1250.0, "fence": 10800.0}
        pieces["note_phases"] = least_ns(lambda: engine._note_phases(
            {"active": args.rows, "uploads": 0}, took, 1250.0))
    print(json.dumps({
        "workload": args.workload, "rows": args.rows,
        "device": devices[0].device_kind,
        "step_ms_p50": {m: statistics.median(v) * 1e3
                        for m, v in times.items()},
        "step_ms_p10": {m: statistics.quantiles(v, n=10)[0] * 1e3
                        for m, v in times.items()},
        "steps_each": len(times[MODES[0]]),
        "phases_p50_us": phases, "pieces_ns": pieces,
        "kv_stats": {k: v for k, v in engine.kv_stats().items()
                     if k.startswith(("stalled", "dispatch_ms", "fence_ms",
                                      "runtime_pokes", "decode_steps"))}}),
        flush=True)


if __name__ == "__main__":
    main()
