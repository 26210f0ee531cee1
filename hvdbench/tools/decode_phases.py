"""Where a decode step's host time went over whole untraced windows,
and which steps stalled: a cell's own run with ``--trace 0`` (the
end-to-end metrics, as the driver reads them), then what the program's
span ring and flight ring hold of the window — the lines
``decode_phases_ms``, ``stalled_steps`` and ``step_protocol`` of the
ring's readers, the three longest prefill spans with their stamps, and
every ``slow_decode_step`` and ``slow_prefill`` event of the flight
ring.  A traced run reports the same through its per-layer
metrics; this is for windows without the profiler, where PERF.md
section 7 (d) asks what a stalled step waited in.  One process a seed:
the chip belongs to one process at a time.  By hand, on the chip.

    python3 hvdbench/tools/decode_phases.py --workload <name> --seed <n> [--seconds 45]
"""

import argparse
import json
import os
import sys
import time

_T_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    from hvdbench import layers, run
    from hvdbench.layer_metrics import _decode_phases as phases
    from hvdbench.layer_metrics import (decode_dispatch_ms, stalled_steps,
                                        step_uploads_share)

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=45.0)
    args = parser.parse_args()
    bench, cell, config, traffic = run.load_cell(args.workload)
    line = run.run_cell(bench, cell, config, traffic, seed=args.seed,
                        seconds=args.seconds, trace=False,
                        t_start=_T_START)
    run.refuse_rehearsal(line)
    view = layers.RunView(
        cell=cell, config=config, traffic=traffic,
        facts={"elapsed_s": args.seconds}, memory={},
        device_kind=line["device"]["kind"], rows=None, busy=None)
    wanted = {"decode_dispatch_ms.tpot", "stalled_steps.tpot",
              "step_uploads_share.tpot"}
    ring = {}
    for reader in (decode_dispatch_ms, stalled_steps, step_uploads_share):
        ring.update(reader.read(wanted, view))
    # Where the window's longest prefills spent their time: a pause in
    # the dispatch or the fence is named; one around them only shows.
    longest = [{"span_us": s["dur_us"],
                **{k: s["args"].get(k) for k in (
                    "bucket", "prompt_len", "prefix_hit", "dispatch_us",
                    "fence_us", "stalled")}}
               for s in sorted(phases.window_prefills(view),
                               key=lambda s: -s["dur_us"])[:3]]
    slow = []
    try:
        from horovod_tpu.obs import flight

        slow = [e for e in flight.events()
                if e["kind"] in ("slow_decode_step", "slow_prefill")]
    except Exception as e:      # the parent of PR 40 records none
        print(json.dumps({"flight": f"not read: {e}"}), flush=True)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": line["correct"], "failed": line["failed"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "ring": ring, "longest_prefills": longest,
        "slow_events_of_the_process": slow}),
        flush=True)


if __name__ == "__main__":
    main()
