"""Find the highest rate an open-loop cell sustains with no growing
backlog: the cell's own traffic at each of a few rates, one after
another in one process.  Done once, on the chip; the traffic file then
states 0.8 of that rate as a number.

    python3 hvdbench/tools/sweep_rate.py --workload <name> --rates 0.9,1.0,1.1 --seconds 40

A rate holds (the rule the traffic files' ``rate_why`` state) when at
the window's close nothing or one request waits and a slot is free,
the first token's median wait is level from the window's first half to
its second (at most 1.3 times), and no first token waited a second.
The knee is the highest rate that holds with every lower rate tried.
"""

import argparse
import contextlib
import io
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def why_not(backlog: dict, slots: int) -> list:
    """What of the rule a rate's ``backlog`` line breaks; empty when
    the rate holds."""
    first, second = backlog["ttft_p50_ms_by_half"]
    out = []
    if backlog["waiting_at_close"] > 1:
        out.append(f"{backlog['waiting_at_close']} wait at the close")
    if backlog["in_flight_at_close"] - backlog["waiting_at_close"] >= slots:
        out.append("no slot free at the close")
    if first is None or second is None or second > 1.3 * first:
        out.append(f"the wait grows {first} -> {second} ms")
    if max(x or 0.0 for x in backlog["ttft_max_ms_by_half"]) >= 1000.0:
        out.append("a first token waited a second")
    return out


def main() -> None:
    from hvdbench import run
    from hvdbench.tools import spread

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--seed", type=int, default=7)
    args = parser.parse_args()
    bench, cell, config, traffic = run.load_cell(args.workload)
    slots = int(config["run"]["engine"]["max_slots"])
    knee, broke = None, False
    for rate in sorted(float(x) for x in args.rates.split(",")):
        said = io.StringIO()
        with contextlib.redirect_stdout(said):
            line = run.run_cell(bench, cell, config,
                                dict(traffic, rate_per_s=rate),
                                seed=args.seed, seconds=args.seconds,
                                trace=False, t_start=time.monotonic())
        run.refuse_rehearsal(line)
        earlier = spread.earlier_lines(said.getvalue())
        backlog, facts = earlier["backlog"], earlier["facts"]
        broken = why_not(backlog, slots)
        broke = broke or bool(broken)
        if not broke:       # no rate above one that broke counts
            knee = rate
        print(json.dumps({
            "rate_per_s": rate, "holds": not broken, "why_not": broken,
            "backlog": backlog, "attempted": line["attempted"],
            "requests_finished": facts["requests_finished"],
            "slot_occupancy": facts["slot_occupancy"],
            "correct": line["correct"], "failed": line["failed"],
            "host_pauses": earlier.get("host_pauses"),
            "metrics": {k: m["value"] for k, m in line["metrics"].items()}}),
            flush=True)
    print(json.dumps({"knee": knee,
                      "four_fifths": round(0.8 * knee, 3) if knee else None}),
          flush=True)


if __name__ == "__main__":
    main()
