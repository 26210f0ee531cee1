"""Find the highest rate an open-loop cell sustains with no growing
backlog: the cell's own traffic at each of a few rates, one after
another in one process.  Done once, on the chip; the traffic file then
states 0.8 of that rate as a number.

    python3 hvdbench/tools/sweep_rate.py --workload <name> --rates 0.9,1.0,1.1 --seconds 40
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    from hvdbench import run

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--seed", type=int, default=7)
    args = parser.parse_args()
    bench, cell, config, traffic = run.load_cell(args.workload)
    for rate in (float(x) for x in args.rates.split(",")):
        line = run.run_cell(bench, cell, config,
                            dict(traffic, rate_per_s=rate), seed=args.seed,
                            seconds=args.seconds, trace=False,
                            t_start=time.monotonic())
        run.refuse_rehearsal(line)
        print(json.dumps({"rate_per_s": rate, "attempted": line["attempted"],
                          "metrics": line["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
