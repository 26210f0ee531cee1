"""The serving cells' control, on the chip at the cell's own load: a
short window of the cell's own traffic, then the reference once over
each sampled request in float32 and again in each lower precision; at
every served position the gap of the token that the lower precision
puts first is read beside the program's.  Several seeds in one process,
so that set-up is paid once.

    python3 hvdbench/tools/serve_control.py --workload <name> --seeds 1,2,3 --seconds 15
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
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    parser.add_argument("--precisions", default="bf16,fp8")
    args = parser.parse_args()
    bench, cell, config, traffic = run.load_cell(args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        line = run.run_cell(bench, cell, config, traffic, seed=seed,
                            seconds=args.seconds, trace=False,
                            t_start=time.monotonic(),
                            control_precisions=args.precisions.split(","))
        run.refuse_rehearsal(line)
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "metrics": line["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
