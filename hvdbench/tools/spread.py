"""How closely a cell repeats, read the way a bound is set from it:
the cell's own command once a seed and set, each run a process of its
own (this one never touches JAX, so every child gets the chip), the
same seeds in every set.

    python3 hvdbench/tools/spread.py --workload <name> --seeds 1,2,3,4,5,6 --sets 2

For each end-to-end metric it prints every reading, each set's spread,
their mean and the share of the metric's bound that mean is.  A set's
spread is the strict one: the distance between the highest and the
lowest run as a share of the set's median, with the run farthest from
the median left out (that never widens it).  ``stats.quartile_spread``
is printed beside it and does not decide.  The rule for a serving bound
(PERF.md section 2): the larger cell's mean spread / 0.4, rounded up to
the next 0.005, never under 0.01, and over 0.03 a cause is looked for
before a bound is written.

Each run's whole output is kept under ``--out`` (default
``hvdbench_out/spread/<workload>/``), and a run's row says what an
acceptance reads besides the metrics: ``correct``, ``failed``, the
queue at the close, programs built inside the window and Python's full
collections inside it.
"""

import argparse
import json
import math
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hvdbench import stats  # noqa: E402


def strict_spread(values) -> float:
    """Highest minus lowest as a share of the median, the run farthest
    from the median left out."""
    mid = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - mid))[:-1] or list(values)
    return (max(kept) - min(kept)) / mid


def bound_for(mean_spread: float) -> float:
    """The bound the rule writes for a mean spread."""
    return max(0.01, math.ceil(mean_spread / 0.4 / 0.005 - 1e-9) * 0.005)


def earlier_lines(text: str) -> dict:
    """The JSON objects a run printed before its result, by their one
    key (``facts``, ``host_pauses``, ``backlog``, ...)."""
    out = {}
    for raw in text.splitlines():
        if raw.startswith("{"):
            try:
                obj = json.loads(raw)
            except ValueError:
                continue
            if len(obj) == 1:
                out.update(obj)
    return out


def one_run(bench: dict, workload: str, seed: int, log: str):
    cmd = bench["command"] + [
        "--workload", workload, "--seed", str(seed),
        "--seconds", str(bench["run_seconds"]), "--trace", "0"]
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    with open(log, "w") as f:
        f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr[-8000:])
    if proc.returncode != 0:
        raise SystemExit(f"{' '.join(cmd)} exited {proc.returncode}; "
                         f"see {log}\n{proc.stderr[-2000:]}")
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    return line, earlier_lines(proc.stdout)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seeds = [int(x) for x in args.seeds.split(",")]
    out = args.out or os.path.join(ROOT, "hvdbench_out", "spread",
                                   args.workload)
    os.makedirs(out, exist_ok=True)
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]
              if "workloads" not in m or args.workload in m["workloads"]}
    readings = {name: [[] for _ in range(args.sets)] for name in bounds}
    for k in range(args.sets):
        for seed in seeds:
            line, said = one_run(bench, args.workload, seed, os.path.join(
                out, f"set{k + 1}_seed{seed}.log"))
            facts = said.get("facts", {})
            gc_seen = said.get("host_pauses", {}).get("gc", {})
            print(json.dumps({
                "set": k + 1, "seed": seed, "correct": line["correct"],
                "failed": line["failed"], "attempted": line["attempted"],
                "queue_at_close": facts.get("queue_at_close"),
                "window_compilations": facts.get("window_compilations"),
                "full_collections": gc_seen.get("full_collections"),
                "compared": line.get("compared"),
                "memory_peak_bytes": line["device"]["memory_peak_bytes"],
                "metrics": {n: m["value"]
                            for n, m in line["metrics"].items()}}),
                flush=True)
            for name in bounds:
                readings[name][k].append(line["metrics"][name]["value"])
    for name, sets in readings.items():
        strict = [strict_spread(v) for v in sets]
        mean = sum(strict) / len(strict)
        medians = [statistics.median(v) for v in sets]
        print(json.dumps({
            "metric": name, "workload": args.workload, "seeds": seeds,
            "readings": sets,
            "medians": medians,
            "last_median_over_first": medians[-1] / medians[0] - 1,
            "strict_spread": strict,
            "quartile_spread": [stats.quartile_spread(v) if len(v) > 1
                                else None for v in sets],
            "mean_strict_spread": mean, "bound": bounds[name],
            "share_of_bound": mean / bounds[name],
            "bound_by_the_rule": bound_for(mean)}), flush=True)


if __name__ == "__main__":
    main()
