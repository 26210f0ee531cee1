"""Config sweep for the headline ResNet-50 benchmark.

Runs ``bench.py`` across batch sizes / steps-per-call and reports each
config's images/sec + MFU so the best can be promoted to the bench
defaults with a measured justification (perf wins must be measured and
explained, not guessed).

    python benchmarks/resnet_sweep.py                 # on the TPU chip
    python benchmarks/resnet_sweep.py --preset tiny   # CPU smoke

A chip belongs to one process at a time.  This parent imports no JAX
(only the standard library), so it never holds the chip: each
``bench.py`` child is the one process on it, and they run one after
another.  Keep it that way — a parent that touches JAX would make every
child fail or hang.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import os


def run_config(preset: str, batch: int, spc: int, iters: int) -> dict:
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.dirname(
               os.path.abspath(__file__))), "bench.py"),
           "--preset", preset, "--batch-size", str(batch),
           "--steps-per-call", str(spc), "--iters", str(iters)]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=1200)
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            row = json.loads(line)
            row.update({"batch": batch, "steps_per_call": spc})
            return row
        except json.JSONDecodeError:
            continue
    return {"batch": batch, "steps_per_call": spc, "error":
            (out.stderr or out.stdout)[-500:]}


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", choices=["full", "tiny"], default="full")
    parser.add_argument("--batches", default=None,
                        help="comma list (default: 128,256,512 full; "
                             "32,64 tiny)")
    parser.add_argument("--steps-per-call", default="10,20")
    parser.add_argument("--iters", type=int, default=4)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    batches = [int(b) for b in (args.batches or
                                ("128,256,512" if args.preset == "full"
                                 else "32,64")).split(",")]
    spcs = [int(s) for s in args.steps_per_call.split(",")]

    rows = []
    for batch in batches:
        for spc in spcs:
            row = run_config(args.preset, batch, spc, args.iters)
            print(json.dumps(row), flush=True)
            rows.append(row)
    ok = [r for r in rows if "value" in r]
    if ok:
        best = max(ok, key=lambda r: r["value"])
        print(json.dumps({"best": best}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
