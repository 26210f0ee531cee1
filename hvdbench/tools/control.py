"""The control that ``correct`` has to fail, on the chip at the cell's
own size: the reference put in the program's place and computed in the
nearest precision below the one the configuration states (``bf16`` is
read too: the program's own precision, which has to pass).

    python3 hvdbench/tools/control.py --workload <name> --seeds 1,2,3

Training cells need no window: the reference follows the first steps in
float32 and in each lower precision, and the gaps are printed as
``check.train_checks`` computes them.  It runs on one chip whatever the
cell's chips: the reference takes the whole global batch in blocks of
rows.  Serving cells read their control after a short window of their
own traffic: ``tools/serve_control.py``."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def train_control(cell, config, traffic, seed, precisions):
    import importlib

    import jax

    from hvdbench import check, generator

    ref = importlib.import_module(f"hvdbench.reference.{config['reference']}")
    chips = cell["chips"]
    rows = int(config["run"]["rows_per_chip"]) * chips
    opt = {k: v for k, v in config["run"]["optimizer"].items()
           if k != "name"}
    batches = [generator.train_batch(traffic, seed, i, rows,
                                     config["vocab_size"])
               for i in range(int(traffic["checked_steps"]))]
    s = ref.sizes(config)
    kw = dict(rows_per_block=int(config["check"]["reference_rows_per_block"]))
    want = ref.train_readings(seed, s, batches, opt, **kw)
    for precision in precisions:
        got = ref.train_readings(seed, s, batches, opt, precision=precision,
                                 **kw)
        got["last_loss"] = got["losses"][-1]
        for entry in check.train_checks(got, want,
                                        config["check"]["limits"]):
            if entry["check"] != "loss_fall":
                print(json.dumps(dict(entry, seed=seed, precision=precision,
                                      workload=cell["name"])), flush=True)
    del want
    jax.clear_caches()


def main() -> None:
    from hvdbench import device, run

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--precisions", default="bf16,fp8")
    args = parser.parse_args()
    bench, cell, config, traffic = run.load_cell(args.workload)
    device.place_compile_cache()
    if traffic["kind"] != "train":
        raise SystemExit("serving cells read their control in "
                         "tools/serve_control.py")
    for seed in (int(x) for x in args.seeds.split(",")):
        train_control(cell, config, traffic, seed,
                      args.precisions.split(","))


if __name__ == "__main__":
    main()
