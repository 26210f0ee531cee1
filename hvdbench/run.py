"""One command, one cell, one run:

    python3 hvdbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, model family, reference, traffic kind and
per-layer readers are files found by name (hvdbench/README.md).  The
last line of standard output is the one JSON object the contract fixes,
ending in ``compared``: each number that decided ``correct`` beside its
limit, which are also the last lines of standard error; everything else
(the set-up split, sample counts, step times) goes on earlier lines.  Without the TPU chips the
cell asks for, the run raises and prints no result.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    family: object
    reference: object
    setup_split: dict
    control_precisions: tuple = ()   # tools/serve_control.py only


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def load_cell(name: str):
    """The cell's entries and files, found by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "hvdbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def metric_names(bench: dict, cell_name: str, group: str):
    """Names of the group's metrics that this cell reports."""
    return [m["name"] for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool,
             rehearsal: bool = False, t_start: float = None,
             control_precisions: tuple = ()) -> dict:
    """Run one cell and return the result line as a dict.  ``rehearsal``
    is for the tests' CPU runs: the result then says so and
    :func:`refuse_rehearsal` will not let it out as a measurement."""
    from hvdbench import device, layers
    from hvdbench.reduce import xplane

    t_start = _T_START if t_start is None else t_start
    import jax  # noqa: F401

    # A rehearsal on the CPU keeps no cache: XLA:CPU warns on every
    # program it loads back.
    cache = None if rehearsal else device.place_compile_cache()
    kind = traffic["kind"].replace("-", "_")
    ctx = Context(
        cell=cell, config=config, traffic=traffic, seed=int(seed),
        seconds=float(seconds), trace=bool(trace), rehearsal=rehearsal,
        family=importlib.import_module(
            f"hvdbench.models.{config['family']}"),
        reference=importlib.import_module(
            f"hvdbench.reference.{config['reference']}"),
        setup_split={}, control_precisions=tuple(control_precisions))
    driver = importlib.import_module(f"hvdbench.drivers.{kind}")
    result = driver.run(ctx)
    devices = result["devices"]
    setup_s = result["t_window_open"] - t_start
    say(setup_split={k: round(v, 3) for k, v in ctx.setup_split.items()},
        setup_s=setup_s, compile_cache=cache)
    for entry in result["checks"]:
        say(**entry)
    facts = result["facts"]
    say(facts={k: v for k, v in facts.items()
               if not isinstance(v, (list, dict)) or len(v) <= 12})

    memory = result["memory"]
    record = device.device_record(devices, memory)
    line = {"correct": all(e["ok"] for e in result["checks"]),
            "attempted": result["attempted"], "failed": result["failed"]}
    if trace:
        rows = busy = None
        if result["trace_path"]:
            rows = xplane.load_events(result["trace_path"], result["spans"])
        if rehearsal and rows is not None and not xplane.device_planes(rows):
            rows = None     # a CPU trace holds no device plane
        if rows is not None:
            busy = xplane.busy(rows)
            record["busy_s"] = busy["busy_s"]
            record["window_s"] = busy["window_s"]
            line["breakdown"] = {
                "device_ops": xplane.top_ops(rows),
                "idle_gaps": xplane.idle_gaps(rows, result["spans"],
                                              busy["window_ns"])}
        view = layers.RunView(cell=cell, config=config, traffic=traffic,
                              facts=facts, memory=memory,
                              device_kind=record["kind"], rows=rows,
                              busy=busy)
        wanted = set(metric_names(bench, cell["name"], "per_layer"))
        units = {m["name"]: m["unit"] for m in bench["per_layer"]}
        values = layers.read_all(wanted, view)
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
        wanted = set(metric_names(bench, cell["name"], "end_to_end"))
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        values = {k: v for k, v in values.items() if k in wanted}
    line["metrics"] = {k: {"value": v, "unit": units[k]}
                       for k, v in sorted(values.items())}
    line["device"] = record
    if rehearsal:
        line["rehearsal"] = True
    # Last: each number compared, beside its limit.
    line["compared"] = {e["check"]: {"value": e["value"],
                                     "limit": e["limit"]}
                        for e in result["checks"]}
    return line


def refuse_rehearsal(line: dict) -> None:
    """A result is a measurement only if it came from TPU chips."""
    if line.get("rehearsal") or line["device"]["platform"] != "tpu":
        raise RuntimeError(
            f"a run on {line['device']['platform']!r} is a rehearsal, "
            f"not a measurement; no result is printed")


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload)
    line = run_cell(bench, cell, config, traffic, seed=args.seed,
                    seconds=args.seconds, trace=bool(args.trace))
    refuse_rehearsal(line)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
