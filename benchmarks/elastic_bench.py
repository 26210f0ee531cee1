"""Elastic join/leave benchmark — BASELINE.json config #6.

Reference vehicle (SURVEY.md §6; mount empty, unverified): "Elastic
Horovod (hvd.elastic) with dynamic TPU-slice join/leave".  The
measurable quantity is COORDINATION latency, not FLOPs: how long from
a membership change (host leaves / host joins, reported by discovery)
until the re-formed world executes its first training step.  The
reference pays discovery polling + rendezvous + state broadcast; here
it is discovery polling + world restart + ``jax.distributed`` re-init
+ durable-state restore — the same user-visible recovery path the
multiproc elastic tests pin for correctness, timed.

Runs real worker processes under ``runner.run_elastic`` on the CPU
mesh (the recovery path has no accelerator component; the chip only
hosts the step compute).  The conductor sequences
3-world → leave → 2-world → join → 3-world on OBSERVED world sizes
(never step schedules: formation/teardown latencies vary by seconds),
and ends the run through a stop file whose check is a COLLECTIVE in
the worker loop.  Prints ONE summary JSON line::

    {"metric": "elastic_leave_join_recovery_seconds", "value": <max>,
     "leave_recovery_s": ..., "join_recovery_s": ..., ...}
"""

from __future__ import annotations

import argparse
import json
import os
import stat
import sys
import tempfile
import textwrap
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKER = """\
import json, os, sys, time
os.environ['XLA_FLAGS'] = ''
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import horovod_tpu as hvd

hvd.init()
rank = hvd.cross_rank()
world = hvd.cross_size()
workdir = os.path.dirname(os.path.abspath(__file__))
state_path = os.path.join(workdir, 'state.json')
state = {'step': 0}
if os.path.exists(state_path):
    state = json.load(open(state_path))

HARD_CAP = int(os.environ.get('ELB_HARD_CAP', '2000'))
STEP_SLEEP = float(os.environ.get('ELB_STEP_SLEEP', '0.25'))
stop_path = os.path.join(workdir, 'stop')
while state['step'] < HARD_CAP:
    # The conductor ends the run via the stop file; the decision is
    # made COLLECTIVE (Max over ranks) so every rank leaves the loop
    # at the same step — a lone early exit would strand peers inside
    # the next collective.
    stop = np.asarray(hvd.allreduce(
        np.full((1, 1), 1.0 if os.path.exists(stop_path) else 0.0,
                np.float32), op=hvd.Max))
    if float(stop.ravel()[0]) > 0:
        break
    x = np.full((1, 8), float(state['step']), np.float32)
    out = np.asarray(hvd.allreduce(x, op=hvd.Sum))
    time.sleep(STEP_SLEEP)   # emulate real step compute: a tiny-op CPU
    state['step'] += 1       # loop would outrun the membership events
    if rank == 0:
        tmp = state_path + '.tmp'
        json.dump(state, open(tmp, 'w'))
        os.replace(tmp, state_path)
        with open(os.path.join(workdir, 'steps.log'), 'a') as f:
            f.write(f"{time.time()} {state['step']} {world}\\n")
    hvd.barrier()
"""


def _write_slots(path: str, value: str) -> None:
    """Atomic replace: the discovery script cats this file every poll
    tick; a truncate+write race would feed it 'localhost:' and crash
    the parse."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(value)
    os.replace(tmp, path)


def _tail_steps(path):
    if not os.path.exists(path):
        return []
    rows = []
    for line in open(path):
        parts = line.split()
        if len(parts) != 3:
            continue  # rank 0 may be mid-write; skip partial lines
        try:
            rows.append((float(parts[0]), int(parts[1]), int(parts[2])))
        except ValueError:
            continue
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--settle-steps", type=int, default=8,
                    help="steps to observe at each world size before "
                         "triggering the next membership event")
    args = ap.parse_args()

    from horovod_tpu.runner import run_elastic

    workdir = tempfile.mkdtemp(prefix="elastic_bench_")
    worker = os.path.join(workdir, "worker.py")
    with open(worker, "w") as f:
        f.write(WORKER)
    slots_path = os.path.join(workdir, "slots")
    with open(slots_path, "w") as f:
        f.write("3")
    discovery = os.path.join(workdir, "discover.sh")
    with open(discovery, "w") as f:
        f.write(textwrap.dedent(f"""\
            #!/bin/sh
            echo "localhost:$(cat {slots_path})"
        """))
    os.chmod(discovery, os.stat(discovery).st_mode | stat.S_IEXEC)

    steps_log = os.path.join(workdir, "steps.log")
    events = {}

    def conductor():
        """Drive the leave/join sequence; never dies on a transient
        read race — a dead conductor would leave the run at world 3
        and void the measurement."""
        while "stopped" not in events:
            try:
                _conduct_once()
            except Exception:
                pass
            time.sleep(0.2)

    def _conduct_once():
        """Phase machine keyed on OBSERVED worlds, not step numbers —
        world formation and teardown latencies vary by seconds, so any
        step-count schedule races the restarts it tries to measure."""
        rows = _tail_steps(steps_log)
        if not rows:
            return
        ts, step, world = rows[-1]
        n3_initial = sum(1 for r in rows if r[2] == 3)
        if n3_initial >= args.settle_steps and "leave_ts" not in events:
            _write_slots(slots_path, "2")
            events["leave_ts"] = time.time()
        if ("leave_ts" in events and "leave_first_step" not in events
                and world == 2 and ts > events["leave_ts"]):
            events["leave_first_step"] = ts
        if "leave_ts" in events and "join_ts" not in events:
            n2 = sum(1 for r in rows
                     if r[2] == 2 and r[0] > events["leave_ts"])
            if n2 >= args.settle_steps:
                _write_slots(slots_path, "3")
                events["join_ts"] = time.time()
        if ("join_ts" in events and "join_first_step" not in events
                and world == 3 and ts > events["join_ts"]):
            events["join_first_step"] = ts
        if "join_first_step" in events and "stopped" not in events:
            n3 = sum(1 for r in rows
                     if r[2] == 3 and r[0] > events["join_ts"])
            if n3 >= args.settle_steps:
                with open(os.path.join(workdir, "stop"), "w") as f:
                    f.write("done")
                events["stopped"] = time.time()

    t = threading.Thread(target=conductor, daemon=True)
    t.start()
    env = {"PYTHONPATH": os.path.dirname(os.path.dirname(
               os.path.abspath(__file__))) + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           "ELB_HARD_CAP": "2000"}
    t0 = time.time()
    rc = run_elastic([sys.executable, worker], min_np=2, max_np=3,
                     discovery_script=discovery, env=env,
                     start_timeout=120.0, poll_interval_s=0.2)
    wall = time.time() - t0
    t.join(timeout=5)

    rows = _tail_steps(steps_log)
    line = {"metric": "elastic_leave_join_recovery_seconds",
            "unit": "seconds", "rc": rc, "steps_run": len(rows),
            "wall_s": round(wall, 1)}
    if rc == 0 and "leave_first_step" in events and "join_first_step" in events:
        leave_s = events["leave_first_step"] - events["leave_ts"]
        join_s = events["join_first_step"] - events["join_ts"]
        line.update(value=round(max(leave_s, join_s), 2),
                    leave_recovery_s=round(leave_s, 2),
                    join_recovery_s=round(join_s, 2))
    else:
        line.update(value=None, error="elastic run did not complete the "
                                      "leave/join cycle")
    if os.environ.get("ELB_DEBUG"):
        line["debug_events"] = {k: v for k, v in events.items()
                                if not k.startswith("_")}
        line["debug_worlds"] = [r[2] for r in rows[::5]]
    print(json.dumps(line))
    sys.stdout.flush()
    sys.exit(0 if rc == 0 and line.get("value") else 3)


if __name__ == "__main__":
    main()
