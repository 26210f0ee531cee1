"""Checkpoint-path benchmark: save stall, write wall, N→N′ restore.

The durable-state twin of ``allreduce_bench.py`` (ISSUE 9): measures
what the async sharded checkpointer (``horovod_tpu/ckpt/``) actually
buys over the synchronous path, on any backend (the path under test is
host memory + filesystem — a CPU run is a real datapoint, not a proxy):

* **save stall** — wall time ``save()`` bills the caller: the full
  write for the sync path, one device→host snapshot for the async path
  (the acceptance ratio ``stall_time_frac`` = async stall / sync wall);
* **async write wall** — what the background writer pays per step;
* **restore latency + bytes/rank at N→N′** for N′ ∈ {N/2, N, 2N} —
  per-rank sharded restores against the manifest's re-derived
  ownership, proving a resize moves only the bytes each new rank owns.

JSON-lines contract: one row per restore configuration, ONE trailing
summary line; ``--out`` writes a ``{"summary", "rows", "metrics"}``
artifact (bench_regress-compatible: the summary is diffed, rows and the
telemetry block are skipped).

Usage::

    python benchmarks/checkpoint_bench.py                 # 32 MiB, CPU-safe
    python benchmarks/checkpoint_bench.py --mb 256 --world 8
    python benchmarks/checkpoint_bench.py --out CKPT_r01.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

METRIC = "ckpt_async_save_stall_ms"


def _build_tree(total_mb: float, leaves: int, seed: int = 0):
    """A params-shaped pytree of ``leaves`` float32 arrays totaling
    ``total_mb`` — sized like the state a real save moves, shaped like
    one (unequal leaves exercise the byte-balanced ZeRO assignment)."""
    import numpy as np

    total = int(total_mb * (1 << 20)) // 4
    # Geometric-ish split: a few big embedding-like leaves, many small.
    weights = np.linspace(1.0, 3.0, leaves)
    weights /= weights.sum()
    rng = np.random.RandomState(seed)
    tree = {}
    for i, w in enumerate(weights):
        n = max(16, int(total * w))
        tree[f"layer_{i:03d}"] = rng.standard_normal(n).astype(np.float32)
    return tree


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mb", type=float, default=32.0,
                    help="total checkpoint payload in MiB (default 32)")
    ap.add_argument("--leaves", type=int, default=24,
                    help="pytree leaf count (default 24)")
    ap.add_argument("--world", type=int, default=4,
                    help="N: simulated save-side world size (zero "
                         "scheme; default 4)")
    ap.add_argument("--iters", type=int, default=5,
                    help="timed save iterations per mode (default 5)")
    ap.add_argument("--dir", default=None,
                    help="scratch directory (default: a fresh tempdir, "
                         "removed afterwards)")
    ap.add_argument("--out", default=None,
                    help="write the full JSON artifact here")
    args = ap.parse_args(argv)
    if args.mb <= 0 or args.leaves < 1 or args.world < 1 \
            or args.iters < 1:
        ap.error("--mb, --leaves, --world and --iters must be positive")

    import horovod_tpu as hvd

    hvd.init()

    import numpy as np

    from horovod_tpu.ckpt import AsyncCheckpointer
    from horovod_tpu.obs import export as obs_export

    scratch = args.dir or tempfile.mkdtemp(prefix="ckpt_bench_")
    made_scratch = args.dir is None
    tree = _build_tree(args.mb, args.leaves)
    nbytes = sum(a.nbytes for a in tree.values())
    rows = []
    try:
        # --- sync saves: the stall IS the write -------------------------
        sync_ms = []
        with AsyncCheckpointer(os.path.join(scratch, "sync"),
                               async_save=False, world=args.world,
                               scheme="zero", journal=False,
                               max_to_keep=2) as ck:
            for i in range(args.iters):
                t0 = time.perf_counter()
                ck.save(i + 1, tree)
                sync_ms.append((time.perf_counter() - t0) * 1e3)

        # --- async saves: stall = snapshot; write happens behind --------
        stall_ms, write_ms = [], []
        with AsyncCheckpointer(os.path.join(scratch, "async"),
                               async_save=True, world=args.world,
                               scheme="zero", journal=False,
                               max_to_keep=2) as ck:
            for i in range(args.iters):
                t0 = time.perf_counter()
                ck.save(i + 1, tree)
                stall_ms.append((time.perf_counter() - t0) * 1e3)
                t1 = time.perf_counter()
                ck.wait_until_finished()   # isolate the write wall
                write_ms.append((time.perf_counter() - t1) * 1e3)

        # --- restore latency + bytes/rank at N → N′ ---------------------
        store_dir = os.path.join(scratch, "restore")
        with AsyncCheckpointer(store_dir, async_save=False,
                               world=args.world, scheme="zero",
                               journal=False) as ck:
            ck.save(1, tree)
            worlds = sorted({max(1, args.world // 2), args.world,
                             args.world * 2})
            for new_world in worlds:
                per_rank_ms, per_rank_bytes = [], []
                for rank in range(new_world):
                    t0 = time.perf_counter()
                    plan, payload = ck.restore_shard(rank=rank,
                                                     world=new_world)
                    per_rank_ms.append(
                        (time.perf_counter() - t0) * 1e3)
                    per_rank_bytes.append(plan.nbytes)
                    got = sum(np.asarray(v).nbytes
                              for v in payload.values())
                    assert got == plan.nbytes, "plan/bytes drift"
                assert sum(per_rank_bytes) == nbytes, \
                    "resharded restore must move each byte exactly once"
                row = {
                    "metric": f"ckpt_restore_ms_w{new_world}",
                    "unit": "ms",
                    "value": round(_median(per_rank_ms), 3),
                    "world_from": args.world,
                    "world_to": new_world,
                    "bytes_per_rank_max": int(max(per_rank_bytes)),
                    "bytes_per_rank_mean": int(np.mean(per_rank_bytes)),
                    "bytes_total": int(nbytes),
                }
                rows.append(row)
                print(json.dumps(row), flush=True)

        sync_save = _median(sync_ms)
        stall = _median(stall_ms)
        summary = {
            "metric": METRIC,
            "unit": "ms",
            "value": round(stall, 3),
            "sync_save_ms": round(sync_save, 3),
            "async_write_ms": round(_median(write_ms), 3),
            # The acceptance ratio (lower is better — "time" keyed so
            # bench_regress infers the direction).
            "stall_time_frac": round(stall / sync_save, 4)
            if sync_save > 0 else None,
            "payload_mb": round(nbytes / (1 << 20), 2),
            "n_leaves": args.leaves,
            "world": args.world,
            "iters": args.iters,
        }
        print(json.dumps(summary), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({
                    "summary": summary,
                    "rows": rows,
                    # Diagnostic telemetry (bench_regress skips it).
                    "metrics": obs_export.json_snapshot()["metrics"],
                }, f, indent=1)
        return 0
    finally:
        if made_scratch:
            shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
