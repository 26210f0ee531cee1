"""Minimal pyspark API shim that executes ``horovod_tpu.spark.run``'s
REAL coordination logic — barrier stage, ``BarrierTaskContext.allGather``
address exchange, per-task env contract, ``jax.distributed`` world
formation — with local OS processes standing in for Spark executors.

pyspark is not installable in this image; like ``mxnet_shim``, this is a
test fixture implementing just the surface the integration touches:
``SparkSession.builder.getOrCreate()``, ``sparkContext.parallelize(...)
.barrier().mapPartitions(fn).collect()``, and ``BarrierTaskContext``
(``allGather`` backed by a filesystem rendezvous).  The mapped function
is cloudpickled to worker processes, exactly Spark's own transport.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time
import types
from typing import Callable, List


class BarrierTaskContext:
    """Worker-side barrier context (one per task process)."""

    _current: "BarrierTaskContext" = None

    def __init__(self, index: int, size: int, sync_dir: str) -> None:
        self._index = index
        self._size = size
        self._sync_dir = sync_dir
        self._round = 0

    @classmethod
    def get(cls) -> "BarrierTaskContext":
        if cls._current is None:
            raise RuntimeError("not inside a barrier task")
        return cls._current

    def partitionId(self) -> int:
        return self._index

    def allGather(self, message: str = "") -> List[str]:
        """All tasks exchange strings; returns them in partition order
        (filesystem rendezvous: atomic per-task files per round)."""
        self._round += 1
        d = os.path.join(self._sync_dir, f"round{self._round}")
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, f".msg_{self._index}.tmp")
        with open(tmp, "w") as f:
            f.write(message)
        os.replace(tmp, os.path.join(d, f"msg_{self._index}"))
        deadline = time.monotonic() + 120.0
        paths = [os.path.join(d, f"msg_{i}") for i in range(self._size)]
        while not all(os.path.exists(p) for p in paths):
            if time.monotonic() > deadline:
                raise TimeoutError(f"allGather round {self._round}: peers "
                                   f"missing in {d}")
            time.sleep(0.05)
        return [open(p).read() for p in paths]

    def barrier(self) -> None:
        self.allGather("")


class _BarrierRDD:
    def __init__(self, n_parts: int) -> None:
        self._n = n_parts
        self._fn: Callable = None

    def mapPartitions(self, fn: Callable) -> "_BarrierRDD":
        self._fn = fn
        return self

    def collect(self) -> list:
        import cloudpickle

        with tempfile.TemporaryDirectory(prefix="pyspark_shim_") as work:
            with open(os.path.join(work, "fn.pkl"), "wb") as f:
                cloudpickle.dump(self._fn, f)
            procs = []
            for i in range(self._n):
                env = dict(os.environ)
                env.update({
                    "PYSPARK_SHIM_WORKDIR": work,
                    "PYSPARK_SHIM_INDEX": str(i),
                    "PYSPARK_SHIM_SIZE": str(self._n),
                    "PYTHONPATH": os.pathsep.join(
                        [os.path.dirname(os.path.dirname(
                            os.path.abspath(__file__))),    # repo root
                         os.path.dirname(os.path.abspath(__file__)),
                         env.get("PYTHONPATH", "")]),
                })
                procs.append(subprocess.Popen(
                    [sys.executable, "-c",
                     "import pyspark_shim; pyspark_shim._worker_main()"],
                    env=env))
            try:
                rcs = [p.wait(timeout=300) for p in procs]
            finally:
                for p in procs:        # never leak a hung task process
                    if p.poll() is None:
                        p.kill()
            if any(rc != 0 for rc in rcs):
                raise RuntimeError(f"shim barrier stage failed: rcs={rcs}")
            out = []
            for i in range(self._n):
                with open(os.path.join(work, f"out_{i}.pkl"), "rb") as f:
                    import pickle

                    out.extend(pickle.load(f))
            return out


class _RDD(_BarrierRDD):
    def barrier(self) -> "_BarrierRDD":
        return self


class _SparkContext:
    defaultParallelism = 2

    def parallelize(self, seq, n_parts: int) -> _RDD:
        return _RDD(int(n_parts))


class _Session:
    def __init__(self) -> None:
        self.sparkContext = _SparkContext()


class _Builder:
    def getOrCreate(self) -> _Session:
        return _Session()


def _worker_main() -> None:
    """Task-process entry: become one barrier task and run the pickled
    partition function (executor-side of Spark's own flow)."""
    os.environ["XLA_FLAGS"] = ""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    install()   # `from pyspark import BarrierTaskContext` must resolve here
    work = os.environ["PYSPARK_SHIM_WORKDIR"]
    index = int(os.environ["PYSPARK_SHIM_INDEX"])
    size = int(os.environ["PYSPARK_SHIM_SIZE"])
    BarrierTaskContext._current = BarrierTaskContext(
        index, size, os.path.join(work, "sync"))
    import cloudpickle

    with open(os.path.join(work, "fn.pkl"), "rb") as f:
        fn = cloudpickle.load(f)
    results = list(fn(iter([index])))
    import pickle

    with open(os.path.join(work, f"out_{index}.pkl"), "wb") as f:
        pickle.dump(results, f)


def install() -> types.ModuleType:
    """Install the shim as ``pyspark`` in sys.modules."""
    shim_mod = sys.modules[__name__]
    mod = types.ModuleType("pyspark")
    mod.BarrierTaskContext = BarrierTaskContext
    sql = types.ModuleType("pyspark.sql")

    class SparkSession:
        builder = _Builder()

    sql.SparkSession = SparkSession
    mod.sql = sql
    mod.__shim__ = shim_mod
    sys.modules["pyspark"] = mod
    sys.modules["pyspark.sql"] = sql
    return mod


def uninstall() -> None:
    for m in ("pyspark", "pyspark.sql"):
        sys.modules.pop(m, None)
