"""Multi-process test tier: real ``jax.distributed`` worlds on loopback.

Reference CI pattern (SURVEY.md §4): the same test bodies that run
single-process also run under ``horovodrun -np 2`` — collective
correctness must hold when each rank is a separate controller process
whose only shared state is the wire.  Here every test spawns N fresh
processes via ``runner.run`` (the gloo-run analogue), each owning one
CPU device; rank == process == slot.

These cover the genuinely multi-controller code paths the in-process
8-virtual-device suite cannot: ragged allgather's deferred second
round, alltoall split negotiation, process-set collectives observed
from *non-member* controllers, and host-binding result-row addressing
(ADVICE r1: subset sets read the wrong head slot).
"""

import os
import sys
import textwrap

import pytest

from horovod_tpu.runner import run

PROLOGUE = """\
import os, sys
# The parent pytest process exports XLA_FLAGS with 8 virtual devices
# (tests/conftest.py); workers must NOT inherit it — these tests want
# one device per controller process so rank == process == slot.
os.environ['XLA_FLAGS'] = ''
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import horovod_tpu as hvd
hvd.init()
rank = hvd.cross_rank()
nproc = hvd.cross_size()
"""


def _env():
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return {"PYTHONPATH": repo_root + os.pathsep
            + os.environ.get("PYTHONPATH", "")}


@pytest.fixture
def world(tmp_path):
    """Run ``body`` (worker-side python, after the standard prologue) on
    ``nproc`` fresh controller processes; fail the test on nonzero rc.
    With ``expect_failure=True`` the assertion is skipped and
    ``(rc, seconds)`` is returned for the caller to judge (fail-fast
    error-contract tests)."""

    def _run(nproc: int, body: str, timeout: float = 300.0,
             expect_failure: bool = False):
        import time

        script = tmp_path / "worker.py"
        script.write_text(PROLOGUE + textwrap.dedent(body) + "\n")
        t0 = time.monotonic()
        rc = run(nproc, [sys.executable, str(script)],
                 start_timeout=timeout, env=_env())
        dt = time.monotonic() - t0
        if expect_failure:
            return rc, dt
        assert rc == 0, f"worker world exited rc={rc}"
        return rc, dt

    return _run
