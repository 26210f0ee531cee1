"""Remote multi-host launch through the REAL driver/task RPC protocol
(reference: gloo_run's ssh + task_fn flow, SURVEY.md §2.5/§3.4 step 3 —
mount empty, unverified).  Two task agents run as separate OS processes
on loopback pretending to be two hosts; everything else is the genuine
path: HMAC-keyed registration, pairwise mesh probe, coordinator-port
reservation, per-slot worker spawn with the env contract, exit-code
supervision, agent shutdown.  Only ssh itself is replaced (local_exec),
matching the repo's shim-over-real-processes pattern."""

import os
import sys
import textwrap

import pytest

from horovod_tpu.runner.remote import local_exec, remote_run

pytestmark = pytest.mark.slow

WORKER = """\
import os, sys
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


def _write_worker(tmp_path, body):
    script = tmp_path / "worker.py"
    script.write_text(WORKER + textwrap.dedent(body) + "\n")
    return script


class TestRemoteLaunch:
    def test_two_hosts_two_slots_each_form_one_world(self, tmp_path):
        """2 agents x 2 slots -> one 4-rank jax.distributed world; the
        allreduce proves the world is real, the marker files prove the
        rank layout (host 0 owns ranks 0-1, host 1 owns 2-3)."""
        script = _write_worker(tmp_path, f"""
        assert nproc == 4, nproc
        out = np.asarray(hvd.allreduce(
            np.full((1, 2), float(rank + 1), np.float32), op=hvd.Sum))
        assert np.allclose(out, 10.0), out  # 1+2+3+4
        open(os.path.join({str(tmp_path)!r},
                          f'rank_{{rank}}.ok'), 'w').write(
            os.environ['HVD_TPU_COORDINATOR_ADDR'])
        """)
        rc = remote_run(
            [("fake-host-a", 2), ("fake-host-b", 2)],
            [sys.executable, str(script)],
            exec_fn=local_exec, env=_env(), start_timeout=60.0)
        assert rc == 0
        markers = sorted(p.name for p in tmp_path.glob("rank_*.ok"))
        assert markers == [f"rank_{r}.ok" for r in range(4)]
        coords = {(tmp_path / m).read_text() for m in markers}
        assert len(coords) == 1  # every rank agreed on the coordinator

    def test_np_caps_world_across_hosts(self, tmp_path):
        script = _write_worker(tmp_path, """
        assert nproc == 3, nproc
        out = np.asarray(hvd.allreduce(
            np.ones((1, 1), np.float32), op=hvd.Sum))
        assert np.allclose(out, 3.0), out
        """)
        rc = remote_run(
            [("fake-host-a", 2), ("fake-host-b", 2)],
            [sys.executable, str(script)],
            np_=3, exec_fn=local_exec, env=_env(), start_timeout=60.0)
        assert rc == 0

    def test_np_over_total_slots_raises(self, tmp_path):
        with pytest.raises(ValueError, match="exceeds total slots"):
            remote_run([("a", 1), ("b", 1)], ["x"], np_=3,
                       exec_fn=local_exec)

    def test_failing_rank_kills_job_and_reports_rc(self, tmp_path):
        script = _write_worker(tmp_path, """
        if rank == 2:
            sys.exit(7)
        import time
        time.sleep(60)  # survivors must be terminated, not waited out
        """)
        rc = remote_run(
            [("fake-host-a", 2), ("fake-host-b", 2)],
            [sys.executable, str(script)],
            exec_fn=local_exec, env=_env(), start_timeout=60.0)
        assert rc == 7

    def test_cli_routes_nonlocal_hosts_through_agents(self, tmp_path,
                                                      monkeypatch):
        """`horovodtpurun -H a:1,b:1` must take the remote path (the
        round-4 CLI erred out here) — patched exec keeps it on
        loopback."""
        import horovod_tpu.runner.launch as launch
        import horovod_tpu.runner.remote as remote

        monkeypatch.setattr(remote, "ssh_exec", local_exec)
        script = _write_worker(tmp_path, f"""
        assert nproc == 2, nproc
        open(os.path.join({str(tmp_path)!r}, f'cli_{{rank}}.ok'),
             'w').close()
        """)
        monkeypatch.setenv("PYTHONPATH", _env()["PYTHONPATH"])
        rc = launch.main(["-H", "fake-a:1,fake-b:1", "--",
                          sys.executable, str(script)])
        assert rc == 0
        assert sorted(p.name for p in tmp_path.glob("cli_*.ok")) == [
            "cli_0.ok", "cli_1.ok"]
