"""``hvd.run(func, ...)`` — the reference's programmatic launcher
(``horovod.run``; SURVEY.md §2.5 CLI row, mount empty, unverified):
a Python function executes across a freshly launched worker world and
per-rank results come back in rank order.  Real controller processes,
real ``jax.distributed`` worlds; the remote case runs the genuine
agent-mesh protocol with the loopback exec shim."""

import os
import sys

import pytest

pytestmark = pytest.mark.slow


def _train_fn(scale, bias=0.0):
    """Module-level so plain pickle works too; workers re-import this
    test module via PYTHONPATH."""
    import os as _os

    _os.environ["XLA_FLAGS"] = ""
    _os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.cross_rank()
    out = np.asarray(hvd.allreduce(
        np.full((1, 2), float(r + 1), np.float32), op=hvd.Sum))
    return {"rank": r, "world": hvd.cross_size(),
            "sum": float(out.ravel()[0]) * scale + bias}


def _env():
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # Module-level fns pickle by reference as multiproc.test_run_func_mp;
    # workers resolve that with tests/ on the path.
    return {"PYTHONPATH": os.pathsep.join(
        [repo_root, os.path.join(repo_root, "tests"),
         os.environ.get("PYTHONPATH", "")])}


class TestRunFunction:
    def test_function_runs_across_world_with_results_in_rank_order(self):
        import horovod_tpu as hvd

        results = hvd.run(_train_fn, args=(10,), kwargs={"bias": 1.0},
                          np=2, env=_env(), start_timeout=120.0)
        assert [r["rank"] for r in results] == [0, 1]
        assert all(r["world"] == 2 for r in results)
        # ranks contribute 1+2 -> 3; *10 + 1
        assert all(abs(r["sum"] - 31.0) < 1e-5 for r in results), results

    def test_closure_travels_by_value(self):
        """cloudpickle carries closures (the reference's contract —
        lambdas/local functions work, not just importable names)."""
        import horovod_tpu as hvd

        factor = 7

        def fn():
            import os as _os

            _os.environ["XLA_FLAGS"] = ""
            _os.environ["JAX_PLATFORMS"] = "cpu"
            import jax

            jax.config.update("jax_platforms", "cpu")
            import horovod_tpu as hvd

            hvd.init()
            return hvd.cross_rank() * factor

        assert hvd.run(fn, np=2, env=_env()) == [0, 7]

    def test_remote_hosts_route_through_agent_mesh(self, monkeypatch):
        import horovod_tpu as hvd
        import horovod_tpu.runner.remote as remote

        monkeypatch.setattr(remote, "ssh_exec", remote.local_exec)
        results = hvd.run(_train_fn, args=(1,), np=2,
                          hosts="fake-a:1,fake-b:1", env=_env(),
                          start_timeout=120.0)
        assert [r["rank"] for r in results] == [0, 1]
        assert all(abs(r["sum"] - 3.0) < 1e-5 for r in results)

    def test_explicit_workdir_kept_default_cleaned(self, tmp_path):
        """workdir= (the shared-filesystem hook for remote hosts) is
        left in place with its artifacts; the default tempdir is
        removed on return."""
        import glob
        import tempfile

        import horovod_tpu as hvd

        wd = tmp_path / "exchange"
        wd.mkdir()
        out = hvd.run(_train_fn, args=(1,), np=2, env=_env(),
                      workdir=str(wd), start_timeout=120.0)
        assert [r["rank"] for r in out] == [0, 1]
        kept = sorted(p.name for p in wd.iterdir())
        assert "payload.pkl" in kept and "result_0.pkl" in kept

        before = set(glob.glob(os.path.join(tempfile.gettempdir(),
                                            "hvd_tpu_run_*")))
        hvd.run(_train_fn, args=(1,), np=2, env=_env(),
                start_timeout=120.0)
        after = set(glob.glob(os.path.join(tempfile.gettempdir(),
                                           "hvd_tpu_run_*")))
        assert after == before  # launcher-created dir was removed

    def test_worker_failure_raises(self):
        import horovod_tpu as hvd

        def boom():
            raise RuntimeError("worker exploded")

        with pytest.raises(RuntimeError, match="rc="):
            hvd.run(boom, np=2, env=_env(), start_timeout=120.0)


class TestCompressedBusbwVehicleMP:
    def test_spmd_wire_sweep_runs_multicontroller(self, world):
        """The --compression busbw vehicle builds its stack with
        make_array_from_callback — this is the witness that the jitted
        global-mesh shard_map really executes across 2 controller
        processes (a host-local jnp.ones here would raise at
        device_put)."""
        world(2, """
        import json, runpy, io, contextlib
        import horovod_tpu
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(horovod_tpu.__file__)))
        sys.argv = ['allreduce_bench.py', '--compression', 'int8',
                    '--max-elems', '4096', '--iters', '2',
                    '--warmup', '1']
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            runpy.run_path(os.path.join(repo, 'benchmarks',
                                        'allreduce_bench.py'),
                           run_name='__main__')
        summary = json.loads(buf.getvalue().strip().splitlines()[-1])
        assert summary['metric'] == 'allreduce_int8_wire_busbw_peak'
        assert summary['n_slots'] == 2 and summary['value'] > 0
        """, timeout=420.0)
