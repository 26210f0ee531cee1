"""Elastic launcher supervision tests.

Reference pattern: ``test/integration/test_elastic_*`` (SURVEY.md §4) —
fake discovery scripts add/remove hosts mid-run; assert the job
survives restarts and honors reset limits.  Here the worlds are local
processes (same as the reference's single-machine elastic CI).
"""

import os
import sys
import textwrap
import threading

import pytest

from horovod_tpu.elastic.driver import FixedDiscovery, HostDiscovery
from horovod_tpu.runner import run_elastic


class MutableDiscovery(HostDiscovery):
    """Discovery whose answer the test mutates mid-run."""

    def __init__(self, slots: int):
        self._slots = slots
        self._lock = threading.Lock()

    def set_slots(self, n: int) -> None:
        with self._lock:
            self._slots = n

    def find_available_hosts_and_slots(self):
        with self._lock:
            return {"localhost": self._slots} if self._slots else {}


def _worker_script(tmp_path, body: str) -> str:
    path = tmp_path / "worker.py"
    path.write_text("import os, sys\n"
                    + textwrap.dedent(body) + "\n")
    return str(path)


def _env():
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return {"PYTHONPATH": repo_root + os.pathsep
            + os.environ.get("PYTHONPATH", "")}


@pytest.mark.slow
class TestRunElastic:
    def test_completes_on_stable_membership(self, tmp_path):
        script = _worker_script(
            tmp_path,
            "print('worker', os.environ['HVD_TPU_PROCESS_ID'], 'of',"
            " os.environ['HVD_TPU_NUM_PROCESSES'])")
        rc = run_elastic([sys.executable, script],
                         min_np=1, max_np=2,
                         discovery=FixedDiscovery({"localhost": 2}),
                         env=_env(), poll_interval_s=0.2)
        assert rc == 0

    def test_world_sized_to_discovery(self, tmp_path):
        out = tmp_path / "np.txt"
        script = _worker_script(
            tmp_path,
            f"open({str(out)!r}, 'a').write("
            f"os.environ['HVD_TPU_NUM_PROCESSES'] + '\\n')")
        rc = run_elastic([sys.executable, script],
                         min_np=1, max_np=8,
                         discovery=FixedDiscovery({"localhost": 3}),
                         env=_env(), poll_interval_s=0.2)
        assert rc == 0
        assert out.read_text().splitlines() == ["3", "3", "3"]

    def test_restart_on_failure_until_reset_limit(self, tmp_path):
        script = _worker_script(tmp_path, "sys.exit(7)")
        rc = run_elastic([sys.executable, script],
                         min_np=1,
                         discovery=FixedDiscovery({"localhost": 1}),
                         env=_env(), poll_interval_s=0.1, reset_limit=2)
        assert rc == 1

    def test_restart_on_membership_change(self, tmp_path):
        # Workers sleep forever; shrinking discovery must trigger a
        # restart, and the restarted world (1 proc) exits 0 via marker.
        marker = tmp_path / "second_round"
        script = _worker_script(tmp_path, textwrap.dedent(f"""
            import time
            if os.environ['HVD_TPU_NUM_PROCESSES'] == '1':
                open({str(marker)!r}, 'w').write('ok')
                sys.exit(0)
            time.sleep(120)
        """).strip())
        disc = MutableDiscovery(2)

        def shrink_soon():
            import time
            time.sleep(2.0)
            disc.set_slots(1)

        threading.Thread(target=shrink_soon, daemon=True).start()
        rc = run_elastic([sys.executable, script], min_np=1,
                         discovery=disc, env=_env(), poll_interval_s=0.2)
        assert rc == 0
        assert marker.exists()
