"""Elastic recovery from real process death (reference:
test/integration/test_elastic_torch.py — SIGKILL a worker mid-step,
survivors re-rendezvous, training resumes with correct state; SURVEY.md
§4, mount empty, unverified).

The failure model here is process death, not a cooperative exception:
rank 2 SIGKILLs itself mid-epoch.  A ``jax.distributed`` world is fixed
at init, so recovery = the supervisor (``run_elastic``) tears the world
down and restarts it at the discovered size; state continuity rides the
durable checkpoint tier (rank 0 writes at each commit), exactly the
preemption-recovery flow on TPU pods.
"""

import json
import os
import stat
import sys
import textwrap

import pytest

from horovod_tpu.runner import run_elastic

pytestmark = pytest.mark.slow

WORKER = """\
import os, sys, json
os.environ['XLA_FLAGS'] = ''
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import signal
import horovod_tpu as hvd

hvd.init()
rank = hvd.cross_rank()
world = hvd.cross_size()
workdir = os.path.dirname(os.path.abspath(__file__))
ckpt = os.path.join(workdir, 'ckpt.json')
marker = os.path.join(workdir, 'marker')

# Resume from the last durable commit (process death wiped memory).
state = {'step': 0, 'accum': 0.0}
if os.path.exists(ckpt):
    state = json.load(open(ckpt))

while state['step'] < 6:
    s = state['step']
    if world == 3 and s == 3 and rank == 2:
        # Simulate hardware failure: this process dies WITHOUT cleanup.
        open(marker, 'w').write('dead')
        os.kill(os.getpid(), signal.SIGKILL)
    x = np.full((1, 2), float(s), np.float32)
    out = float(np.asarray(hvd.allreduce(x, op=hvd.Sum)).ravel()[0])
    state['accum'] += out
    state['step'] += 1
    # Durable commit: rank 0 persists, everyone lines up behind it.
    if rank == 0:
        tmp = ckpt + '.tmp'
        json.dump(state, open(tmp, 'w'))
        os.replace(tmp, ckpt)
    hvd.barrier()

print(f'rank {rank} done: {state}')
"""


class TestElasticKill:
    def test_sigkill_worker_world_restarts_and_resumes(self, tmp_path):
        worker = tmp_path / "worker.py"
        worker.write_text(WORKER)
        discovery = tmp_path / "discover.sh"
        discovery.write_text(textwrap.dedent(f"""\
            #!/bin/sh
            if [ -f {tmp_path}/marker ]; then
              echo "localhost:2"
            else
              echo "localhost:3"
            fi
        """))
        discovery.chmod(discovery.stat().st_mode | stat.S_IEXEC)

        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env = {"PYTHONPATH": repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        rc = run_elastic([sys.executable, str(worker)],
                         min_np=2, discovery_script=str(discovery),
                         env=env, start_timeout=120.0, reset_limit=5)
        assert rc == 0, f"elastic world failed rc={rc}"

        state = json.load(open(tmp_path / "ckpt.json"))
        assert state["step"] == 6, state
        # Steps 0-2 ran in the 3-process world (contribution 3*s per
        # step), the SIGKILL hit at step 3, and steps 3-5 resumed from
        # the durable commit in the 2-process world (2*s per step).
        want = 3 * (0 + 1 + 2) + 2 * (3 + 4 + 5)
        assert abs(state["accum"] - want) < 1e-6, (state, want)
