"""Elastic GROW: a live 2-process world gains two hosts, re-forms at 4,
and training continues from durable state (reference: the host-add half
of elastic — discovery reports new slots, the driver re-rendezvous-es,
workers resume from checkpoint; SURVEY.md §3.5, mount empty,
unverified).  Round-4 verdict item 5: kill/shrink recovery was tested,
growth was not.

One worker script exercises BOTH state tiers the verdict names:

* **durable (orbax)** — a ``jax.distributed`` world is fixed at init,
  so growth = supervisor restart at the new size; the restarted world
  resumes from ``TpuState.load_from`` (every rank enters the restore,
  orbax-coordinated);
* **in-memory commit** — after the grow, an injected
  ``HorovodInternalError`` at world 4 rolls uncommitted poison back to
  the last ``commit()`` via the ``hvd.elastic.run`` wrapper (re-init,
  restore, sync) without any process restart.

The accumulator arithmetic discriminates every path: steps 0-2 ran at
world 2 (contribution 2*s), steps 3-8 at world 4 (4*s), the rolled-back
step-5 poison (+1e6) must vanish, and the replayed step must count
exactly once — total 2*(0+1+2) + 4*(3+...+8) = 138.
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
import os, sys, json, time
os.environ['XLA_FLAGS'] = ''
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.elastic import (HorovodInternalError, TpuState,
                                 run as elastic_run)
from horovod_tpu.checkpoint import Checkpointer

hvd.init()
rank = hvd.cross_rank()
workdir = os.path.dirname(os.path.abspath(__file__))
marker = os.path.join(workdir, 'marker')
TOTAL = 9

state = TpuState(params={'w': jax.numpy.zeros((2,))},
                 step=0, accum=0.0, faulted=False)
ck = Checkpointer(os.path.join(workdir, 'ck'), async_save=False)
if ck.latest_step() is not None:
    state.load_from(ck)
    open(os.path.join(workdir,
                      f'resumed_{rank}_of_{hvd.cross_size()}'),
         'w').write(str(int(state.step)))

@elastic_run
def train(state):
    while int(state.step) < TOTAL:
        s = int(state.step)
        w = hvd.cross_size()
        if w == 2 and s == 3:
            # Ask for growth, then idle: the supervisor tears this
            # world down and restarts at the discovered size 4.
            if hvd.cross_rank() == 0 and not os.path.exists(marker):
                open(marker, 'w').write('grow')
            time.sleep(3600)
        if w == 4 and s == 5 and not state.faulted:
            # In-memory commit tier: committed flag survives, the
            # uncommitted poison must not.
            state.faulted = True
            state.commit()
            state.accum += 1e6
            raise HorovodInternalError('injected at grown size')
        if state.faulted and s == 5:
            # Retry entry: rollback restored the committed accumulator
            # (2*(0+1+2) + 4*(3+4) = 34) on every rank.
            assert abs(float(state.accum) - 34.0) < 1e-6, state.accum
            open(os.path.join(workdir, f'rolledback_{hvd.cross_rank()}'),
                 'w').write(str(float(state.accum)))
        x = np.full((1, 2), float(s), np.float32)
        out = float(np.asarray(hvd.allreduce(x, op=hvd.Sum)).ravel()[0])
        state.accum = float(state.accum) + out
        state.params = jax.tree.map(lambda p: p + 1.0, state.params)
        state.step = s + 1
        state.commit()
        # Durable tier: every rank enters the orbax-coordinated save.
        state.save_to(ck, int(state.step))

train(state)
assert hvd.cross_size() == 4, hvd.cross_size()
assert int(state.step) == TOTAL
assert abs(float(state.accum) - 138.0) < 1e-5, state.accum
assert float(np.asarray(state.params['w'])[0]) == float(TOTAL)
if hvd.cross_rank() == 0:
    json.dump({'accum': float(state.accum), 'step': int(state.step)},
              open(os.path.join(workdir, 'result.json'), 'w'))
print(f'rank {rank} done at world {hvd.cross_size()}')
"""


class TestElasticGrow:
    def test_world_grows_2_to_4_with_durable_and_commit_restore(
            self, tmp_path):
        worker = tmp_path / "worker.py"
        worker.write_text(WORKER)
        discovery = tmp_path / "discover.sh"
        discovery.write_text(textwrap.dedent(f"""\
            #!/bin/sh
            if [ -f {tmp_path}/marker ]; then
              echo "localhost:4"
            else
              echo "localhost:2"
            fi
        """))
        discovery.chmod(discovery.stat().st_mode | stat.S_IEXEC)

        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env = {"PYTHONPATH": repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        rc = run_elastic([sys.executable, str(worker)],
                         min_np=2, max_np=4,
                         discovery_script=str(discovery),
                         env=env, start_timeout=120.0, reset_limit=5)
        assert rc == 0, f"elastic world failed rc={rc}"

        result = json.load(open(tmp_path / "result.json"))
        assert result == {"accum": 138.0, "step": 9}
        # The grown world resumed from the durable tier at step 3 on
        # all four ranks...
        resumed = sorted(p.name for p in tmp_path.glob("resumed_*_of_4"))
        assert resumed == [f"resumed_{r}_of_4" for r in range(4)], resumed
        assert {(tmp_path / m).read_text() for m in resumed} == {"3"}
        # ...and the in-memory rollback fired on all four ranks.
        rolled = sorted(p.name for p in tmp_path.glob("rolledback_*"))
        assert rolled == [f"rolledback_{r}" for r in range(4)], rolled
