"""Deterministic fault-injection harness (horovod_tpu/faults.py) and the
shared retry helper (utils/retry.py).

The properties under test are the ones that make chaos testing usable:
spec parsing fails loudly, a seeded plan fires the *identical* failure
sequence across runs, and an unset plan is a true no-op on the hot
path."""

import os
import subprocess
import time

import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import faults
from horovod_tpu.config import Config, FaultClause, parse_fault_spec
from horovod_tpu.elastic import HorovodInternalError
from horovod_tpu.utils.retry import RetryPolicy, jittered, retry_call


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    """Every test starts and ends with no armed plan."""
    faults.clear()
    yield
    faults.clear()


class TestSpecParsing:
    def test_issue_example(self):
        clauses = parse_fault_spec("collective:step=40;discovery:flap=0.2,seed=7")
        assert clauses["collective"] == FaultClause(site="collective", step=40)
        assert clauses["discovery"] == FaultClause(
            site="discovery", p=0.2, seed=7, mode="flap")

    def test_all_keys(self):
        c = parse_fault_spec(
            "rpc:p=0.5,seed=3,times=2,mode=delay,delay_ms=250")["rpc"]
        assert (c.p, c.seed, c.times, c.mode, c.delay_ms) == \
            (0.5, 3, 2, "delay", 250.0)

    @pytest.mark.parametrize("bad", [
        "warp:step=1",                    # unknown site
        "collective:steps=1",             # unknown key
        "collective:step=x",              # unparseable value
        "collective:mode=raise",          # no trigger
        "rpc:step=1,mode=corrupt",        # mode of another site
        "discovery:flap=1.5",             # probability out of range
        "collective:step=1;collective:step=2",  # duplicate clause
        "collective:step",                # not key=value
    ])
    def test_malformed_specs_raise(self, bad):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)

    def test_config_validates_env_spec(self, monkeypatch):
        monkeypatch.setenv("HVD_TPU_FAULT_SPEC", "collective:step=3")
        assert Config.from_env().fault_spec == "collective:step=3"
        monkeypatch.setenv("HVD_TPU_FAULT_SPEC", "nonsense:p=1")
        with pytest.raises(ValueError):
            Config.from_env()

    def test_empty_spec_is_none(self, monkeypatch):
        monkeypatch.setenv("HVD_TPU_FAULT_SPEC", "  ")
        assert Config.from_env().fault_spec is None


class TestDeterminism:
    def _drive_collective(self, spec, n=200):
        fired = []
        with faults.inject(spec):
            for i in range(n):
                try:
                    faults.on_collective(f"op{i}")
                except HorovodInternalError:
                    fired.append(i)
            hist = faults.history()
        return fired, hist

    def test_seeded_probability_reproduces_exactly(self):
        spec = "collective:p=0.1,seed=13,times=1000"
        a_fired, a_hist = self._drive_collective(spec)
        b_fired, b_hist = self._drive_collective(spec)
        assert a_fired, "p=0.1 over 200 events should fire"
        assert a_fired == b_fired
        assert a_hist == b_hist

    def test_different_seeds_differ(self):
        a, _ = self._drive_collective("collective:p=0.1,seed=1,times=1000")
        b, _ = self._drive_collective("collective:p=0.1,seed=2,times=1000")
        assert a != b

    def test_step_fires_exactly_once_at_index(self):
        fired, hist = self._drive_collective("collective:step=7")
        assert fired == [7]
        assert hist == [("collective", 7, "raise:op7")]

    def test_times_caps_firings(self):
        fired, _ = self._drive_collective("collective:p=1.0,times=3,seed=0")
        assert fired == [0, 1, 2]

    def test_env_spec_reproduces_across_processes(self, tmp_path):
        """The acceptance property, end to end: two fresh processes
        running the same program under the same HVD_TPU_FAULT_SPEC
        observe the identical failure sequence."""
        import sys

        script = tmp_path / "probe.py"
        script.write_text(
            "import os\n"
            "os.environ['XLA_FLAGS'] = ''\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "import numpy as np\n"
            "import horovod_tpu as hvd\n"
            "from horovod_tpu import faults\n"
            "from horovod_tpu.elastic import HorovodInternalError\n"
            "hvd.init()\n"
            "x = np.ones((hvd.size(), 3), np.float32)\n"
            "fired = []\n"
            "for i in range(40):\n"
            "    try:\n"
            "        hvd.allreduce(x)\n"
            "    except HorovodInternalError:\n"
            "        fired.append(i)\n"
            "print('FIRED', fired, faults.history())\n"
        )
        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        env["HVD_TPU_FAULT_SPEC"] = "collective:p=0.15,seed=21,times=1000"

        def one_run():
            out = subprocess.run([sys.executable, str(script)], env=env,
                                 capture_output=True, text=True, timeout=120)
            assert out.returncode == 0, out.stderr[-2000:]
            lines = [l for l in out.stdout.splitlines()
                     if l.startswith("FIRED")]
            assert lines, out.stdout
            return lines[0]

        a, b = one_run(), one_run()
        assert a == b
        assert "[]" not in a.split("FIRED")[1][:20]  # it actually fired

    def test_flap_sequence_reproduces(self):
        spec = "discovery:flap=0.5,seed=42"
        hosts = {f"h{i}": 2 for i in range(8)}

        def drive():
            seq = []
            with faults.inject(spec):
                for _ in range(20):
                    seq.append(sorted(faults.on_discovery_hosts(dict(hosts))))
            return seq

        a, b = drive(), drive()
        assert a == b
        assert any(len(s) < 8 for s in a), "flap=0.5 should drop hosts"


class TestNoOpWhenDisabled:
    def test_hooks_are_noops(self):
        assert faults._active is None
        faults.on_collective("x")
        faults.on_fusion()
        faults.on_rpc("y")
        assert faults.on_checkpoint_save(3) is None
        assert faults.on_discovery_hosts({"a": 1}) == {"a": 1}
        assert faults.history() == []
        assert faults.active_spec() is None

    def test_collectives_unaffected(self):
        x = np.ones((hvd.size(), 4), np.float32)
        out = hvd.allreduce(x, op=hvd.Sum)
        assert float(np.asarray(out)[0]) == hvd.size()

    def test_inject_restores_previous_plan(self):
        with faults.inject("collective:step=1000"):
            outer = faults.active_spec()
            with faults.inject("rpc:step=0"):
                assert faults.active_spec() == "rpc:step=0"
            assert faults.active_spec() == outer
        assert faults.active_spec() is None


class TestCollectiveSite:
    def test_allreduce_raises_at_step(self):
        x = np.ones((hvd.size(), 4), np.float32)
        with faults.inject("collective:step=2"):
            hvd.allreduce(x)   # dispatch 0
            hvd.allreduce(x)   # dispatch 1
            with pytest.raises(HorovodInternalError, match="injected"):
                hvd.allreduce(x)  # dispatch 2 -> fires
            # One-shot: the retry goes through.
            out = hvd.allreduce(x, op=hvd.Sum)
            assert faults.history() == [("collective", 2, "raise:allreduce")]
        assert float(np.asarray(out)[0]) == hvd.size()

    def test_elastic_run_recovers_from_injected_fault(self, monkeypatch):
        from horovod_tpu.elastic import ObjectState, run
        from horovod_tpu.elastic import state as state_mod

        sleeps = []
        monkeypatch.setattr(state_mod.time, "sleep",
                            lambda s: sleeps.append(s))
        state = ObjectState(step=0, total=0.0)
        x = np.ones((hvd.size(), 2), np.float32)

        @run
        def train(state):
            while state.step < 4:
                out = hvd.allreduce(x, op=hvd.Sum, name="train_ar")
                state.total += float(np.asarray(out)[0])
                state.step += 1
                state.commit()
            return state.total

        with faults.inject("collective:step=2"):
            total = train(state)
            assert [h[0] for h in faults.history()] == ["collective"]
        # Step 2's dispatch failed, rolled back to the step-2 commit,
        # and the retry completed: exactly 4 contributions.
        assert total == 4.0 * hvd.size()
        assert sleeps and all(s > 0 for s in sleeps)  # backoff happened

    def test_elastic_reinit_preserves_armed_plan(self, monkeypatch):
        """shutdown+init with the SAME env spec (the elastic recovery
        path) must keep the live plan — counters and history span the
        process, or a step fault would re-fire on every reset."""
        import horovod_tpu as hvd
        from horovod_tpu import basics

        monkeypatch.setenv("HVD_TPU_FAULT_SPEC", "collective:step=1000")
        faults.configure("collective:step=1000")
        plan = faults._active
        faults.on_collective("tick")  # advance pre-reset state
        basics.shutdown()
        basics.init()
        try:
            assert faults._active is plan
            assert plan.site("collective").counter == 1
        finally:
            monkeypatch.delenv("HVD_TPU_FAULT_SPEC")
            faults.clear()
            basics.shutdown()
            basics.init()  # restore a pristine session config

    def test_fusion_site_unit(self):
        with faults.inject("fusion:step=0"):
            with pytest.raises(HorovodInternalError, match="fusion"):
                faults.on_fusion("two_phase_apply")


class TestAccumulateSite:
    """ISSUE 4 satellite: the microbatch-loop boundary is a chaos site
    like every other hot path — trace time, one event per boundary."""

    def test_spec_parses(self):
        c = parse_fault_spec("accumulate:step=2")["accumulate"]
        assert c == FaultClause(site="accumulate", step=2)
        with pytest.raises(ValueError, match="unknown mode"):
            parse_fault_spec("accumulate:step=1,mode=drop")

    def test_unit_fires_at_boundary_index(self):
        with faults.inject("accumulate:step=1") as plan:
            faults.on_accumulate(0)   # boundary 0: no fire
            with pytest.raises(HorovodInternalError, match="accumulate"):
                faults.on_accumulate(1)
            assert plan.history[0][0] == "accumulate"

    def test_microbatch_train_step_raises_at_trace(self):
        import optax

        import horovod_tpu as hvd
        from horovod_tpu.optim import make_train_step

        def loss_fn(params, batch):
            x, y = batch
            return ((x @ params["w"] - y) ** 2).mean()

        x = np.random.RandomState(0).randn(64, 4).astype(np.float32)
        y = x.sum(axis=1)
        params = {"w": jnp.zeros((4,), jnp.float32)}
        tx = optax.sgd(0.1)
        step = make_train_step(loss_fn, tx, donate=False, microbatches=4)
        with faults.inject("accumulate:step=1"):
            with pytest.raises(HorovodInternalError, match="accumulate"):
                step(params, tx.init(params), (x, y))
        # Disarmed: the same step builds and runs clean.
        p, _, loss = step(params, tx.init(params), (x, y))
        assert np.isfinite(float(loss))

    def test_spmd_step_threads_the_site(self):
        import optax

        from horovod_tpu.parallel.train import make_spmd_train_step

        def loss_fn(params, batch):
            x, y = batch
            return ((x @ params["w"] - y) ** 2).mean()

        x = np.random.RandomState(0).randn(8, 4).astype(np.float32)
        y = x.sum(axis=1)
        params = {"w": jnp.zeros((4,), jnp.float32)}
        tx = optax.sgd(0.1)
        step = make_spmd_train_step(loss_fn, tx, donate=False,
                                    microbatches=2)
        with faults.inject("accumulate:step=0"):
            with pytest.raises(HorovodInternalError, match="accumulate"):
                step(params, tx.init(params), (x, y))


class TestDiscoverySite:
    def _script_discovery(self, tmp_path, retries=1, backoff_s=0.0):
        from horovod_tpu.elastic.driver import ScriptDiscovery

        script = tmp_path / "discover.sh"
        script.write_text("#!/bin/sh\necho hostA:2\necho hostB:2\n")
        script.chmod(0o755)
        return ScriptDiscovery(str(script), retries=retries,
                               backoff_s=backoff_s)

    def test_timeout_mode_raises_through_single_attempt(self, tmp_path):
        disc = self._script_discovery(tmp_path, retries=1)
        with faults.inject("discovery:step=0,mode=timeout"):
            with pytest.raises(subprocess.SubprocessError):
                disc.find_available_hosts_and_slots()

    def test_retry_helper_absorbs_one_shot_fault(self, tmp_path):
        disc = self._script_discovery(tmp_path, retries=3)
        with faults.inject("discovery:step=0,mode=error"):
            hosts = disc.find_available_hosts_and_slots()
        assert hosts == {"hostA": 2, "hostB": 2}

    def test_flap_drops_hosts_from_script(self, tmp_path):
        disc = self._script_discovery(tmp_path)
        with faults.inject("discovery:flap=1.0,seed=0"):
            assert disc.find_available_hosts_and_slots() == {}

    def test_flap_honors_times_cap(self, tmp_path):
        disc = self._script_discovery(tmp_path)
        with faults.inject("discovery:flap=1.0,seed=0,times=2"):
            assert disc.find_available_hosts_and_slots() == {}
            assert disc.find_available_hosts_and_slots() == {}
            # Budget exhausted: the host set comes back untouched.
            assert disc.find_available_hosts_and_slots() == \
                {"hostA": 2, "hostB": 2}


class TestRpcSite:
    def _service_client(self, retries=3):
        from horovod_tpu.runner.common.network import (
            BasicClient, BasicService, PingRequest)
        from horovod_tpu.utils.retry import RetryPolicy

        key = b"k" * 32
        svc = BasicService("svc", key, host="127.0.0.1")
        client = BasicClient(
            "svc", [("127.0.0.1", svc.port)], key,
            retry_policy=RetryPolicy(attempts=retries, base_delay_s=0.01,
                                     max_delay_s=0.05))
        return svc, client, PingRequest

    def test_drop_is_absorbed_by_request_retry(self):
        svc, client, PingRequest = self._service_client()
        try:
            # The plan arms after the constructor's probe, so event 0 is
            # the request's first attempt: it drops, the retry succeeds.
            with faults.inject("rpc:step=0,mode=drop"):
                resp = client.request(PingRequest())
                assert [h[2].split(":")[0] for h in faults.history()] == \
                    ["drop"]
            assert resp is not None
        finally:
            svc.shutdown()

    def test_drop_exhausts_bounded_retries(self):
        svc, client, PingRequest = self._service_client(retries=2)
        try:
            with faults.inject("rpc:p=1.0,seed=0,times=1000"):
                with pytest.raises(ConnectionError, match="injected"):
                    client.request(PingRequest())
        finally:
            svc.shutdown()

    def test_delay_slows_but_succeeds(self):
        svc, client, PingRequest = self._service_client()
        try:
            with faults.inject("rpc:step=0,mode=delay,delay_ms=200"):
                t0 = time.monotonic()
                client.request(PingRequest())
                assert time.monotonic() - t0 >= 0.2
        finally:
            svc.shutdown()


@pytest.mark.chaos
class TestChaosRecoverySingleController:
    """Seeded end-to-end recovery on the in-process 8-slot mesh — the
    single-controller twin of tests/multiproc/test_chaos_recovery_mp.py
    (same knobs, so scripts/chaos_soak.py can loop either)."""

    def test_injected_fault_rolls_back_and_converges(self, monkeypatch):
        import jax

        from horovod_tpu.elastic import TpuState, run
        from horovod_tpu.elastic import state as state_mod

        monkeypatch.setattr(state_mod.time, "sleep", lambda s: None)
        fault_step = int(os.environ.get("HVD_TPU_CHAOS_STEP", "5"))
        seed = int(os.environ.get("HVD_TPU_CHAOS_SEED", "0"))
        TOTAL = max(8, fault_step + 2)

        state = TpuState(params={"w": jax.numpy.zeros((2,))},
                         step=0, accum=0.0)
        meta = {"tries": 0}

        @run
        def train(state):
            meta["tries"] += 1
            if meta["tries"] == 2:
                expect = sum(hvd.size() * t for t in range(int(state.step)))
                assert abs(float(state.accum) - expect) < 1e-6
            while int(state.step) < TOTAL:
                s = int(state.step)
                x = np.full((hvd.size(), 2), float(s), np.float32)
                out = float(np.asarray(
                    hvd.allreduce(x, op=hvd.Sum)).ravel()[0])
                state.accum = float(state.accum) + out
                state.params = jax.tree.map(lambda p: p + 1.0, state.params)
                state.step = s + 1
                state.commit()
            return state

        with faults.inject(f"collective:step={fault_step},seed={seed}"):
            train(state)
            fired = [h for h in faults.history() if h[0] == "collective"]
        assert len(fired) == 1 and fired[0][1] == fault_step, fired
        assert meta["tries"] == 2, meta
        want = sum(hvd.size() * t for t in range(TOTAL))
        assert abs(float(state.accum) - want) < 1e-6, (state.accum, want)
        assert float(np.asarray(state.params["w"])[0]) == float(TOTAL)


class TestRetryHelper:
    def test_succeeds_after_transient_failures(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "ok"

        slept = []
        out = retry_call(flaky, policy=RetryPolicy(attempts=5,
                                                   base_delay_s=0.1),
                         retry_on=(OSError,), sleep=slept.append)
        assert out == "ok"
        assert calls["n"] == 3
        assert len(slept) == 2
        assert slept[1] > slept[0] * 0.5  # roughly exponential (jittered)

    def test_give_up_on_carves_out_deterministic_failures(self):
        calls = {"n": 0}

        def missing():
            calls["n"] += 1
            raise FileNotFoundError("gone")

        with pytest.raises(FileNotFoundError):
            retry_call(missing, policy=RetryPolicy(attempts=5,
                                                   base_delay_s=0.0),
                       retry_on=(OSError,), give_up_on=(FileNotFoundError,),
                       sleep=lambda s: None)
        assert calls["n"] == 1  # never retried

    def test_non_retryable_propagates_immediately(self):
        calls = {"n": 0}

        def bad():
            calls["n"] += 1
            raise ValueError("logic bug")

        with pytest.raises(ValueError):
            retry_call(bad, retry_on=(OSError,), sleep=lambda s: None)
        assert calls["n"] == 1

    def test_attempts_exhausted_reraises_last(self):
        calls = {"n": 0}

        def always():
            calls["n"] += 1
            raise OSError(f"fail {calls['n']}")

        with pytest.raises(OSError, match="fail 3"):
            retry_call(always, policy=RetryPolicy(attempts=3,
                                                  base_delay_s=0.0),
                       sleep=lambda s: None)
        assert calls["n"] == 3

    def test_deadline_bounds_wall_clock(self):
        def always():
            raise OSError("down")

        t0 = time.monotonic()
        with pytest.raises(OSError):
            retry_call(always,
                       policy=RetryPolicy(attempts=0, base_delay_s=0.01,
                                          max_delay_s=0.02, deadline_s=0.2))
        assert time.monotonic() - t0 < 2.0

    def test_unlimited_attempts_need_deadline_semantics(self):
        calls = {"n": 0}

        def eventually():
            calls["n"] += 1
            if calls["n"] < 10:
                raise OSError("x")
            return calls["n"]

        assert retry_call(eventually,
                          policy=RetryPolicy(attempts=0, base_delay_s=0.0),
                          sleep=lambda s: None) == 10

    def test_jitter_bounds(self):
        import random

        rng = random.Random(7)
        for _ in range(100):
            d = jittered(1.0, 0.5, rng)
            assert 0.5 <= d <= 1.5
        assert jittered(0.0) == 0.0
        assert jittered(2.0, 0.0) == 2.0

    def test_policy_delay_caps(self):
        p = RetryPolicy(base_delay_s=1.0, max_delay_s=4.0, jitter=0.0)
        assert [p.delay_s(i) for i in (1, 2, 3, 4)] == [1.0, 2.0, 4.0, 4.0]

    def test_on_retry_callback_sees_attempts(self):
        seen = []

        def flaky():
            if len(seen) < 2:
                raise OSError("x")
            return True

        retry_call(flaky, policy=RetryPolicy(attempts=5, base_delay_s=0.0),
                   on_retry=lambda i, e: seen.append(i),
                   sleep=lambda s: None)
        assert seen == [1, 2]
