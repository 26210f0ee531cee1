"""Distributed tracing + crash flight recorder (horovod_tpu/obs/trace.py
+ flight.py; docs/tracing.md): span semantics and wire propagation, the
Cristian clock-offset estimator against a synthetic RTT/skew oracle,
cross-process merge (parents resolve, corrected ordering is monotone,
flow arrows emitted), critical-path attribution, flight-recorder dump
contracts, and the ISSUE 7 acceptance drills — a serve request traced
router -> replica -> engine across two BasicService processes, and a
train step under an injected collective fault shipping its own
postmortem."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.obs import flight, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = b"t" * 32


@pytest.fixture(autouse=True)
def _clean_rings():
    """Process-global rings: every test starts from a clean, enabled
    tracer and leaves no residue for the next."""
    trace.configure(enabled=True)
    trace.clear()
    flight.reset_for_tests()
    flight.configure(enabled=True)
    yield
    trace.clear()
    flight.reset_for_tests()


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


class TestSpanBasics:
    def test_nested_spans_parent_under_one_trace(self):
        with trace.span("hvd_tpu_step", root=True) as root_ctx:
            with trace.span("hvd_tpu_rpc_client", kind="client") as child:
                assert child[0] == root_ctx[0]   # same trace
        spans = trace.snapshot()
        (root,) = _by_name(spans, "hvd_tpu_step")
        (kid,) = _by_name(spans, "hvd_tpu_rpc_client")
        assert root["parent_id"] is None
        assert kid["parent_id"] == root["span_id"]
        assert kid["trace_id"] == root["trace_id"]
        assert root["dur_us"] >= kid["dur_us"] >= 0

    def test_root_forces_fresh_trace(self):
        with trace.span("hvd_tpu_step", root=True):
            with trace.span("hvd_tpu_step", root=True) as inner:
                pass
        spans = trace.snapshot()
        assert len(trace.trace_ids(spans)) == 2
        inner_rec = [s for s in spans if s["span_id"] == inner[1]][0]
        assert inner_rec["parent_id"] is None

    def test_explicit_parent_grafts_remote_context(self):
        remote = ("ab" * 16, "cd" * 8)
        with trace.span("hvd_tpu_rpc_server", parent=remote, kind="server"):
            pass
        (rec,) = trace.snapshot()
        assert rec["trace_id"] == remote[0]
        assert rec["parent_id"] == remote[1]

    def test_disabled_records_nothing_and_yields_none(self):
        trace.configure(enabled=False)
        with trace.span("hvd_tpu_step", root=True) as ctx:
            assert ctx is None
            assert trace.instant("hvd_tpu_fault") is None
        assert trace.snapshot() == []

    def test_escaping_exception_recorded_in_args(self):
        with pytest.raises(RuntimeError):
            with trace.span("hvd_tpu_step", root=True):
                raise RuntimeError("boom")
        (rec,) = trace.snapshot()
        assert rec["args"]["error"] == "RuntimeError"

    def test_instant_parents_to_current_context(self):
        with trace.span("hvd_tpu_step", root=True) as ctx:
            trace.instant("hvd_tpu_fault", args={"site": "collective"})
        fault = _by_name(trace.snapshot(), "hvd_tpu_fault")[0]
        assert fault["trace_id"] == ctx[0]
        assert fault["parent_id"] == ctx[1]
        assert fault["dur_us"] == 0.0

    def test_ring_is_bounded_and_resize_keeps_newest(self):
        trace.configure(ring=8)
        try:
            for i in range(20):
                trace.record_span(f"hvd_tpu_step", parent=None,
                                  start_us=float(i), dur_us=1.0,
                                  args={"i": i})
            spans = trace.snapshot()
            assert len(spans) == 8
            assert [s["args"]["i"] for s in spans] == list(range(12, 20))
        finally:
            trace.configure(ring=16384)

    def test_context_is_thread_local(self):
        seen = {}

        def worker():
            seen["ctx"] = trace.current()

        with trace.span("hvd_tpu_step", root=True):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["ctx"] is None


class TestOneClockAndBridge:
    """ISSUE 24: one span path on one clock, bridged to the profiler
    (docs/tracing.md "One span path, one clock")."""

    def test_now_us_is_the_monotonic_clock_on_one_wall_anchor(self):
        t = time.monotonic()
        assert trace.now_us() == pytest.approx(trace.mono_us(t), abs=5e3)
        # The anchor makes it read as unix time, to within clock steps.
        assert trace.now_us() == pytest.approx(time.time() * 1e6, abs=5e6)
        # Differences are exactly the monotonic clock's.
        assert trace.mono_us(t + 0.25) - trace.mono_us(t) == \
            pytest.approx(250_000.0)

    def test_span_and_reconstructed_span_share_the_clock(self):
        t0 = time.monotonic()
        with trace.span("hvd_tpu_step", root=True):
            time.sleep(0.002)
        t1 = time.monotonic()
        trace.record_span("hvd_tpu_serve_queued", parent=None,
                          start_us=trace.mono_us(t0),
                          dur_us=(t1 - t0) * 1e6)
        live, rebuilt = trace.snapshot()
        assert rebuilt["start_us"] <= live["start_us"]
        assert (live["start_us"] + live["dur_us"]
                <= rebuilt["start_us"] + rebuilt["dur_us"])

    def test_span_args_are_read_when_the_span_closes(self):
        counts = {"queued": 2}
        with trace.span("hvd_tpu_serve_step", args=counts):
            counts["emitted"] = 5
        (rec,) = trace.snapshot()
        assert rec["args"] == {"queued": 2, "emitted": 5}
        assert rec["args"] is not counts

    def test_configure_pins_rank_and_timeline_mirror(self, monkeypatch):
        recorded = []

        class FakeTimeline:
            enabled = True

            def _now_us(self):
                return 5_000_000.0

            def record(self, cat, name, start, dur, args=None):
                recorded.append((name, args))

            def flow(self, *a, **k):
                pass

        monkeypatch.delenv("HVD_TPU_PROCESS_ID", raising=False)
        before = trace.process_rank()
        trace.configure(rank=3, timeline=FakeTimeline())
        try:
            assert trace.process_rank() == 3
            with trace.span("hvd_tpu_engine_decode", args={"active": 4}):
                pass
            (rec,) = trace.snapshot()
            assert rec["rank"] == 3
            ((name, args),) = recorded
            assert name == "hvd_tpu_engine_decode"
            assert args["active"] == 4 and args["span_id"] == rec["span_id"]
        finally:
            trace.configure(rank=before, timeline=None)
        with trace.span("hvd_tpu_engine_decode"):
            pass
        assert len(recorded) == 1           # mirror detached
        monkeypatch.setenv("HVD_TPU_PROCESS_ID", "5")
        trace.configure(rank=None)
        try:
            assert trace.process_rank() == 5    # the launch env answers
        finally:
            trace.configure(rank=before)

    def test_init_pins_this_process_rank_and_timeline(self, tmp_path):
        import jax

        assert hvd.is_initialized()
        assert trace.process_rank() == jax.process_index()
        path = str(tmp_path / "tl.json")
        hvd.start_timeline(path)
        try:
            with trace.span("hvd_tpu_engine_decode", args={"active": 1}):
                pass
        finally:
            hvd.stop_timeline()
        assert "hvd_tpu_engine_decode" in open(path).read()
        trace.clear()
        with trace.span("hvd_tpu_engine_decode"):
            pass                        # a stopped timeline takes nothing
        assert "hvd_tpu_engine_decode" in open(path).read()

    def test_span_reaches_a_live_profiler_session(self, tmp_path):
        import glob

        import jax

        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace.span("hvd_tpu_step", root=True,
                            args={"kind": "train", "step": 7,
                                  "skipped": [1, 2]}):
                with trace.span("hvd_tpu_ckpt_save"):
                    time.sleep(0.001)
            trace.record_span("hvd_tpu_serve_queued", parent=None,
                              start_us=trace.now_us(), dur_us=1.0)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        (host,) = [p for p in data.planes if p.name == "/host:CPU"]
        events = {ev.name: ev for line in host.lines for ev in line.events
                  if ev.name.startswith("hvd_tpu_")}
        assert set(events) == {"hvd_tpu_step", "hvd_tpu_ckpt_save"}
        outer, inner = events["hvd_tpu_step"], events["hvd_tpu_ckpt_save"]
        assert outer.start_ns <= inner.start_ns
        assert (inner.start_ns + inner.duration_ns
                <= outer.start_ns + outer.duration_ns)
        stats = dict(outer.stats)
        assert stats["kind"] == "train" and stats["step"] == 7
        assert "skipped" not in stats      # scalars only
        # Ring and profiler agree on how long the span took.
        (ring_outer,) = _by_name(trace.snapshot(), "hvd_tpu_step")
        assert outer.duration_ns / 1e3 == pytest.approx(
            ring_outer["dur_us"], rel=0.2, abs=200.0)

    def test_ids_and_pid_cost_no_system_call_and_survive_a_fork(
            self, monkeypatch):
        """Span ids come from a generator seeded once and the pid is
        read once (a span on the hot path makes no system call); the
        at-fork hook gives a child its own ids and its own pid."""
        monkeypatch.setattr(os, "urandom", lambda n: pytest.fail(
            "a span id asked the OS for entropy"))
        monkeypatch.setattr(os, "getpid", lambda: pytest.fail(
            "a span asked the OS for the pid"))
        with trace.span("hvd_tpu_step", root=True):
            with trace.span("hvd_tpu_ckpt_save"):
                pass
        monkeypatch.undo()
        root, child = sorted(trace.snapshot(),
                             key=lambda r: r["parent_id"] is not None)
        assert len(root["trace_id"]) == 32 and len(root["span_id"]) == 16
        int(root["trace_id"], 16), int(child["span_id"], 16)
        assert root["pid"] == child["pid"] == os.getpid()
        ids = {trace._new_id(8) for _ in range(1000)}
        assert len(ids) == 1000
        # What a forked child runs: same state in, different ids out.
        state = trace._ids.getstate()
        nxt = trace._new_id(8)
        trace._ids.setstate(state)
        monkeypatch.setattr(trace, "_pid", -1)
        trace._after_fork_in_child()
        assert trace._new_id(8) != nxt
        assert trace._pid == os.getpid()

    def test_disabled_span_enters_no_annotation(self, monkeypatch):
        import contextlib

        entered = []

        def spy(name, args):
            entered.append(name)
            return contextlib.nullcontext()

        monkeypatch.setattr(trace, "_annotation", spy)
        trace.configure(enabled=False)
        with trace.span("hvd_tpu_step", root=True):
            pass
        assert entered == []


class TestTrainStepScopes:
    """ISSUE 24 part C: the compiled train step names its phases in
    every operation's metadata, and nothing else changes."""

    def _lowered(self, tx):
        import jax.numpy as jnp

        loss_fn = lambda p, b: (((b @ p["w"]) + p["b"]) ** 2).mean()  # noqa: E731
        step = hvd.make_train_step(loss_fn, tx, donate=False)
        params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
        opt_state = tx.init(params)
        batch = jnp.ones((hvd.size() * 2, 4))
        inner = getattr(step, "__wrapped__", step)
        text = inner.lower(params, opt_state, batch).as_text(
            debug_info=True)
        return text, step(params, opt_state, batch)

    @pytest.mark.parametrize("wrapped", [False, True])
    def test_scopes_are_in_the_lowered_step(self, wrapped):
        import optax

        tx = optax.adam(1e-2)
        if wrapped:
            tx = hvd.DistributedOptimizer(tx)
        text, (params, _, loss) = self._lowered(tx)
        for scope in ("hvd_tpu_fwd_bwd", "hvd_tpu_optimizer",
                      "hvd_tpu_wire_pack", "hvd_tpu_wire_unpack",
                      "hvd_tpu_wire_bucket_0"):
            assert scope in text, scope
        if wrapped:
            # The optimizer's own wire nests inside the optimizer scope.
            assert "hvd_tpu_optimizer/hvd_tpu_wire_bucket_0" in text
        assert np.isfinite(float(loss))

    def test_scopes_change_no_operation(self, monkeypatch):
        """Metadata only: with the scopes taken away the step lowers to
        the same operations."""
        import contextlib
        import re

        import optax

        def ops_of(text):
            body = re.sub(r"loc\(.*?\)\s*$", "", text, flags=re.M)
            body = "\n".join(l for l in body.splitlines()
                             if not l.lstrip().startswith("#loc"))
            return re.sub(r"\s+", " ", body)

        with_scopes, _ = self._lowered(optax.sgd(0.1))
        monkeypatch.setattr(trace, "scope",
                            lambda name: contextlib.nullcontext())
        without, _ = self._lowered(optax.sgd(0.1))
        assert "hvd_tpu_fwd_bwd" not in without
        assert ops_of(with_scopes) == ops_of(without)


class TestDeferredRoot:
    """new_context/use_context + record_span(ctx=): a root whose
    interval is only known at completion (serving_bench --trace) still
    owns its trace — children recorded meanwhile resolve to it."""

    def test_deferred_root_joins_its_trace(self):
        ctx = trace.new_context()
        with trace.use_context(ctx):
            with trace.span("hvd_tpu_serve_prefill") as child:
                assert child[0] == ctx[0]
        t0 = trace.now_us()
        sid = trace.record_span("hvd_tpu_serve_request", parent=None,
                                start_us=t0 - 5_000.0, dur_us=5_000.0,
                                ctx=ctx)
        assert sid == ctx[1]
        spans = trace.snapshot()
        assert trace.unresolved_parents(spans) == []
        rep = trace.critical_path(spans, ctx[0])
        assert rep["root"] == "hvd_tpu_serve_request"
        assert rep["total_us"] == pytest.approx(5_000.0)

    def test_use_context_restores_previous(self):
        assert trace.current() is None
        with trace.use_context(("t" * 32, "s" * 16)):
            assert trace.current() == ("t" * 32, "s" * 16)
        assert trace.current() is None

    def test_reconstructed_span_mirrors_at_its_wall_position(
            self, monkeypatch):
        """The Timeline mirror anchors a span by when it *ended* on the
        wall clock — a phase recorded long after the interval (the
        batcher's queued window, recorded at prefill start) must not be
        shown ending at 'now'."""
        recorded = []

        class FakeTimeline:
            enabled = True

            def _now_us(self):
                return 1_000_000.0

            def record(self, cat, name, start, dur, args=None):
                recorded.append((name, start, dur))

            def flow(self, *a, **k):
                pass

        trace.configure(timeline=FakeTimeline())
        try:
            end_wall = trace.now_us() - 250_000.0    # ended 250 ms ago
            trace.record_span("hvd_tpu_serve_queued", parent=None,
                              start_us=end_wall - 50_000.0,
                              dur_us=50_000.0)
        finally:
            trace.configure(timeline=None)
        ((name, start, dur),) = recorded
        assert name == "hvd_tpu_serve_queued"
        # Back-dated from the TL's "now" by lag (250 ms) + dur (50 ms).
        assert start == pytest.approx(1_000_000.0 - 300_000.0, abs=20_000)
        assert dur == pytest.approx(50_000.0)


class TestPropagation:
    def test_inject_extract_roundtrip(self):
        class Req:
            pass

        with trace.span("hvd_tpu_step", root=True) as ctx:
            req = trace.inject(Req())
        assert trace.extract(req) == ctx

    def test_extract_rejects_garbage(self):
        class Req:
            pass

        req = Req()
        assert trace.extract(req) is None
        req._hvd_trace = "not-a-pair"
        assert trace.extract(req) is None
        req._hvd_trace = (1, 2)
        assert trace.extract(req) is None

    def test_inject_tolerates_slots_classes(self):
        class Slotted:
            __slots__ = ()

        with trace.span("hvd_tpu_step", root=True):
            obj = trace.inject(Slotted())   # must not raise
        assert trace.extract(obj) is None


class TestClockOffset:
    def test_symmetric_wire_recovers_exact_offset(self):
        # Peer clock = local + 5000 us, symmetric 200 us one-way delay.
        samples = [(1000.0, 1400.0, 1000.0 + 200.0 + 5000.0)]
        off, err = trace.estimate_clock_offset(samples)
        assert off == pytest.approx(5000.0)
        assert err == pytest.approx(200.0)

    def test_minimum_rtt_sample_wins(self):
        # The tight sample has the honest offset; the congested one is
        # wildly asymmetric — Cristian must pick the min-RTT bound.
        good = (0.0, 100.0, 50.0 + 7000.0)
        congested = (200.0, 10200.0, 5200.0 + 7000.0 + 4000.0)
        off, err = trace.estimate_clock_offset([congested, good])
        assert off == pytest.approx(7000.0)
        assert err == pytest.approx(50.0)

    def test_synthetic_rtt_skew_oracle(self):
        """Randomized-jitter oracle: the estimate must land within the
        reported error bound of the true skew for every drawn world."""
        rng = np.random.default_rng(7)
        for true_skew in (-2.5e6, -137.0, 0.0, 4242.0, 9.9e8):
            samples = []
            t = 1e9
            for _ in range(24):
                up = 50.0 + float(rng.exponential(300.0))
                down = 50.0 + float(rng.exponential(300.0))
                peer_stamp = t + up + true_skew
                samples.append((t, t + up + down, peer_stamp))
                t += 10_000.0
            off, err = trace.estimate_clock_offset(samples)
            assert abs(off - true_skew) <= err, (true_skew, off, err)
            # The bound itself is half the best draw's RTT: tight-ish.
            assert err < 5e4

    def test_rejects_negative_rtt_and_empty(self):
        with pytest.raises(ValueError, match="negative RTT"):
            trace.estimate_clock_offset([(100.0, 50.0, 0.0)])
        with pytest.raises(ValueError):
            trace.estimate_clock_offset([])


def _mk_span(name, trace_id, span_id, parent, start, dur, rank):
    return {"name": name, "trace_id": trace_id, "span_id": span_id,
            "parent_id": parent, "kind": "internal", "start_us": start,
            "dur_us": dur, "rank": rank, "pid": 1000 + rank, "args": {}}


class TestMerge:
    def _skewed_world(self):
        """Three simulated processes with wildly different wall clocks
        observing one causal chain root(p0) -> mid(p1) -> leaf(p2); each
        process stamps with ITS OWN skewed clock."""
        skews = {0: 0.0, 1: -3.7e8, 2: 2.2e9}   # peer = ref + skew
        true_start = {"root": 1e9, "mid": 1e9 + 10_000.0,
                      "leaf": 1e9 + 20_000.0}
        spans = {
            0: [_mk_span("hvd_tpu_step", "t1", "s-root", None,
                         true_start["root"] + skews[0], 50_000.0, 0)],
            1: [_mk_span("hvd_tpu_rpc_server", "t1", "s-mid", "s-root",
                         true_start["mid"] + skews[1], 30_000.0, 1)],
            2: [_mk_span("hvd_tpu_serve_decode", "t1", "s-leaf", "s-mid",
                         true_start["leaf"] + skews[2], 10_000.0, 2)],
        }
        return skews, true_start, spans

    def test_merged_ordering_monotone_across_skewed_processes(self):
        """THE estimator satellite oracle: raw clocks order the chain
        backwards; after per-process offset correction (estimated from
        synthetic ping RTTs against rank0) the merged slices are
        causally monotone."""
        skews, true_start, spans = self._skewed_world()
        # Raw stamps are hopeless: leaf appears ~2.2e9 us after root,
        # mid ~3.7e8 BEFORE it.  Estimate each peer's offset from ping
        # samples with jittered but symmetric-ish delays.
        rng = np.random.default_rng(3)
        offsets = {0: 0.0}
        for rank in (1, 2):
            samples = []
            t = 5e8
            for _ in range(16):
                up = 80.0 + float(rng.exponential(150.0))
                down = 80.0 + float(rng.exponential(150.0))
                samples.append((t, t + up + down, t + up + skews[rank]))
                t += 7_000.0
            off, err = trace.estimate_clock_offset(samples)
            assert abs(off - skews[rank]) <= err
            offsets[rank] = off
        events = trace.merge_traces({
            f"rank{r}": (offsets[r], spans[r]) for r in spans})
        slices = {e["args"]["span_id"]: e for e in events
                  if e["ph"] == "X"}
        got = [slices[s]["ts"] for s in ("s-root", "s-mid", "s-leaf")]
        assert got == sorted(got), got
        # ...and each corrected stamp is within the ping error of truth.
        for sid, name in (("s-root", "root"), ("s-mid", "mid"),
                          ("s-leaf", "leaf")):
            assert slices[sid]["ts"] == pytest.approx(
                true_start[name], abs=1e3)

    def test_cross_process_edges_draw_flow_arrows(self):
        _, _, spans = self._skewed_world()
        events = trace.merge_traces(
            {f"rank{r}": (0.0, spans[r]) for r in spans})
        flows = [e for e in events if e["ph"] in ("s", "f")]
        # Two cross-process edges -> two s/f pairs keyed by child span.
        assert sorted(e["id"] for e in flows) == \
            ["s-leaf", "s-leaf", "s-mid", "s-mid"]
        for e in flows:
            if e["ph"] == "f":
                assert e["bp"] == "e"
        # Process metadata names each group.
        names = {e["args"]["name"] for e in events if e["ph"] == "M"}
        assert names == {"rank0", "rank1", "rank2"}

    def test_unresolved_parents_detects_missing_ring(self):
        _, _, spans = self._skewed_world()
        collected = spans[0] + spans[2]          # rank1's ring lost
        assert trace.unresolved_parents(collected) == ["s-mid"]
        assert trace.unresolved_parents(
            spans[0] + spans[1] + spans[2]) == []


class TestCriticalPath:
    def test_self_time_attribution_names_dominant_phase(self):
        spans = [
            _mk_span("hvd_tpu_serve_request", "t1", "a", None,
                     0.0, 100_000.0, 0),
            _mk_span("hvd_tpu_rpc_client", "t1", "b", "a",
                     1_000.0, 95_000.0, 0),
            _mk_span("hvd_tpu_rpc_server", "t1", "c", "b",
                     2_000.0, 90_000.0, 1),
            _mk_span("hvd_tpu_serve_prefill", "t1", "d", "c",
                     3_000.0, 10_000.0, 1),
            _mk_span("hvd_tpu_serve_decode", "t1", "e", "c",
                     13_000.0, 70_000.0, 1),
        ]
        rep = trace.critical_path(spans)
        assert rep["root"] == "hvd_tpu_serve_request"
        assert rep["dominant"] == "hvd_tpu_serve_decode"
        assert rep["dominant_self_us"] == pytest.approx(70_000.0)
        assert rep["path"] == ["hvd_tpu_serve_request",
                               "hvd_tpu_rpc_client",
                               "hvd_tpu_rpc_server",
                               "hvd_tpu_serve_decode"]
        # rpc_server self time = 90k - (10k + 70k) = 10k.
        assert rep["self_us"]["hvd_tpu_rpc_server"] == pytest.approx(
            10_000.0)
        assert rep["unresolved_parents"] == []

    def test_picks_longest_trace_by_default(self):
        spans = [
            _mk_span("hvd_tpu_step", "short", "s1", None, 0.0, 10.0, 0),
            _mk_span("hvd_tpu_step", "long", "s2", None, 0.0, 99.0, 0),
        ]
        assert trace.critical_path(spans)["trace_id"] == "long"
        assert trace.critical_path(spans, "short")["trace_id"] == "short"

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            trace.critical_path([])


class TestFlightRecorder:
    def test_events_ring_bounded(self):
        flight.configure(ring=4)
        for i in range(10):
            flight.record("retry", attempt=i)
        evts = flight.events()
        assert len(evts) == 4
        assert [e["attempt"] for e in evts] == [6, 7, 8, 9]

    def test_dump_carries_events_spans_and_identity(self, tmp_path):
        flight.configure(directory=str(tmp_path))
        with trace.span("hvd_tpu_step", root=True):
            trace.instant("hvd_tpu_fault", args={"site": "collective"})
        flight.record("fault", site="collective")
        path = flight.dump("unit_test")
        assert path is not None and os.path.exists(path)
        doc = json.load(open(path))
        # Rank-tagged: filename and payload agree (an initialized world
        # reports its real process index, a bare one the env fallback).
        assert f"_r{doc['rank']}_" in os.path.basename(path)
        assert doc["reason"] == "unit_test"
        assert [e["kind"] for e in doc["events"]] == ["fault"]
        assert "hvd_tpu_fault" in {s["name"] for s in doc["spans"]}
        assert flight.last_dumps() == [path]

    def test_fault_firing_dumps_once_per_site(self, tmp_path):
        """A probability-mode site fires on every dispatch; only the
        FIRST firing per site dumps (the rest land in the ring, carried
        by the terminal-error dump) — the hot path must not pay file
        I/O per firing."""
        from horovod_tpu import faults

        flight.configure(directory=str(tmp_path))
        with faults.inject("collective:p=1.0,seed=1"):
            for _ in range(3):
                with pytest.raises(Exception):
                    faults.on_collective("allreduce")
        dumps = os.listdir(tmp_path)
        assert sum("fault_collective" in d for d in dumps) == 1
        # ...but a distinct site (fresh plan or not) still gets its own
        # first-firing dump.
        with faults.inject("rpc:step=0,mode=drop"):
            with pytest.raises(ConnectionError):
                faults.on_rpc("ping")
        dumps = os.listdir(tmp_path)
        assert sum("fault_rpc" in d for d in dumps) == 1
        assert len([e for e in flight.events()
                    if e["kind"] == "fault"]) == 4

    def test_dump_is_fail_soft(self, tmp_path):
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("file where the dir should go")
        flight.configure(directory=str(blocker))
        assert flight.dump("nope") is None   # never raises

    def test_disabled_records_nothing(self, tmp_path):
        flight.configure(enabled=False, directory=str(tmp_path))
        flight.record("fault", site="x")
        assert flight.dump("off") is None
        assert flight.events() == []

    def test_empty_directory_rearms_env_default(self, monkeypatch,
                                                tmp_path):
        monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path / "envd"))
        flight.configure(directory="")      # Config left the knob unset
        path = flight.dump("env_default")
        assert path is not None
        assert path.startswith(str(tmp_path / "envd"))


class TestWirePropagation:
    def test_rpc_spans_parent_across_the_wire(self):
        """BasicClient._call injects, BasicService extracts: the server
        span's parent is the client span, both on one trace."""
        from horovod_tpu.runner.common.network import (BasicClient,
                                                       BasicService,
                                                       PingRequest)

        svc = BasicService("trace-unit", KEY, host="127.0.0.1")
        try:
            client = BasicClient("trace-unit",
                                 [("127.0.0.1", svc.port)], KEY)
            with trace.span("hvd_tpu_step", root=True) as ctx:
                resp = client.request(PingRequest())
            assert resp.clock_us is not None
        finally:
            svc.shutdown()
        # The client constructor probes the service with its own
        # (fresh-trace) ping exchange; our exchange is the one on the
        # step trace.
        spans = [s for s in trace.snapshot() if s["trace_id"] == ctx[0]]
        (cli,) = _by_name(spans, "hvd_tpu_rpc_client")
        (srv,) = _by_name(spans, "hvd_tpu_rpc_server")
        assert srv["parent_id"] == cli["span_id"]
        assert srv["args"]["req"] == "PingRequest"

    def test_trace_request_fetches_and_optionally_drains(self):
        from horovod_tpu.runner.common.network import (BasicClient,
                                                       BasicService,
                                                       TraceRequest)

        with trace.span("hvd_tpu_step", root=True):
            pass
        svc = BasicService("trace-fetch", KEY, host="127.0.0.1")
        try:
            client = BasicClient("trace-fetch",
                                 [("127.0.0.1", svc.port)], KEY)
            resp = client.request(TraceRequest(clear=True))
        finally:
            svc.shutdown()
        assert resp.now_us > 0 and resp.pid == os.getpid()
        assert "hvd_tpu_step" in {s["name"] for s in resp.spans}
        # clear=True drained the ring (the TraceRequest exchange itself
        # re-recorded its own client/server spans afterwards).
        left = {s["name"] for s in trace.snapshot()}
        assert "hvd_tpu_step" not in left

    def test_untraced_peer_request_grows_no_server_span(self):
        from horovod_tpu.runner.common.network import (BasicClient,
                                                       BasicService,
                                                       PingRequest)

        svc = BasicService("trace-off", KEY, host="127.0.0.1")
        try:
            client = BasicClient("trace-off",
                                 [("127.0.0.1", svc.port)], KEY)
            trace.clear()         # drop the constructor-probe spans
            req = PingRequest()   # no _hvd_trace on the request
            trace.configure(enabled=False)
            client.request(req)
            trace.configure(enabled=True)
        finally:
            svc.shutdown()
        assert _by_name(trace.snapshot(), "hvd_tpu_rpc_server") == []


class TestTraceMergeScript:
    def _dump(self, path, rank, spans):
        with open(path, "w") as f:
            json.dump({"reason": "test", "rank": rank, "pid": 1,
                       "events": [], "spans": spans}, f)

    def test_merges_flight_dumps_into_one_perfetto_file(self, tmp_path):
        spans0 = [_mk_span("hvd_tpu_step", "t1", "a", None,
                           0.0, 9_000.0, 0)]
        spans1 = [_mk_span("hvd_tpu_rpc_server", "t1", "b", "a",
                           1_000.0, 5_000.0, 1)]
        self._dump(tmp_path / "d0.json", 0, spans0)
        self._dump(tmp_path / "d1.json", 1, spans1)
        out = tmp_path / "merged.json"
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts",
                                          "trace_merge.py"),
             str(out), str(tmp_path / "d0.json"),
             str(tmp_path / "d1.json"), "--report"],
            capture_output=True, text=True, cwd=ROOT)
        assert proc.returncode == 0, proc.stderr
        doc = json.load(open(out))
        slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(slices) == 2
        assert doc["metadata"]["unresolved_parents"] == []
        assert {p for p in doc["metadata"]["processes"]} == \
            {"rank0", "rank1"}
        (rep,) = doc["metadata"]["critical_paths"]
        assert rep["root"] == "hvd_tpu_step"
        assert rep["root"] in proc.stdout
        # One cross-process edge -> one flow arrow pair.
        assert [e["ph"] for e in doc["traceEvents"]
                if e["ph"] in ("s", "f")].count("s") == 1

    def test_warns_on_unresolved_parents(self, tmp_path):
        self._dump(tmp_path / "d1.json", 1,
                   [_mk_span("hvd_tpu_rpc_server", "t1", "b", "lost",
                             0.0, 5.0, 1)])
        out = tmp_path / "merged.json"
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts",
                                          "trace_merge.py"),
             str(out), str(tmp_path / "d1.json")],
            capture_output=True, text=True, cwd=ROOT)
        assert proc.returncode == 0
        assert "unresolved" in proc.stderr
        assert json.load(open(out))["metadata"]["unresolved_parents"] \
            == ["lost"]

    def test_nothing_to_merge_is_an_error(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts",
                                          "trace_merge.py"),
             str(tmp_path / "out.json")],
            capture_output=True, text=True, cwd=ROOT)
        assert proc.returncode != 0


class TestChaosSoakFlightDumps:
    """ISSUE 7 satellite: a failed soak iteration's summary row records
    its flight-recorder dump paths; a passed iteration leaves nothing
    behind."""

    @staticmethod
    def _chaos_soak():
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "chaos_soak", os.path.join(ROOT, "scripts", "chaos_soak.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    @staticmethod
    def _target(tmp_path, fail):
        # Stands in for the chaos suite: dumps "a postmortem" into
        # HVD_TPU_FLIGHT_DIR exactly like obs/flight.py would, then
        # passes or fails.
        path = tmp_path / f"test_fake_chaos_{'fail' if fail else 'pass'}.py"
        path.write_text(
            "import json, os, pytest\n"
            "@pytest.mark.chaos\n"
            "def test_drill():\n"
            "    d = os.environ['HVD_TPU_FLIGHT_DIR']\n"
            "    os.makedirs(d, exist_ok=True)\n"
            "    with open(os.path.join(d, 'hvd_tpu_flight_r0.json'),"
            " 'w') as f:\n"
            "        json.dump({'reason': 'fault', 'spans': []}, f)\n"
            f"    assert {not fail}\n")
        return str(path)

    def test_failed_iteration_records_dump_paths(self, tmp_path):
        soak = self._chaos_soak()
        flight_dir = str(tmp_path / "flight" / "iter_0000")
        row = soak.run_once(self._target(tmp_path, fail=True),
                            step=0, seed=1, timeout_s=120.0,
                            flight_dir=flight_dir)
        assert not row["passed"]
        (dump,) = row["flight_dumps"]
        assert json.load(open(dump))["reason"] == "fault"

    def test_passed_iteration_cleans_up(self, tmp_path):
        soak = self._chaos_soak()
        flight_dir = str(tmp_path / "flight" / "iter_0000")
        row = soak.run_once(self._target(tmp_path, fail=False),
                            step=0, seed=1, timeout_s=120.0,
                            flight_dir=flight_dir)
        assert row["passed"], row["tail"]
        assert "flight_dumps" not in row
        assert not os.path.exists(flight_dir)


_REPLICA_SCRIPT = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["HVD_TPU_PROCESS_ID"] = "1"
import jax, jax.numpy as jnp
from horovod_tpu.models.transformer import GPT, GPTConfig
from horovod_tpu.serve import (ContinuousBatcher, InferenceEngine,
                               InferenceServer)

cfg = GPTConfig(vocab_size=97, n_layer=1, n_head=2, d_model=32, d_ff=64,
                max_seq_len=32, dtype=jnp.float32, param_dtype=jnp.float32)
model = GPT(cfg)
params = model.init(jax.random.PRNGKey(0),
                    jnp.zeros((1, 8), jnp.int32))["params"]
engine = InferenceEngine(model, params, max_slots=2,
                         prefill_buckets=(8, 16), max_seq_len=32)
batcher = ContinuousBatcher(engine)
srv = InferenceServer(batcher, key=%r, name="replica0", host="127.0.0.1")
print(srv.port, flush=True)
sys.stdin.read()        # parent closes stdin to stop us
srv.shutdown()
""" % KEY


class TestEndToEnd:
    @pytest.mark.serving
    def test_serve_request_traced_across_two_processes(self):
        """ISSUE 7 acceptance (serve side): one request traced
        router -> replica -> engine across two real OS processes merges
        into ONE trace — every span's parent resolves, and the
        critical-path report names the decode phase."""
        from horovod_tpu.runner.common.network import (BasicClient,
                                                       PingRequest,
                                                       TraceRequest)
        from horovod_tpu.serve import ReplicaSpec, Router

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen([sys.executable, "-c", _REPLICA_SCRIPT],
                                stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, text=True,
                                cwd=ROOT, env=env)
        try:
            port = int(proc.stdout.readline())   # blocks through jax init
            router = Router([ReplicaSpec("replica0",
                                         [("127.0.0.1", port)])], KEY)
            # Warm the replica's compiled programs so the traced request
            # measures runtime, not XLA compilation.
            router.generate([5, 6, 7], max_new_tokens=4)
            trace.clear()
            resp = router.generate([3, 14, 15, 92], max_new_tokens=16,
                                   request_id="traced-req")
            assert resp.error is None and len(resp.tokens) == 16

            local = trace.snapshot()
            peer = BasicClient("replica0", [("127.0.0.1", port)], KEY)
            samples = []
            for _ in range(9):
                send = trace.now_us()
                pong = peer.request(PingRequest())
                samples.append((send, trace.now_us(), pong.clock_us))
            offset, err = trace.estimate_clock_offset(samples)
            remote = peer.request(TraceRequest()).spans
        finally:
            proc.stdin.close()
            proc.wait(timeout=30)

        # The request's spans, both sides of the wire:
        (root,) = [s for s in _by_name(local, "hvd_tpu_serve_request")
                   if s["args"].get("request_id") == "traced-req"]
        tid = root["trace_id"]
        all_spans = [s for s in local + remote if s["trace_id"] == tid]
        names = {s["name"] for s in all_spans}
        assert {"hvd_tpu_serve_request", "hvd_tpu_rpc_client",
                "hvd_tpu_rpc_server", "hvd_tpu_serve_queued",
                "hvd_tpu_serve_prefill",
                "hvd_tpu_serve_decode"} <= names
        # ONE trace, every parent resolving — including across the
        # process boundary (server's parent is the client span id).
        assert trace.unresolved_parents(all_spans) == []
        by_id = {s["span_id"]: s for s in all_spans}
        (srv_span,) = [s for s in all_spans
                       if s["name"] == "hvd_tpu_rpc_server"
                       and s["args"].get("req") == "GenerateRequest"]
        assert by_id[srv_span["parent_id"]]["name"] == "hvd_tpu_rpc_client"
        (decode,) = _by_name(all_spans, "hvd_tpu_serve_decode")
        assert by_id[decode["parent_id"]] is srv_span
        assert srv_span["pid"] != root["pid"]    # genuinely two processes

        # Merge with the ping-estimated offset and attribute latency:
        # a 16-token generation is decode-dominated.
        merged = trace.merge_traces({"router": (0.0, local),
                                     "replica": (offset, remote)})
        assert any(e["ph"] == "s" for e in merged)   # cross-proc arrows
        rep = trace.critical_path(all_spans, tid)
        assert rep["dominant"] == "hvd_tpu_serve_decode"
        assert rep["path"][-1] == "hvd_tpu_serve_decode"
        assert err >= 0.0

    def test_train_step_under_fault_ships_postmortem(self, monkeypatch,
                                                     tmp_path):
        """ISSUE 7 acceptance (train side): a collective fault during
        elastic training dumps a rank-tagged postmortem containing the
        fault-site span and the elastic rollback event."""
        import jax.numpy as jnp
        import optax

        from horovod_tpu import basics, faults
        from horovod_tpu.elastic import ObjectState, run
        from horovod_tpu.elastic import state as state_mod

        monkeypatch.setattr(state_mod.time, "sleep", lambda s: None)
        monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
        flight.configure(directory=str(tmp_path))

        spec = "collective:step=2"
        monkeypatch.setenv("HVD_TPU_FAULT_SPEC", spec)
        tx = optax.sgd(0.1)
        loss_fn = lambda p, b: ((p["w"] * b).sum() ** 2)  # noqa: E731
        x = np.ones((hvd.size(), 2), np.float32)
        state = ObjectState(step=0)

        @run
        def train(state):
            step = hvd.make_train_step(loss_fn, tx, donate=False)
            params = {"w": jnp.ones((4,))}
            opt_state = tx.init(params)
            batch = jnp.ones((8, 4))
            while state.step < 4:
                hvd.allreduce(x, op=hvd.Sum, name="trace_e2e")
                params, opt_state, loss = step(params, opt_state, batch)
                state.step += 1
                state.commit()
            return float(loss)

        try:
            with faults.inject(spec):
                train(state)
        finally:
            monkeypatch.delenv("HVD_TPU_FAULT_SPEC")
            faults.clear()
            basics.shutdown()
            basics.init()

        dumps = sorted(os.listdir(tmp_path))
        assert dumps, "no flight-recorder dump written"
        # The rollback dump is written entering the recovery path,
        # AFTER the firing dump — it carries the whole story.
        rollback = [d for d in dumps if "horovod_internal_error" in d]
        assert rollback, dumps
        doc = json.load(open(tmp_path / rollback[-1]))
        # The fault-site span, parented into the live trace world:
        fault_spans = [s for s in doc["spans"]
                       if s["name"] == "hvd_tpu_fault"]
        assert any(s["args"].get("site") == "collective"
                   for s in fault_spans)
        # Step spans made it into the ring too (the traced step loop).
        assert any(s["name"] == "hvd_tpu_step" for s in doc["spans"])
        # The elastic rollback event and the fault firing:
        kinds = [e["kind"] for e in doc["events"]]
        assert "fault" in kinds and "elastic_rollback" in kinds
        (rb,) = [e for e in doc["events"]
                 if e["kind"] == "elastic_rollback"]
        assert "HorovodInternalError" in rb["error"] \
            or "fault" in rb["error"]
        assert doc["fault_spec"] == spec
        # Rank-tagged filename (single-controller world: rank 0).
        assert "_r0_" in rollback[-1]
        # And the firing itself dumped immediately (postmortem exists
        # even when recovery never runs).
        assert any("fault_collective" in d for d in dumps)