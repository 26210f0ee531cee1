"""The readers of the program's own spans (``reduce/program_spans.py``,
``reduce/xspace.py`` and the per-layer readers on top of them).  The
arithmetic is pinned on ``reduce/sample_program_spans.jsonl`` — rows
written by hand in the shape a traced chat run has: three scheduling
steps, the first with a prefill, each engine span inside the
benchmark's own wrapper, and one span of another thread.  The reader of
the operations' metadata is pinned on an XSpace written here byte by
byte.  Then a CPU rehearsal: each serve reader reads what a CPU run
holds (the ring; the host plane of a CPU trace) and none raises."""

import os
import re
import struct
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hvdbench import layers  # noqa: E402
from hvdbench.layer_metrics import (engine_host_ms, queue_wait_ms,  # noqa: E402
                                    sched_self_ms, scope_ms)
from hvdbench.reduce import program_spans as ps  # noqa: E402
from hvdbench.reduce import xplane, xspace  # noqa: E402

SAMPLE = os.path.join(ROOT, "hvdbench", "reduce",
                      "sample_program_spans.jsonl")


@pytest.fixture(scope="module")
def rows():
    return xplane.read_jsonl(SAMPLE)


def view_of(rows, **facts):
    return layers.RunView(
        cell={"name": "no-such-cell"}, config={}, traffic={}, facts=facts,
        memory={}, device_kind="TPU v5 lite", rows=rows, busy=None)


# --- the arithmetic, on the sample -------------------------------------------

def test_a_steps_self_time_is_what_its_engine_children_leave(rows):
    own = ps.self_times(rows, ps.SERVE_STEP,
                        (ps.ENGINE_PREFILL, ps.ENGINE_DECODE))
    # 290 - (143 + 145), 146.5 - 145.7, 145.4 - 145.0 ms; the prefill
    # span of another thread inside the third step does not count.
    assert own == pytest.approx([0.002, 0.0008, 0.0004])


def test_host_time_of_a_decode_is_its_span_less_the_device(rows):
    host = ps.host_times_under(rows, ps.ENGINE_DECODE)
    # 145.0 - 140, 145.7 - 141, 145.0 - 142 ms; the span under which
    # the device ran nothing (cut by the trace's start) is left out.
    assert host == pytest.approx([0.005, 0.0047, 0.003])


def test_program_spans_lie_inside_the_benchmarks_wrappers(rows):
    for inner, outer in ((ps.ENGINE_PREFILL, "engine_prefill"),
                         (ps.ENGINE_DECODE, "engine_decode")):
        wrappers = xplane.spans_of(rows, outer)
        assert wrappers
        for w in wrappers:
            inside = [s for s in xplane.spans_of(rows, inner)
                      if s["line"] == w["line"]
                      and w["start_ns"] <= s["start_ns"]
                      and s["start_ns"] + s["dur_ns"]
                      <= w["start_ns"] + w["dur_ns"]]
            assert len(inside) == 1


def test_the_serve_readers_on_the_sample(rows, monkeypatch):
    monkeypatch.setattr(ps, "rows", lambda view: rows)
    view = view_of(rows)
    assert sched_self_ms.read({"sched_self_ms.tpot"}, view) == {
        "sched_self_ms.tpot": pytest.approx(0.8)}
    assert engine_host_ms.read({"engine_host_ms.tpot"}, view) == {
        "engine_host_ms.tpot": pytest.approx(4.7)}
    # Asked for nothing, a reader gives nothing.
    assert sched_self_ms.read({"decode_step_ms.tpot"}, view) == {}


def test_a_program_without_the_spans_reads_as_nothing(rows, monkeypatch):
    """The parent commit: only the benchmark's own wrappers are there."""
    old = [r for r in rows if not r["name"].startswith("hvd_tpu_")]
    monkeypatch.setattr(ps, "rows", lambda view: old)
    view = view_of(old)
    assert sched_self_ms.read({"sched_self_ms.tpot"}, view) == {}
    assert engine_host_ms.read({"engine_host_ms.tpot"}, view) == {}
    monkeypatch.setattr(ps, "ring", lambda: [])
    assert queue_wait_ms.read({"queue_wait_ms.itl"},
                              view_of(old, elapsed_s=45.0)) == {}


def test_an_untraced_run_finds_no_trace_file():
    view = view_of(None)
    assert ps.trace_file(view) is None and ps.rows(view) == []
    assert ps.scope_seconds(view) is None


def test_a_reader_that_fails_reports_nothing_and_says_so(monkeypatch, capsys):
    def broken(view):
        raise RuntimeError("boom")

    monkeypatch.setattr(ps, "rows", broken)
    monkeypatch.setattr(ps, "ring", lambda: [{"name": ps.SERVE_STEP}])
    monkeypatch.setattr(ps, "scope_seconds", broken)
    view = view_of([], elapsed_s=1.0, traced_steps=2)
    assert sched_self_ms.read({"sched_self_ms.tpot"}, view) == {}
    assert engine_host_ms.read({"engine_host_ms.tpot"}, view) == {}
    assert queue_wait_ms.read({"queue_wait_ms.itl"}, view) == {}
    assert scope_ms.read({"fwd_bwd_ms.train"}, view) == {}
    assert capsys.readouterr().out.count("not read") == 4


# --- the ring ----------------------------------------------------------------

def _ring(n_steps=40, step_us=100_000.0, waits=(10e3, 30e3, 50e3)):
    spans = [{"name": ps.SERVE_STEP, "start_us": i * step_us,
              "dur_us": step_us - 1.0} for i in range(n_steps)]
    close = (n_steps - 1) * step_us + step_us - 1.0
    for k, w in enumerate(waits):      # ended 0.5, 1.5, 2.5 s before close
        end = close - (0.5 + k) * 1e6
        spans.append({"name": ps.QUEUED, "start_us": end - w, "dur_us": w})
    return spans, close


def test_queue_wait_is_the_median_over_the_windows_queued_spans(monkeypatch):
    spans, _ = _ring()
    monkeypatch.setattr(ps, "ring", lambda: spans)
    read = lambda s: queue_wait_ms.read(  # noqa: E731
        {"queue_wait_ms.itl"}, view_of([], elapsed_s=s))
    assert read(3.0) == {"queue_wait_ms.itl": pytest.approx(30.0)}
    # A 2 s window holds the two that ended 0.5 and 1.5 s before it closed.
    assert read(2.0) == {"queue_wait_ms.itl": pytest.approx(20.0)}


def test_a_ring_that_lost_the_windows_opening_is_not_read(monkeypatch, capsys):
    spans, _ = _ring()
    monkeypatch.setattr(ps, "ring", lambda: spans)
    # The ring's oldest span ended 3.9 s before the close: a 10 s window
    # opened before it, so part of the window may have been washed out.
    assert queue_wait_ms.read({"queue_wait_ms.itl"},
                              view_of([], elapsed_s=10.0)) == {}
    assert "no longer holds" in capsys.readouterr().out


# --- the operations' metadata ------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    if isinstance(payload, float):
        return _varint(number << 3 | 1) + struct.pack("<d", payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _xspace(ops, stat_name="tf_op"):
    """One device plane whose ``XLA Ops`` line runs each of ``ops``
    (``(hlo text, op_name or None, duration_ps[, offset_ps])``) once."""
    stat_meta = _field(5, _field(1, 7) + _field(2, _field(1, 7)
                                                + _field(2, stat_name)))
    flops_meta = _field(5, _field(1, 8) + _field(2, _field(1, 8)
                                                 + _field(2, "flops")))
    metas, events, cursor = b"", b"", 0
    for i, (name, op_name, dur, *at) in enumerate(ops, start=1):
        stats = _field(5, _field(1, 8) + _field(3, 12345))
        if op_name is not None:
            stats += _field(5, _field(1, 7) + _field(5, op_name))
        metas += _field(4, _field(1, i) + _field(2, _field(1, i)
                                                 + _field(2, name) + stats))
        start = at[0] if at else cursor     # one after another, unless
        cursor = max(cursor, start + dur)   # it says where it starts
        events += _field(4, _field(1, i) + _field(2, start)
                         + _field(3, dur))
    line = _field(3, _field(1, 1) + _field(2, "XLA Ops") + events)
    steps = _field(3, _field(1, 2) + _field(2, "Steps")
                   + _field(4, _field(1, 1) + _field(3, 10 ** 9)))
    plane = _field(1, 1) + _field(2, "/device:TPU:0") + steps + line \
        + metas + stat_meta + flops_meta
    other = _field(1, 2) + _field(2, "/host:CPU")
    return _field(1, other) + _field(1, plane) + _field(4, "host")


OPS = [
    ("%fusion.1 = bf16[8,1024]{1,0} fusion(...)",
     "jit(step)/jit(main)/shard_map/hvd_tpu_fwd_bwd/jvp(GPT)/dot_general",
     200_000_000_000),
    ("%fusion.2 = bf16[8,1024]{1,0} fusion(...)",
     "jit(step)/jit(main)/shard_map/hvd_tpu_fwd_bwd/transpose(jvp(GPT))/mul",
     70_000_000_000),
    ("%fusion.3 = f32[1024]{0} fusion(...)",
     "jit(step)/jit(main)/shard_map/hvd_tpu_optimizer/add", 15_000_000_000),
    ("%concatenate.1 = f32[4096]{0} concatenate(...)",
     "jit(step)/shard_map/hvd_tpu_optimizer/hvd_tpu_wire_pack/concatenate",
     9_000_000_000),
    ("%all-reduce.1 = f32[4096]{0} all-reduce(...)",
     "jit(step)/shard_map/hvd_tpu_optimizer/hvd_tpu_wire_bucket_0/psum",
     28_000_000_000),
    ("%slice.1 = f32[1024]{0} slice(...)",
     "jit(step)/shard_map/hvd_tpu_optimizer/hvd_tpu_wire_unpack/slice",
     11_000_000_000),
    ("%copy.1 = f32[8]{0} copy(...)", "jit(step)/jit(main)/copy",
     1_000_000_000),
    ("%copy.2 = f32[8]{0} copy(...)", None, 500_000_000),
]


def test_device_time_by_the_programs_scope(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(OPS))
    assert [n for n, _ in xspace.planes(str(path))] == [
        "/host:CPU", "/device:TPU:0"]
    got = xspace.seconds_by_scope(str(path), "/device:TPU:0", "XLA Ops",
                                  ps.SCOPE)
    assert got["ops"] == 8 and got["named"] == 7
    assert got["by_scope"] == {
        "hvd_tpu_fwd_bwd": pytest.approx(0.27),
        "hvd_tpu_optimizer": pytest.approx(0.015),
        "hvd_tpu_wire_pack": pytest.approx(0.009),      # the innermost
        "hvd_tpu_wire_bucket_0": pytest.approx(0.028),
        "hvd_tpu_wire_unpack": pytest.approx(0.011)}
    assert got["other_s"] == pytest.approx(0.0015)
    assert got["other_top"] == [["copy", pytest.approx(0.0015)]]
    assert xspace.seconds_by_scope(str(path), "/device:TPU:9", "XLA Ops",
                                   ps.SCOPE) is None


def test_a_while_counts_once_and_its_body_goes_with_it(tmp_path):
    """On a TPU's ``XLA Ops`` a ``while`` is one event with the events
    of its body inside it: each picosecond counts once, and a body
    operation with no scope of its own goes with the ``while``."""
    ps_ = 10 ** 9       # a millisecond, in picoseconds
    ops = [
        ("%while.1 = (s32[], f32[8]) while(...)",
         "jit(step)/hvd_tpu_fwd_bwd/transpose(jvp(attn))/while", 50 * ps_, 0),
        ("%fusion.9 = f32[8]{0} fusion(...)",
         "jit(step)/hvd_tpu_fwd_bwd/transpose(jvp(attn))/while/body/mul",
         20 * ps_, 1 * ps_),
        ("%copy.9 = f32[8]{0} copy(...)", None, 25 * ps_, 22 * ps_),
        ("%fusion.10 = f32[8]{0} fusion(...)",
         "jit(step)/hvd_tpu_optimizer/add", 5 * ps_, 50 * ps_),
        ("%copy.10 = f32[8]{0} copy(...)", None, 2 * ps_, 55 * ps_),
    ]
    assert xspace.exclusive([(1, 0, 50), (2, 1, 20), (3, 22, 25),
                             (4, 50, 5)]) == [
        (1, 5, None), (2, 20, 0), (3, 25, 0), (4, 5, None)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops))
    got = xspace.seconds_by_scope(str(path), "/device:TPU:0", "XLA Ops",
                                  ps.SCOPE)
    assert got["by_scope"] == {"hvd_tpu_fwd_bwd": pytest.approx(0.050),
                               "hvd_tpu_optimizer": pytest.approx(0.005)}
    assert got["other_s"] == pytest.approx(0.002)
    assert sum(got["by_scope"].values()) + got["other_s"] == \
        pytest.approx(0.057)           # what the line was busy


def test_the_train_readers_on_such_a_trace(tmp_path, monkeypatch, capsys):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(OPS))
    monkeypatch.setattr(ps, "trace_file", lambda view: str(path))
    device_rows = [{"plane": "/device:TPU:0", "line": "XLA Ops",
                    "name": "%x = f32[] copy()", "start_ns": 0.0,
                    "dur_ns": 1.0}]
    wanted = {"fwd_bwd_ms.train", "optimizer_ms.train",
              "wire_pack_ms.train"}
    got = scope_ms.read(wanted, view_of(device_rows, traced_steps=2))
    assert got == {"fwd_bwd_ms.train": pytest.approx(135.0),
                   "optimizer_ms.train": pytest.approx(7.5),
                   "wire_pack_ms.train": pytest.approx(10.0)}
    said = capsys.readouterr().out
    assert '"hvd_tpu_wire_buckets": 14.0' in said
    assert '"unattributed": 0.75' in said
    # One chip has no wire: the metric is left out, not reported as 0.
    path.write_bytes(_xspace(OPS[:3] + OPS[6:]))
    got = scope_ms.read(wanted, view_of(device_rows, traced_steps=2))
    assert set(got) == {"fwd_bwd_ms.train", "optimizer_ms.train"}


def test_a_trace_whose_operations_carry_no_scope_reads_as_nothing(
        tmp_path, monkeypatch, capsys):
    """The parent commit's trace, or a runtime that leaves op_name out."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace([(n, None, d) for n, _, d in OPS]))
    monkeypatch.setattr(ps, "trace_file", lambda view: str(path))
    rows = [{"plane": "/device:TPU:0", "line": "XLA Ops", "name": "%x",
             "start_ns": 0.0, "dur_ns": 1.0}]
    assert scope_ms.read({"fwd_bwd_ms.train"},
                         view_of(rows, traced_steps=2)) == {}
    assert "no operation of the trace lies under" in capsys.readouterr().out


def test_a_file_that_is_no_xspace_raises_value_error(tmp_path):
    path = tmp_path / "bad.pb"
    path.write_bytes(b"\x0a\xff\xff\xff")
    with pytest.raises(ValueError):
        list(xspace.planes(str(path)))


# --- a CPU rehearsal ---------------------------------------------------------

def test_each_serve_reader_reads_a_cpu_run():
    """A traced rehearsal of the chat cell on the CPU: the ring gives
    the queue wait through the run itself; the CPU trace's host plane
    gives the scheduler's self time; the engine's host time needs a
    device plane and reads as nothing.  None raises."""
    from hvdbench.tests import test_rehearsal as reh

    ps._rows_cache.clear()
    line = reh.rehearse(reh.CELLS["serve-open"], trace=True)
    assert line["metrics"]["queue_wait_ms.itl"]["value"] >= 0
    assert line["metrics"]["queue_wait_ms.itl"]["unit"] == "ms"
    bench = reh.tiny.bench()
    cell = next(c for c in bench["workloads"]
                if c["name"] == reh.CELLS["serve-open"])
    view = layers.RunView(cell=cell, config={}, traffic={}, facts={},
                          memory={}, device_kind="cpu", rows=[], busy=None)
    found = ps.rows(view)
    names = {r["name"] for r in found}
    assert {ps.SERVE_STEP, ps.ENGINE_PREFILL, ps.ENGINE_DECODE} <= names
    got = sched_self_ms.read({"sched_self_ms.tpot"}, view)
    assert got["sched_self_ms.tpot"] >= 0
    assert engine_host_ms.read({"engine_host_ms.tpot"}, view) == {}
    # Every engine span of the trace lies inside a step span.
    steps = xplane.spans_of(found, ps.SERVE_STEP)
    for name in (ps.ENGINE_PREFILL, ps.ENGINE_DECODE):
        for s in xplane.spans_of(found, name):
            assert any(t["start_ns"] <= s["start_ns"]
                       and s["start_ns"] + s["dur_ns"]
                       <= t["start_ns"] + t["dur_ns"] for t in steps)
