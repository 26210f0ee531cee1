"""The readers of a decode step's phases (``layer_metrics/
_decode_phases.py`` and the six readers on top of it), pinned on
``reduce/sample_decode_phases.jsonl`` — rows in the shape
``xplane.load_events`` returns, written so that every number is known
by construction (microseconds below; the file holds nanoseconds).

Six scheduling steps on the thread ``python``, the first with a
prefill, each ``hvd_tpu_engine_decode`` holding its dispatch and fence
annotations; a decode span of another thread with nothing in it.

====  ===================  ==================  ==================
step  decode span          dispatch            fence
====  ===================  ==================  ==================
1     31,500 – 59,000      32,000 – 34,000     34,200 – 56,500
2     62,200 – 89,500      62,500 – 63,800     64,100 – 88,500
3     90,600 – 299,500     91,000 – 92,500     92,800 – 298,500
4     300,600 – 329,500    301,000 – 302,500   302,700 – 328,500
5     330,500 – 359,500    333,000 – 334,500   334,700 – 358,500
6     360,400 – 389,500    360,600 – 366,000   366,200 – 388,500
====  ===================  ==================  ==================

Device 0 runs twelve operations from 1,000 to 387,000 (the traced
window, 386,000): five small ones under the prefill, then (33,500 –
43,500, 43,501 – 55,500), 63,000 – 87,000, 92,000 – 114,000, 302,000 –
327,000, 334,000 – 357,000, 365,000 – 387,000 under the six decode
spans.  Step 3 is the stalled one: its device finished at 114,000 and
its fence returned at 298,500.  The eleven gaps, each cut where the
program's phases begin and end: prefill dispatch 1,000; prefill fence
2,500; the rest of the prefill span 1,800; the rest of the decode spans
10,800; the steps' own time 3,200; outside the program 4,600 (step 1
ends at 59,100 and step 2 begins at 62,000: 2,900; 500, 500, 400 and
300 between the later steps); the decode fences 190,000; the decode
dispatches 9,400; under 2 us 1: together
223,301, the window less the 162,699 the device was busy."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hvdbench import layers  # noqa: E402
from hvdbench.layer_metrics import _decode_phases as phases  # noqa: E402
from hvdbench.layer_metrics import (decode_dispatch_ms,  # noqa: E402
                                    decode_launch_ms, decode_readback_ms,
                                    idle_outside_program, stalled_steps,
                                    step_uploads_share)
from hvdbench.reduce import program_spans as ps  # noqa: E402
from hvdbench.reduce import xplane  # noqa: E402

SAMPLE = os.path.join(ROOT, "hvdbench", "reduce",
                      "sample_decode_phases.jsonl")
ALL = {"decode_dispatch_ms.tpot", "decode_launch_ms.tpot",
       "decode_readback_ms.tpot", "stalled_steps.tpot",
       "step_uploads_share.tpot", "idle_outside_program.tpot"}
READERS = (decode_dispatch_ms, decode_launch_ms, decode_readback_ms,
           stalled_steps, step_uploads_share, idle_outside_program)
# The ring's clock is the trace's plus a constant the reader has to find.
RING_AHEAD_US = 1.7e15


@pytest.fixture(scope="module")
def rows():
    return xplane.read_jsonl(SAMPLE)


def view_of(rows, **facts):
    busy = xplane.busy(rows) if rows and xplane.device_planes(rows) else None
    return layers.RunView(
        cell={"name": "no-such-cell"}, config={}, traffic={}, facts=facts,
        memory={}, device_kind="TPU v5 lite", rows=rows, busy=busy)


def ring_of(rows, phase_args=True):
    """The span ring the program would hold after the sample's run: a
    step of the warm-up, then a serve step and a decode span a step on
    the ring's clock and the first step's prefill, the spans' args read
    off the annotations as the engine stamps them."""
    main = [r for r in rows if r["line"] == "python"]
    warm_up = {"name": ps.SERVE_STEP, "start_us": RING_AHEAD_US - 1e5,
               "dur_us": 1e3, "args": {}}
    spans = [warm_up] + [{"name": ps.SERVE_STEP,
              "start_us": r["start_ns"] / 1e3 + RING_AHEAD_US,
              "dur_us": r["dur_ns"] / 1e3, "args": {}}
             for r in xplane.spans_of(main, ps.SERVE_STEP)]
    (prefill,) = xplane.spans_of(main, ps.ENGINE_PREFILL)
    (call,) = xplane.spans_of(main, phases.PREFILL_DISPATCH)
    (fence,) = xplane.spans_of(main, phases.PREFILL_FENCE)
    args = {"slot": 0, "bucket": 64, "prompt_len": 40, "prefix_hit": 0}
    if phase_args:
        args.update(dispatch_us=call["dur_ns"] / 1e3,
                    fence_us=fence["dur_ns"] / 1e3, stalled="fence")
    spans.append({"name": ps.ENGINE_PREFILL,
                  "start_us": prefill["start_ns"] / 1e3 + RING_AHEAD_US,
                  "dur_us": prefill["dur_ns"] / 1e3, "args": args})
    decodes = xplane.spans_of(main, ps.ENGINE_DECODE)
    dispatches = xplane.spans_of(main, phases.DECODE_DISPATCH)
    fences = xplane.spans_of(main, phases.DECODE_FENCE)
    for k, (d, call, fence) in enumerate(zip(decodes, dispatches, fences)):
        args = {"active": 5, "uploads": (1, 0, 0, 2, 0, 0)[k],
                "sampling": int(k == 1), "live_blocks": 40 + k}
        if k == 4:
            args["poked"] = True
        if phase_args:
            args.update(
                prepare_us=(call["start_ns"] - d["start_ns"]) / 1e3,
                dispatch_us=call["dur_ns"] / 1e3,
                fence_us=fence["dur_ns"] / 1e3)
            if k == 2:
                args["stalled"] = "fence"
        spans.append({"name": ps.ENGINE_DECODE,
                      "start_us": d["start_ns"] / 1e3 + RING_AHEAD_US,
                      "dur_us": d["dur_ns"] / 1e3, "args": args})
    return spans


@pytest.fixture
def on_the_sample(rows, monkeypatch):
    monkeypatch.setattr(phases, "rows", lambda view: rows)
    monkeypatch.setattr(ps, "ring", lambda: ring_of(rows))
    return view_of(rows, elapsed_s=0.389)


def read_all(view):
    out = {}
    for reader in READERS:
        out.update(reader.read(ALL, view))
    return out


# --- the arithmetic, on the sample -------------------------------------------

def test_each_decode_span_against_the_device(rows):
    steps = phases.steps(rows)
    assert len(steps) == 7     # six, and the other thread's
    mine = [s for s in steps if s["launch_ns"] is not None]
    assert [s["launch_ns"] / 1e3 for s in mine] == pytest.approx(
        [2000, 800, 1400, 1400, 3500, 4600])
    assert [s["readback_ns"] / 1e3 for s in mine] == pytest.approx(
        [1000, 1500, 184500, 1500, 1500, 1500])
    assert [s["busy_ns"] / 1e3 for s in mine] == pytest.approx(
        [21999, 24000, 22000, 25000, 23000, 22000])
    (other,) = [s for s in steps if s["launch_ns"] is None]
    assert other["readback_ns"] is None and other["busy_ns"] == 0


def test_the_idle_parts_add_up_to_the_idle_total(rows):
    busy = xplane.busy(rows)
    parts = phases.idle_by_phase(rows, busy["window_ns"])
    # Cut where the phases begin and end, the long gap between two
    # steps is a fence's end, bookkeeping, the loop and a dispatch.
    us = {k: v * 1e6 for k, v in parts.items()}
    assert us == {
        "hvd_tpu_decode_dispatch": pytest.approx(9400),
        "hvd_tpu_decode_fence": pytest.approx(190000),
        "hvd_tpu_prefill_dispatch": pytest.approx(1000),
        "hvd_tpu_prefill_fence": pytest.approx(2500),
        "rest_of_hvd_tpu_engine_decode": pytest.approx(10800),
        "rest_of_hvd_tpu_engine_prefill": pytest.approx(1800),
        "own_time_of_hvd_tpu_serve_step": pytest.approx(3200),
        "outside_program": pytest.approx(4600),
        "gaps_under_2us": pytest.approx(1)}
    assert busy["window_s"] == pytest.approx(0.386)
    assert busy["busy_s"] == pytest.approx(0.162699)
    assert sum(parts.values()) == pytest.approx(
        busy["window_s"] - busy["busy_s"], rel=1e-9)
    # The benchmark's own reduction agrees on the total.
    assert sum(s for _, s in xplane.idle_gaps(
        rows, (), busy["window_ns"])) == pytest.approx(sum(parts.values()))


def test_the_six_readers_on_the_sample(on_the_sample, capsys):
    got = read_all(on_the_sample)
    assert got == {
        # dispatch calls of 2000, 1300, 1500, 1500, 1500, 5400 us
        "decode_dispatch_ms.tpot": pytest.approx(1.5),
        # launches of 800, 1400, 1400, 2000, 3500, 4600 us
        "decode_launch_ms.tpot": pytest.approx(1.7),
        "decode_readback_ms.tpot": pytest.approx(1.5),
        "stalled_steps.tpot": 1,
        "step_uploads_share.tpot": pytest.approx(100 * 2 / 6),
        "idle_outside_program.tpot": pytest.approx(100 * 4600 / 386000)}
    said = capsys.readouterr().out
    # The stalled step lies in the trace: the device worked 22 ms of
    # its 208.9, so the host waited for a device that had finished.
    assert ('"stalled_steps": [{"phase": "fence", "us": 205700.0, '
            '"span_us": 208900.0, "uploads": 0, "active": 5, '
            '"device_busy_s": 0.022, "launch_ms": 1.4, '
            '"readback_ms": 184.5}]') in said
    assert ('"step_protocol": {"decode_steps": 6, "upload_steps": 2, '
            '"uploads": 3, "runtime_pokes": 1, "sampling_steps": 1, '
            '"live_blocks": 255}') in said
    # The prefill's fence held it up too: named, and not counted.
    assert ('"stalled_prefills": [{"phase": "fence", "us": 26000.0, '
            '"span_us": 30400.0, "bucket": 64, "prompt_len": 40}], '
            '"prefills": 1') in said
    assert '"idle_by_program_phase": {' in said
    assert '"sum_of_phases_p50": ' in said


def test_a_window_shorter_than_the_ring_reads_its_own_steps(on_the_sample):
    """The last 100 ms hold the decode spans that ended in them: steps
    3 to 6 (step 3 ended 90.5 ms before the close)."""
    view = view_of(on_the_sample.rows, elapsed_s=0.1)
    got = step_uploads_share.read(ALL, view)
    assert got == {"step_uploads_share.tpot": pytest.approx(25.0)}
    assert stalled_steps.read(ALL, view) == {"stalled_steps.tpot": 1}
    assert stalled_steps.read(ALL, view_of(
        on_the_sample.rows, elapsed_s=0.08)) == {"stalled_steps.tpot": 0}


def test_a_stalled_step_outside_the_trace_has_no_device_time(
        rows, monkeypatch, capsys):
    """An untraced run: the ring says which phase, and nothing is said
    of the device."""
    monkeypatch.setattr(ps, "ring", lambda: ring_of(rows))
    got = stalled_steps.read(ALL, view_of(None, elapsed_s=0.389))
    assert got == {"stalled_steps.tpot": 1}
    assert ('"device_busy_s": null, "launch_ms": null, '
            '"readback_ms": null') in capsys.readouterr().out


# --- a program without the phases: the parent of PR 40 -----------------------

def test_the_parent_reads_as_nothing_where_it_has_nothing(
        rows, monkeypatch, capsys):
    old = [r for r in rows if r["name"] not in (
        phases.DECODE_DISPATCH, phases.DECODE_FENCE,
        phases.PREFILL_DISPATCH, phases.PREFILL_FENCE)]
    monkeypatch.setattr(phases, "rows", lambda view: old)
    monkeypatch.setattr(ps, "ring", lambda: ring_of(rows, phase_args=False))
    got = read_all(view_of(old, elapsed_s=0.389))
    # What it has it had before: ``args.uploads`` and the step spans.
    assert set(got) == {"step_uploads_share.tpot",
                        "idle_outside_program.tpot"}
    assert got["idle_outside_program.tpot"] == pytest.approx(
        100 * 4600 / 386000)
    assert "not read" not in capsys.readouterr().out
    # And with no ring and no trace at all, nothing and no error.
    monkeypatch.setattr(ps, "ring", lambda: [])
    assert read_all(view_of(None, elapsed_s=0.389)) == {}
    assert read_all(view_of(None)) == {}


def test_a_ring_that_lost_the_windows_opening_is_not_read(on_the_sample,
                                                          capsys):
    view = view_of(on_the_sample.rows, elapsed_s=10.0)
    got = read_all(view)
    assert set(got) == {"decode_launch_ms.tpot", "decode_readback_ms.tpot",
                        "idle_outside_program.tpot"}
    # Said once for the three readers of the ring.
    assert capsys.readouterr().out.count("no longer holds") == 1


def test_a_reader_that_fails_reports_nothing_and_says_so(monkeypatch, capsys):
    def broken(*a):
        raise RuntimeError("boom")

    monkeypatch.setattr(phases, "rows", broken)
    monkeypatch.setattr(phases, "window_decodes", broken)
    device = [{"plane": "/device:TPU:0", "line": "XLA Ops", "name": "%x",
               "start_ns": 0.0, "dur_ns": 1.0}]
    assert read_all(view_of(device, elapsed_s=1.0)) == {}
    assert capsys.readouterr().out.count("not read") == 6


# --- a CPU rehearsal ---------------------------------------------------------

def _rehearsed(line, bench, cell_name):
    from hvdbench import run

    per_layer = set(run.metric_names(bench, cell_name, "per_layer"))
    assert ALL <= per_layer
    got = set(line["metrics"])
    # The ring is read on the CPU too; a CPU trace has no device plane.
    assert {"decode_dispatch_ms.tpot", "stalled_steps.tpot",
            "step_uploads_share.tpot"} <= got
    assert not {"decode_launch_ms.tpot", "decode_readback_ms.tpot",
                "idle_outside_program.tpot"} & got
    assert line["metrics"]["decode_dispatch_ms.tpot"]["value"] > 0
    assert line["metrics"]["decode_dispatch_ms.tpot"]["unit"] == "ms"
    assert 0 <= line["metrics"]["step_uploads_share.tpot"]["value"] <= 100


def test_the_chat_rehearsal_reads_the_ring_and_leaves_the_trace_out(capsys):
    from hvdbench.tests import test_rehearsal as reh

    line = reh.rehearse(reh.CELLS["serve-open"], trace=True)
    _rehearsed(line, reh.tiny.bench(), reh.CELLS["serve-open"])
    said = capsys.readouterr().out
    assert '"decode_phases_ms": {' in said and '"step_protocol": {' in said
    assert "not read" not in said


def test_the_state_rehearsal_reads_the_ring_and_leaves_the_trace_out(capsys):
    from hvdbench.tests import test_rehearsal_state as reh

    line = reh.rehearse(trace=True)
    _rehearsed(line, reh.tiny()[0], reh.CELL)
    assert "not read" not in capsys.readouterr().out
