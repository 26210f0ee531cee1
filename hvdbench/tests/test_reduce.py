"""The trace reduction, pinned on a small recorded trace: the first
24 ms of device operations of one traced run of ``gpt2m-train-1chip``
(TPU v5 lite, PR 23; ``tools/dump_trace.py --rows``, names cut short)
and that run's host spans.  Collectives and idle gaps, which one chip's
busy trace does not hold, are pinned on rows written by hand."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hvdbench import flops  # noqa: E402
from hvdbench.reduce import xplane  # noqa: E402

SAMPLE = os.path.join(ROOT, "hvdbench", "reduce", "sample_events.jsonl")


@pytest.fixture(scope="module")
def rows():
    return xplane.read_jsonl(SAMPLE)


def test_busy_and_window(rows):
    b = xplane.busy(rows)
    assert b["planes"] == 1
    assert b["window_s"] == pytest.approx(0.02570437, rel=1e-6)
    assert b["busy_s"] == pytest.approx(0.025702586, rel=1e-6)
    half = (b["window_ns"][0], b["window_ns"][0] + 10e6)
    assert xplane.busy(rows, half)["window_s"] == pytest.approx(0.010)
    assert xplane.busy(rows, half)["busy_s"] <= 0.010


def test_operations_by_label(rows):
    top = xplane.top_ops(rows, 3)
    assert [name for name, _ in top] == [
        "attn_bf16_128_1024_64_", "convert_reduce_fusion_f32_8_1024_",
        "fusion_bf16_8_1024_4096_"]
    assert top[0][1] == pytest.approx(0.016629495, rel=1e-6)


def test_the_flash_kernel_is_found_by_the_name_in_the_configuration(rows):
    with open(os.path.join(ROOT, "hvdbench", "configs",
                           "gpt2-medium.json")) as f:
        cfg = json.load(f)
    seconds, calls = xplane.time_of(
        rows, cfg["run"]["kernels"]["flash_fwd"]["match"])
    assert calls == 5
    assert seconds == pytest.approx(0.016629495, rel=1e-6)
    share = flops.roofline_share(flops.flash_fwd_cost(8, 16, 1024, 64),
                                 seconds / calls, "TPU v5 lite")
    assert share["bound"] == "compute"
    assert 2.0 < share["percent"] < 3.5


def test_device_time_under_a_host_span(rows):
    assert xplane.device_time_under(rows, "train_window") == [
        pytest.approx(0.025702586, rel=1e-6)]
    under = xplane.device_time_under(rows, "train_step_dispatch")
    assert len(under) == 6 and under[-1] == 0.0
    assert sum(under) == pytest.approx(0.025702586, rel=1e-6)
    assert xplane.device_time_under(rows, "no_such_span") == []


def _op(name, start, dur, line=xplane.OP_LINE, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_labels():
    assert xplane.op_label(_op(
        "%copy.12 = bf16[1025,16,25,64]{3,2,1,0:T(8,128)(2,1)} copy(%p)",
        0, 1)) == "copy_bf16_1025_16_25_64_"
    assert xplane.op_label(_op(
        "%fusion.3.clone = (f32[8]{0}, f32[8]{0}) fusion(%a)", 0, 1)) \
        == "fusion_f32_8_"
    assert xplane.op_label(_op("%while.2 = (s32[], f32[2]) while(%t)",
                               0, 1)) == "while_s32__"


def test_collectives_and_their_exposed_part():
    rows = [
        _op("%fusion.1 = f32[8]{0} fusion(f32[8] %a), kind=kLoop", 0, 100),
        _op("%all-reduce-start.1 = (f32[8], f32[8]) all-reduce-start(%g)",
            100, 10),
        # In flight from 100 to 400; a fusion hides 150 ns of it.
        _op("%all-reduce-start.1 = (f32[8], f32[8]) all-reduce-start(%g)",
            100, 300, line=xplane.ASYNC_LINE),
        _op("%fusion.2 = f32[8]{0} fusion(f32[8] %all-reduce-done.0)",
            150, 150),
        _op("%all-reduce-done.1 = f32[8]{0} all-reduce-done(%s)", 300, 100),
        _op("%ar.7 = f32[4]{0:T(4)} all-reduce(f32[4] %x), to_apply=%add",
            500, 50),
        _op("%copy-start.3 = (f32[4], f32[4], u32[]) copy-start(%y)",
            560, 200, line=xplane.ASYNC_LINE),
    ]
    c = xplane.collectives(rows)
    assert c["count"] == 4
    assert c["total_s"] == pytest.approx(350e-9)
    assert c["exposed_s"] == pytest.approx(200e-9)
    b = xplane.busy(rows)          # the async line is not busy time
    assert b["busy_s"] == pytest.approx(410e-9)
    assert b["window_s"] == pytest.approx(550e-9)


def test_idle_gaps_go_to_the_host_span_over_their_middle():
    rows = [
        _op("%a.1 = f32[1] fusion(%x)", 0, 1000),
        _op("%a.2 = f32[1] fusion(%x)", 1001, 999),        # 1 ns gap
        _op("%a.3 = f32[1] fusion(%x)", 12000, 1000),      # 10 us gap
        _op("%a.4 = f32[1] fusion(%x)", 20000, 1000),      # 7 us gap
        {"plane": xplane.HOST_PLANE, "line": "python3",
         "name": "engine_decode", "start_ns": 1500.0, "dur_ns": 10000.0},
        {"plane": xplane.HOST_PLANE, "line": "python3",
         "name": "engine_prefill", "start_ns": 30000.0, "dur_ns": 10.0},
    ]
    gaps = xplane.idle_gaps(rows, ["engine_prefill", "engine_decode"],
                            (0.0, 22000.0))
    assert gaps == [["engine_decode", pytest.approx(10e-6)],
                    ["no_span", pytest.approx(7e-6)],
                    ["gaps_under_2us", pytest.approx(1.001e-6)]]
    assert xplane.device_planes(rows) == ["/device:TPU:0"]


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError):
        xplane.busy([{"plane": xplane.HOST_PLANE, "line": "x", "name": "y",
                      "start_ns": 0.0, "dur_ns": 1.0}])
