"""A CPU rehearsal of the traffic kind ``serve-open-mixed`` at a tiny
size — the cell ``mimov2flash-serve-reason`` with four layers of width
32 and every ratio of the published shapes kept
(``hvdbench/tests/tiny_mimo_v2.py``) — ending in a well-formed result
that is marked as a rehearsal; then the tests that ``correct`` owes: the
reference in fp8 is not correct where the one in bfloat16 is, and a
model that treats a window layer as a full one is not correct.

    JAX_PLATFORMS=cpu python -m pytest hvdbench/tests/test_rehearsal_mimo_v2.py -q
"""

import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hvdbench import check, flops_mimo_v2, run  # noqa: E402
from hvdbench.reference import mimo_v2 as ref  # noqa: E402
from hvdbench.tests import tiny_mimo_v2  # noqa: E402

CELL = "mimov2flash-serve-reason"
SEED = 2**31 + 11
# Read here on the CPU as PERF.md section 2 says the real limit was read
# on the chip, on three seeds of 56 to 81 served tokens: sound runs
# reach 0.0006 (the bfloat16 reference 0.0003), the fp8 control's widest
# gap is 0.0042 to 0.0079, a reference without the sinks 0.0027 to
# 0.0038, one whose window layers see everything 0.019 to 0.021.
LIMIT = 0.0015


def tiny():
    bench, cell, _, traffic = run.load_cell(CELL)
    config, traffic = tiny_mimo_v2.config(), copy.deepcopy(traffic)
    config["check"].update(limits={"served_logit_gap": LIMIT})
    traffic["prompt_len"].update(median=20, max=60, min=6)
    traffic["output_len"].update(median=24, max=40, min=12)
    traffic.update(rate_per_s=4.0, preroll_s=0.5, trace_seconds=1)
    return bench, cell, config, traffic


def rehearse(*, trace=False, seconds=4.0, seed=SEED, config=None,
             control=()):
    bench, cell, cfg, traffic = tiny()
    # Short answers, so that some finish inside the window on a busy
    # machine too (a step here takes 0.05 to 0.6 s by the hour).
    traffic["output_len"].update(median=6, max=10, min=4)
    return run.run_cell(bench, cell, config or cfg, traffic, seed=seed,
                        seconds=seconds, trace=trace, rehearsal=True,
                        t_start=time.monotonic(),
                        control_precisions=control)


def test_rehearsal_ends_in_a_well_formed_line_that_is_no_measurement():
    bench = tiny()[0]
    line = rehearse()
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = set(run.metric_names(bench, CELL, "end_to_end"))
    assert want == {"tpot_p50_ms", "itl_p95_ms", "setup_s"}
    assert set(line["metrics"]) == want
    json.dumps(line)
    with pytest.raises(RuntimeError, match="rehearsal"):
        run.refuse_rehearsal(line)


def test_traced_rehearsal_reports_the_counters_of_both_kinds(capsys):
    bench = tiny()[0]
    line = rehearse(trace=True)
    per_layer = set(run.metric_names(bench, CELL, "per_layer"))
    new = {"window_attention_ms.tpot", "full_attention_ms.tpot",
           "paged_decode_roofline.tpot", "moe_route_ms.tpot",
           "moe_experts_ms.tpot", "moe_experts_roofline.tpot",
           "kv_window_gb.tpot", "kv_full_gb.tpot", "mfu_mimo_v2.tpot"}
    assert new <= per_layer
    assert set(line["metrics"]) <= per_layer
    # A CPU trace has no device plane: the readers of the scopes find
    # nothing and say nothing; the counters are read all the same.
    got = {k: m["value"] for k, m in line["metrics"].items()}
    assert {"window_compilations.tpot", "slot_occupancy.tpot",
            "kv_window_gb.tpot", "kv_full_gb.tpot"} <= set(got)
    assert got["window_compilations.tpot"] == 0
    # Window layers: at most a ring of 3 blocks of 4 a slot, 4 slots,
    # two layers of 2 KV heads x (24 + 16) x 4 bytes x 4 positions.
    assert 0 < got["kv_window_gb.tpot"] <= 4 * 3 * 2 * 2 * 40 * 4 * 4 / 1e9
    assert got["kv_full_gb.tpot"] > 0
    said = [json.loads(x) for x in capsys.readouterr().out.split("\n")
            if x.startswith("{")]
    facts = [x["facts"] for x in said if "facts" in x][0]
    assert facts["requests_finished"] > 0
    # The counters are read when the window opens and at its close, and
    # a reader takes what grew between: the pre-roll is in none.
    steps = [x["kv_counters"] for x in said if "kv_counters" in x][0]
    assert 0 < steps["decode_steps_at_open"] < steps["decode_steps_at_close"]


def test_a_readers_step_is_a_step_of_the_window():
    from types import SimpleNamespace

    from hvdbench.layer_metrics import _mimo_v2

    kv = {"paged_decode_steps": 30, "paged_live_positions_full": 3000,
          "paged_live_positions_window": 240, "paged_live_rows": 90,
          "experts_touched": 70, "expert_pairs_held": 100,
          "kv_full_block_steps": 600, "kv_window_block_steps": 90,
          "bytes_per_block": 10, "kv_window_bytes_per_block": 40}
    at_open = dict(kv, paged_decode_steps=10, paged_live_positions_full=600,
                   expert_pairs_held=20, kv_full_block_steps=100,
                   kv_window_block_steps=30)
    per = _mimo_v2.counters_a_step(SimpleNamespace(
        facts={"kv": kv, "kv_at_open": at_open}))
    assert per["steps"] == 20 and per["positions_full"] == 120
    assert per["expert_pairs"] == 4 and per["experts_touched"] == 0
    assert per["bytes_full"] == 250 and per["bytes_window"] == 120
    # Without the first reading (another harness) it is the whole run's.
    assert _mimo_v2.counters_a_step(SimpleNamespace(
        facts={"kv": kv}))["positions_full"] == 100
    # Only a decode program's operations count under a scope.
    name = "jit(_decode_paged_impl)/GPT/block_1/experts/%s/jit(gmm)/while"
    assert _mimo_v2.DECODE.findall(name % "hvd_tpu_moe_experts") == [
        "hvd_tpu_moe_experts"]
    assert _mimo_v2.DECODE.findall(
        "jit(prefill)/GPT/block_1/experts/hvd_tpu_moe_experts/x") == []
    assert _mimo_v2.DECODE.findall(
        "jit(_decode_paged_impl)/GPT/block_1/attn/"
        "hvd_tpu_paged_attention_window/pallas_call") == [
            "hvd_tpu_paged_attention_window"]


def test_the_kind_refuses_an_engine_without_a_ring():
    bench, cell, config, traffic = tiny()
    config["hybrid_layer_pattern"] = [0, 0, 0, 0]
    # What the engine allots a model that may share prefixes.
    config["run"]["engine"]["kv_blocks"] = 257
    with pytest.raises(RuntimeError, match="holds no ring"):
        run.run_cell(bench, cell, config, traffic, seed=1, seconds=0.5,
                     trace=False, rehearsal=True, t_start=time.monotonic())


def test_traffic_over_the_stated_cap_is_refused():
    bench, cell, config, traffic = tiny()
    config["run"]["batcher"]["max_new_tokens"] = 30
    with pytest.raises(RuntimeError, match="cuts them at 30"):
        run.run_cell(bench, cell, config, traffic, seed=1, seconds=0.5,
                     trace=False, rehearsal=True, t_start=time.monotonic())


def _served(seed=SEED):
    """What a sound tiny run served, as the check samples it."""
    from horovod_tpu.serve import InferenceEngine, SamplingParams
    from hvdbench import generator
    from hvdbench.models import mimo_v2 as family

    _, _, config, traffic = tiny()
    model = family.build_model(config, "full")
    params = family.make_params(config, seed)
    eng = InferenceEngine(model, params, max_slots=4,
                          prefill_buckets=(16, 64), kv_block=4)
    out = []
    for spec in generator.request_block(traffic, seed, 0, 211)[:3]:
        tokens = [eng.start(0, list(spec.prompt), SamplingParams(
            max_new_tokens=spec.max_new_tokens))]
        while len(tokens) < spec.max_new_tokens:
            tokens += eng.step()[0]
        eng.release(0)
        out.append((list(spec.prompt), tokens))
    return config, out


def _gaps(config, sample, seed=SEED, **kw):
    import jax

    s = ref.sizes(config)
    params = jax.jit(lambda k: ref.init_params(k, s))(ref.seed_key(seed))
    return ref.served_token_gaps(params, sample, s,
                                 pad_to=config["check"]["pad_to"], **kw)


def test_the_fp8_control_is_not_correct_where_bfloat16_is():
    config, sample = _served()
    limits = {"served_logit_gap": LIMIT}
    gaps, low = _gaps(config, sample, control_precision="bf16")
    assert check.serve_checks(gaps, 3, limits)[0]["ok"]
    assert check.serve_checks(low, 3, limits)[0]["ok"]
    _, low = _gaps(config, sample, control_precision="fp8")
    assert not check.serve_checks(low, 3, limits)[0]["ok"]


@pytest.mark.parametrize("what, change", [
    ("window", {"sliding_window": 64}),
    ("sink", {"add_swa_attention_sink_bias": False})])
def test_a_dropped_window_or_sink_is_not_correct(what, change):
    """The reference told that window layers see everything the tiny
    requests hold, or have no sink: what the program served is no
    longer what it puts first."""
    config, sample = _served()
    limits = {"served_logit_gap": LIMIT}
    assert check.serve_checks(_gaps(config, sample)[0], 3, limits)[0]["ok"]
    wrong = dict(copy.deepcopy(config), **change)
    assert not check.serve_checks(_gaps(wrong, sample)[0], 3,
                                  limits)[0]["ok"], what


def test_the_shape_formulas_against_a_hand_count():
    s = ref.sizes(tiny_mimo_v2.config())
    # One position of a window layer: 2 KV heads x (24 + 16) x 2 bytes.
    assert flops_mimo_v2.kv_row_bytes(s, "window") == 160
    assert flops_mimo_v2.kv_row_bytes(s, "full") == 80
    cost = flops_mimo_v2.decode_attention_cost(s, 100, 16, 2)
    assert cost["bytes"] == 2 * (100 * 80 + 2 * 4 * 40 * 2) \
        + 2 * (16 * 160 + 2 * 4 * 40 * 2)
    assert flops_mimo_v2.expert_bytes(s) == 3 * 32 * 16 * 2
    e = flops_mimo_v2.decode_experts_cost(s, 5, 7)
    assert e == {"flops": 7 * 6 * 32 * 16, "bytes": 5 * 3072 + 7 * 128}
    per_layer_attn = 2 * 32 * (4 * 24 + 24 + 16) + 2 * 4 * 16 * 32
    assert flops_mimo_v2.serve_flops_per_token(s, 0) == pytest.approx(
        2 * 32 * 211 + 2 * per_layer_attn
        + 2 * (2 * 32 * (4 * 24 + 2 * 40) + 2 * 4 * 16 * 32)
        + 6 * 32 * 64 + 3 * (2 * 32 * 16 + (4 * 4 / 16) * 6 * 32 * 16))


def test_the_route_flip_tool_counts_pairs_at_a_tiny_size():
    """``tools/route_flips_serve.py`` on the tiny configuration: 64
    tokens x top 4 in each of three expert layers, and a bfloat16
    stream that flips few or none of them at this size."""
    from hvdbench.tools import route_flips_serve

    got = route_flips_serve.flips(tiny()[2], SEED)
    assert got["pairs_a_layer"] == 64 * 4
    assert len(got["differ"]) == len(got["differ_held"]) == 3
    assert all(0 <= d <= 26 for d in got["differ"])
