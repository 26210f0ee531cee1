"""A CPU rehearsal of the traffic kind ``serve-open-blocks`` at a tiny
size — the cell ``sdar30b-serve-reason`` with three layers of width 32
and every ratio of the published shapes kept
(``hvdbench/tests/tiny_sdar.py``) — ending in a well-formed result that
is marked as a rehearsal; then the tests that ``correct`` owes: the
reference in fp8 is not correct where the one in bfloat16 is, and
neither is a program that skips the commit pass, nor one that unmasks
in position order instead of by confidence.

    JAX_PLATFORMS=cpu python -m pytest hvdbench/tests/test_rehearsal_sdar.py -q
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

from hvdbench import check, flops_sdar, run  # noqa: E402
from hvdbench.reference import sdar as ref  # noqa: E402
from hvdbench.tests import tiny_sdar  # noqa: E402

CELL = "sdar30b-serve-reason"
SEED = 2**31 + 13
# Read here on the CPU, on three requests of 12 to 40 served tokens in
# float32: a sound program reads 0 to 2e-6 on both gaps; the bfloat16
# reference 0.0002 to 0.0009 on the logits; the fp8 control 0.003 to
# 0.012.  With random weights the masked positions of a block all put
# the same token first (they embed the same mask), so a fault that moves
# the logits without changing which token leads shows in the order of
# the confidences, which lie close: a program that skips the commit pass
# reads 0.0013 there, one that unmasks in position order 0.0005 to 0.004.
LIMITS = {"served_logit_gap": 0.0015, "served_order_gap": 0.0002}


def tiny():
    bench, cell, _, traffic = run.load_cell(CELL)
    config, traffic = tiny_sdar.config(), copy.deepcopy(traffic)
    config["check"].update(limits=dict(LIMITS))
    traffic["prompt_len"].update(median=20, max=60, min=6)
    traffic["output_len"].update(median=24, max=40, min=12)
    traffic.update(rate_per_s=4.0, preroll_s=0.5, trace_seconds=1)
    return bench, cell, config, traffic


def rehearse(*, trace=False, seconds=12.0, seed=SEED, control=()):
    bench, cell, cfg, traffic = tiny()
    # Short answers at a low rate, so that some finish inside the window
    # on a busy machine too (a block step here takes 0.1 to 0.5 s: the
    # grouped products run under the interpreter).
    traffic["output_len"].update(median=6, max=8, min=4)
    traffic.update(rate_per_s=1.5, preroll_s=2.0)
    # The tiny program in float32, so that the limits above hold it.
    cfg["run"].update(activation_dtype="float32", param_dtype="float32")
    return run.run_cell(bench, cell, cfg, traffic, seed=seed,
                        seconds=seconds, trace=trace, rehearsal=True,
                        t_start=time.monotonic(),
                        control_precisions=control)


def test_rehearsal_ends_in_a_well_formed_line_that_is_no_measurement():
    bench = tiny()[0]
    line = rehearse()
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"served_logit_gap", "served_order_gap"}
    assert line["attempted"] > 0 and line["failed"] == 0
    want = set(run.metric_names(bench, CELL, "end_to_end"))
    assert want == {"tpot_p50_ms", "itl_p95_ms", "setup_s"}
    assert set(line["metrics"]) == want
    json.dumps(line)
    with pytest.raises(RuntimeError, match="rehearsal"):
        run.refuse_rehearsal(line)


def test_traced_rehearsal_reports_the_block_steps_counters(capsys):
    bench = tiny()[0]
    line = rehearse(trace=True)
    per_layer = set(run.metric_names(bench, CELL, "per_layer"))
    new = {"tokens_a_step.tpot", "forwards_a_block.tpot",
           "block_attention_ms.tpot", "block_decode_roofline.tpot",
           "block_transfer_ms.tpot", "moe_experts_roofline_sdar.tpot",
           "kv_gb.tpot", "mfu_sdar.tpot", "moe_route_ms.tpot",
           "moe_experts_ms.tpot"}
    assert new <= per_layer
    assert set(line["metrics"]) <= per_layer
    # A CPU trace has no device plane: the readers of the scopes find
    # nothing and say nothing; the counters are read all the same.
    got = {k: m["value"] for k, m in line["metrics"].items()}
    assert {"window_compilations.tpot", "slot_occupancy.tpot",
            "tokens_a_step.tpot", "forwards_a_block.tpot",
            "kv_gb.tpot"} <= set(got)
    assert got["window_compilations.tpot"] == 0
    # Two denoising steps and the commit pass a block; blocks in flight
    # at the window's edges move the two a little off 3 and 4 / 3.
    assert 2.0 < got["forwards_a_block.tpot"] < 4.5
    assert 0.8 < got["tokens_a_step.tpot"] < 2.0
    assert got["kv_gb.tpot"] > 0
    said = [json.loads(x) for x in capsys.readouterr().out.split("\n")
            if x.startswith("{")]
    facts = [x["facts"] for x in said if "facts" in x][0]
    assert facts["requests_finished"] > 0
    steps = [x["kv_counters"] for x in said if "kv_counters" in x][0]
    assert 0 < steps["block_steps_at_open"] < steps["block_steps_at_close"]


def test_a_readers_counters_are_the_windows():
    from types import SimpleNamespace

    from hvdbench.layer_metrics import _sdar, block_steps

    kv = {"block_steps": 30, "denoise_forwards": 600, "commit_forwards": 300,
          "blocks_committed": 300, "tokens_final": 1200,
          "paged_live_positions_full": 90000, "paged_live_rows": 900,
          "experts_touched": 2000, "expert_pairs_held": 30000,
          "kv_full_block_steps": 600, "bytes_per_block": 1000}
    at_open = {k: v // 3 for k, v in kv.items() if k != "bytes_per_block"}
    view = SimpleNamespace(facts={"kv": kv, "kv_at_open": at_open})
    per = _sdar.grown(view)
    assert per["block_steps"] == 20 and per["tokens_final"] == 800
    got = block_steps.read({"tokens_a_step.tpot", "forwards_a_block.tpot",
                            "kv_gb.tpot"}, view)
    assert got["tokens_a_step.tpot"] == pytest.approx(800 / 600)
    assert got["forwards_a_block.tpot"] == pytest.approx(3.0)
    assert got["kv_gb.tpot"] == pytest.approx(400 / 20 * 1000 / 1e9)
    # An engine that counts no block step (another model, the parent):
    # nothing is read and nothing raised.
    other = SimpleNamespace(facts={"kv": {"paged_decode_steps": 5}})
    assert _sdar.grown(other) is None
    assert block_steps.read({"tokens_a_step.tpot"}, other) == {}
    assert block_steps.read({"decode_step_ms.tpot"}, view) == {}
    # Only a decode program's operations count under a scope.
    name = "jit(_decode_block_impl)/GPT/block_1/%s/x"
    assert _sdar.DECODE.findall(name % "attn/hvd_tpu_paged_attention") == [
        "hvd_tpu_paged_attention"]
    assert _sdar.DECODE.findall(
        "jit(_decode_block_impl)/hvd_tpu_block_transfer/GPT/"
        "hvd_tpu_block_transfer/lm_head") == ["hvd_tpu_block_transfer"]
    assert _sdar.DECODE.findall(
        "jit(prefill)/GPT/block_1/experts/hvd_tpu_moe_experts/x") == []


def test_the_kind_refuses_a_model_that_decodes_a_token_a_step():
    bench, cell, config, traffic = tiny()
    config["run"]["generation"]["block_length"] = 0
    with pytest.raises((RuntimeError, ZeroDivisionError, ValueError)):
        run.run_cell(bench, cell, config, traffic, seed=1, seconds=0.5,
                     trace=False, rehearsal=True, t_start=time.monotonic())


def test_traffic_over_the_stated_cap_is_refused():
    bench, cell, config, traffic = tiny()
    config["run"]["batcher"]["max_new_tokens"] = 30
    with pytest.raises(RuntimeError, match="cuts them at 30"):
        run.run_cell(bench, cell, config, traffic, seed=1, seconds=0.5,
                     trace=False, rehearsal=True, t_start=time.monotonic())


def _served(seed=SEED, spoil=None):
    """What a tiny float32 run served, as the check samples it:
    ``(prompt, tokens, their denoising steps)``.  ``spoil`` wraps the
    engine's transfer rule (a program with a fault in it)."""
    from horovod_tpu.serve import InferenceEngine, SamplingParams
    from horovod_tpu.serve import engine as engine_mod
    from hvdbench import generator
    from hvdbench.models import sdar as family

    _, _, config, traffic = tiny()
    config["run"].update(activation_dtype="float32", param_dtype="float32")
    model = family.build_model(config, "full")
    params = family.make_params(config, seed)
    sound = engine_mod._transfer
    if spoil is not None:
        engine_mod._transfer = spoil(sound)
    try:
        eng = InferenceEngine(model, params, max_slots=4,
                              prefill_buckets=(16, 64), kv_block=4)
        out = []
        for spec in generator.request_block(traffic, seed, 0, 211)[:3]:
            eng.start(0, list(spec.prompt), SamplingParams(
                max_new_tokens=spec.max_new_tokens, denoising_steps=2,
                transfer="static"))
            tokens, steps = [], []
            while len(tokens) < spec.max_new_tokens:
                got = eng.step()[0]
                tokens += list(got)
                steps += got.steps
            eng.release(0)
            n = spec.max_new_tokens
            out.append((list(spec.prompt), tokens[:n], steps[:n]))
    finally:
        engine_mod._transfer = sound
    return config, out


def _checks(config, sample, seed=SEED, **kw):
    import jax

    s = ref.sizes(config)
    params = jax.jit(lambda k: ref.init_params(k, s))(ref.seed_key(seed))
    found = ref.served_token_gaps(
        params, sample, s, pad_to=config["check"]["pad_to"],
        denoising_steps=2, **kw)
    prefix = "control_" if kw.get("control_precision") else ""
    return {name: check._entry(name, max(found[prefix + key]), LIMITS[name])
            for name, key in (("served_logit_gap", "logit_gaps"),
                              ("served_order_gap", "order_gaps"))}


def test_the_fp8_control_is_not_correct_where_bfloat16_is():
    config, sample = _served()
    sound = _checks(config, sample)
    assert all(e["ok"] for e in sound.values()), sound
    bf16 = _checks(config, sample, control_precision="bf16")
    assert bf16["served_logit_gap"]["ok"], bf16
    fp8 = _checks(config, sample, control_precision="fp8")
    assert not all(e["ok"] for e in fp8.values()), fp8


def test_a_program_that_skips_the_commit_pass_is_not_correct():
    """The cache keeps the K/V of a block's last denoising step, masks
    among its inputs: the next block's logits are no longer the
    reference's."""
    import jax.numpy as jnp

    def skipping(transfer):
        def spoiled(step, logits, mask_token):
            out = transfer(step, logits, mask_token)
            final = out["report"][:, -1] > 0
            return dict(
                out, positions=out["positions"] + 4 * final,
                masked=out["masked"] | final[:, None],
                steps=jnp.where(final, 0, out["steps"]),
                tokens=jnp.where(final[:, None], mask_token, out["tokens"]),
                report=out["report"].at[:, -2].add(4 * final))
        return spoiled

    config, sample = _served(spoil=skipping)
    got = _checks(config, sample)
    assert not all(e["ok"] for e in got.values()), got


def test_a_program_that_unmasks_in_position_order_is_not_correct():
    """Which positions a step unmasks is part of the arithmetic: the
    first masked ones in place of the most confident."""
    import jax.numpy as jnp

    def in_order(transfer):
        def spoiled(step, logits, mask_token):
            # Confidence falls with the position: the logits of every
            # later position are flattened a little more.
            slope = 1.0 - 0.2 * jnp.arange(logits.shape[1])[None, :, None]
            return transfer(step, logits * slope, mask_token)
        return spoiled

    config, sample = _served(spoil=in_order)
    got = _checks(config, sample)
    assert not got["served_order_gap"]["ok"], got


def test_the_shape_formulas_against_a_hand_count():
    s = ref.sizes(tiny_sdar.config())
    # One position of a layer: 2 KV heads x 2 x 16 x 2 bytes.
    assert flops_sdar.kv_row_bytes(s) == 128
    cost = flops_sdar.block_attention_cost(s, 100, 2)
    assert cost["bytes"] == 3 * (100 * 128 + 2 * 4 * 4 * 32 * 2)
    assert cost["flops"] == 3 * 100 * 4 * 2 * 4 * 32
    assert flops_sdar.expert_bytes(s) == 3 * 32 * 16 * 2
    e = flops_sdar.block_experts_cost(s, 5, 7)
    assert e == {"flops": 7 * 6 * 32 * 16, "bytes": 5 * 3072 + 7 * 128}
    layer = (2 * 32 * (64 + 64) + 2 * 64 * 32 + 2 * 32 * 16
             + 4 * 6 * 32 * 16)
    assert flops_sdar.forward_flops_per_position(s, 0) == pytest.approx(
        3 * layer + 2 * 32 * 211)
    assert flops_sdar.serve_flops_per_token(s, 10, 3.0) == pytest.approx(
        3 * (3 * (layer + 2 * 4 * 32 * 10) + 2 * 32 * 211))
