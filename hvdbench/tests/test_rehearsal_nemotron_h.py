"""A CPU rehearsal of the ``train`` kind for the ``nemotron_h`` family
at a tiny size: the whole of a run but the look for a chip, refused as
a measurement; the control (the reference in fp8, in the program's
place) comes out as not correct where the bfloat16 one passes; so does
a step that leaves an expert's gradient out; and the shape formulas of
``flops_nemotron_h.py`` against a count by hand.

Run by hand on the CPU (about a minute):

    JAX_PLATFORMS=cpu python -m pytest hvdbench/tests/test_rehearsal_nemotron_h.py -q
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

from hvdbench import check, flops_nemotron_h, generator, run  # noqa: E402
from hvdbench.reference import nemotron_h as ref  # noqa: E402

CELL = "nemotron3nano-train-1chip"


def tiny_config() -> dict:
    """The configuration's file at sizes a test run can hold: the
    pattern, the letters' meaning, the held range inside a wider router
    and the recomputed layers as they are."""
    with open(os.path.join(ROOT, "hvdbench", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg.update(vocab_size=211, hidden_size=32, num_attention_heads=4,
               num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
               mamba_head_dim=8, n_groups=2, ssm_state_size=16,
               chunk_size=16, n_routed_experts=4, num_experts_per_tok=3,
               moe_intermediate_size=24,
               moe_shared_expert_intermediate_size=48)
    cfg["run"].update(attention="full", router_outputs=16,
                      experts_held={"offset": 4, "count": 4},
                      rows_per_chip=2)
    # Limits of this size, read here on the CPU as PERF.md section 2
    # says the real ones were read on the chip, over three seeds: the
    # program and the bfloat16 reference reach 0.013 (gradient) and
    # 0.022 (change; a state left unchanged reads 1), the fp8 control
    # 0.05 to 0.10 in the gradient.
    cfg["check"]["limits"] = {"loss_gap": 5e-4, "grad_norm_gap": 0.025,
                              "delta_norm_gap": 0.05, "loss_fall": 0.0}
    return cfg


def tiny_traffic() -> dict:
    with open(os.path.join(ROOT, "hvdbench", "traffic",
                           "lm-1x8192.json")) as f:
        t = json.load(f)
    t.update(seq_len=64, ring=4, rows=2)
    return t


def rehearse(*, trace=False, seconds=1.5, seed=2**31 + 11):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    line = run.run_cell(bench, cell, tiny_config(), tiny_traffic(), seed=seed,
                        seconds=seconds, trace=trace, rehearsal=True,
                        t_start=time.monotonic())
    return bench, line


def test_rehearsal_ends_in_a_well_formed_line_that_is_no_measurement():
    bench, line = rehearse()
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = set(run.metric_names(bench, CELL, "end_to_end"))
    assert set(line["metrics"]) == want == {"train_tokens_per_s", "setup_s"}
    json.dumps(line)
    with pytest.raises(RuntimeError, match="rehearsal"):
        run.refuse_rehearsal(line)


def test_the_whole_steps_share_of_the_peak_is_read_from_the_facts():
    """On the CPU no train cell can be traced (no peak is published for
    it), so the reader is handed a finished run's facts."""
    from hvdbench import layers
    from hvdbench.layer_metrics import mfu_nemotron_h

    cfg = tiny_config()
    view = layers.RunView(
        cell={"name": CELL}, config=cfg, traffic=tiny_traffic(),
        facts={"steps": 10, "elapsed_s": 2.0, "tokens_per_step": 128,
               "chips": 1, "seq_len": 64}, memory={},
        device_kind="TPU v5 lite", rows=None, busy=None)
    need = flops_nemotron_h.train_flops_per_token(ref.sizes(cfg), 64)
    got = mfu_nemotron_h.read({"mfu_nemotron_h.train", "mfu.train"}, view)
    assert got == {"mfu_nemotron_h.train":
                   pytest.approx(100 * 640 * need / 197e12)}
    assert mfu_nemotron_h.read({"mfu.train"}, view) == {}


def test_training_control_in_fp8_is_not_correct():
    cfg, traffic = tiny_config(), tiny_traffic()
    s = ref.sizes(cfg)
    opt = {k: v for k, v in cfg["run"]["optimizer"].items() if k != "name"}
    batches = [generator.train_batch(traffic, 5, i, 2, cfg["vocab_size"])
               for i in range(3)]
    want = ref.train_readings(5, s, batches, opt, rows_per_block=1)
    verdicts = {}
    for precision in ("bf16", cfg["run"]["control_precision"]):
        got = ref.train_readings(5, s, batches, opt, rows_per_block=1,
                                 precision=precision)
        got["last_loss"] = got["losses"][0] - 1.0
        checks = check.train_checks(got, want, cfg["check"]["limits"])
        verdicts[precision] = all(e["ok"] for e in checks)
    assert verdicts == {"bf16": True, "fp8": False}


def test_a_step_that_leaves_an_experts_gradient_out_is_not_correct(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    real = hvd.make_train_step

    def broken(loss_fn, tx, **kw):
        def one_expert_frozen(params, batch):
            up = params["block_1"]["experts"]["up"]
            frozen = jnp.concatenate(
                [jax.lax.stop_gradient(up[:1]), up[1:]])
            params = dict(params, block_1=dict(
                params["block_1"], experts=dict(
                    params["block_1"]["experts"], up=frozen)))
            return loss_fn(params, batch)

        return real(one_expert_frozen, tx, **kw)

    monkeypatch.setattr(hvd, "make_train_step", broken)
    _, line = rehearse()
    assert line["correct"] is False
    assert line["compared"]["grad_norm_gap"]["value"] > \
        line["compared"]["grad_norm_gap"]["limit"]


def test_shape_formulas_against_a_hand_count():
    s = ref.sizes(tiny_config())
    # d 32; scan: inner 32, B and C 2 * 2 * 16 = 64, 4 heads: in_proj
    # 32 x (64 + 64 + 4) + out_proj 32 x 32 = 5,248 a layer, 4 layers.
    met = flops_nemotron_h.matmul_params_met(s)
    assert met["scan_projections"] == 4 * 5248
    # attention: qkv 32 x (4 + 2 * 2) * 8 + out 32 x 32 = 3,072.
    assert met["attention_projections"] == 3072
    # experts, 4 layers: router 32 x 16; shared 2 x 32 x 48; routed
    # 3 of 16 experts a token of which 4 are here: 3 * 4 / 16 = 0.75
    # experts of 2 x 32 x 24 met.
    assert met["router"] == 4 * 512 and met["shared_expert"] == 4 * 3072
    assert met["routed_experts"] == 4 * 0.75 * 1536
    assert met["head"] == 32 * 211
    # The scan's own forward, a token and layer, chunk 16: 17 x (2 * 16
    # + 32) + 4 * 32 * 16 + 2 * 32 * 16 / 16 = 1,088 + 2,048 + 64.
    assert flops_nemotron_h.scan_flops_per_token(s) == 3200
    assert flops_nemotron_h.conv_flops_per_token(s) == 2 * 4 * 96
    total = flops_nemotron_h.train_flops_per_token(s, 64)
    assert total == (6 * sum(met.values()) + 6 * 64 * 32
                     + 3 * 4 * (3200 + 768))
    # One step of 128 tokens: bytes of x, B, C (2 B), dt (4 B), y and
    # 128 / 16 states of 32 x 16 float32 written and read, x 3, x 4.
    cost = flops_nemotron_h.scan_cost(s, 128)
    assert cost["flops"] == 3 * 4 * 128 * 3200
    assert cost["bytes"] == 3 * 4 * (128 * ((64 + 64) * 2 + 16)
                                     + 2 * 8 * 512 * 4)
    # 10 and 30 pairs in two layers: 6 x 40 x 2 x 32 x 24 operations.
    cost = flops_nemotron_h.experts_cost(s, [10, 30])
    assert cost["flops"] == 6 * 40 * 2 * 32 * 24
    assert cost["bytes"] == 3 * (2 * 4 * 2 * 32 * 24 + 40 * (64 + 48)) * 2
