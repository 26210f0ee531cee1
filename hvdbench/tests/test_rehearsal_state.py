"""A CPU rehearsal of the traffic kind ``serve-open-state`` at a tiny
size — the cell ``brumby14b-serve-reason`` with two layers of width 64
— ending in a well-formed result that is marked as a rehearsal; then
the two tests that ``correct`` owes: the reference in fp8 is not
correct where the one in bfloat16 is, and a token altered where the
engine produces it is not correct.

    JAX_PLATFORMS=cpu python -m pytest hvdbench/tests/test_rehearsal_state.py -q
"""

import copy
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hvdbench import check, run  # noqa: E402
from hvdbench.reference import brumby as ref  # noqa: E402

CELL = "brumby14b-serve-reason"


def tiny():
    """The cell's own files at a size a test run holds.  The limit is
    read here on the CPU as PERF.md section 2 says the real one was
    read on the chip: sound runs reach 0.0005 (the bfloat16 reference;
    the program's served tokens were the reference's best on three
    seeds), the fp8 control's widest gap over 45 to 62 served positions
    is 0.012 to 0.027."""
    bench, cell, config, traffic = run.load_cell(CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(vocab_size=211, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  max_position_embeddings=4096)
    config["run"]["engine"].update(max_seq_len=4096)
    config["check"].update(pad_to=32, limits={"served_logit_gap": 0.004})
    traffic["prompt_len"].update(median=30, max=100, min=8)
    traffic["output_len"].update(median=8, max=12, min=4)
    traffic.update(rate_per_s=20.0, preroll_s=0.5, trace_seconds=1)
    return bench, cell, config, traffic


def rehearse(*, trace=False, seconds=1.5, seed=2**31 + 11):
    bench, cell, config, traffic = tiny()
    return run.run_cell(bench, cell, config, traffic, seed=seed,
                        seconds=seconds, trace=trace, rehearsal=True,
                        t_start=time.monotonic())


def test_rehearsal_ends_in_a_well_formed_line_that_is_no_measurement():
    bench = tiny()[0]
    line = rehearse()
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, "sound run, tiny limits"
    assert line["attempted"] > 0 and line["failed"] == 0
    want = set(run.metric_names(bench, CELL, "end_to_end"))
    assert want == {"tpot_p50_ms", "itl_p95_ms", "setup_s"}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    json.dumps(line)
    with pytest.raises(RuntimeError, match="rehearsal"):
        run.refuse_rehearsal(line)


def test_the_heap_is_frozen_when_the_window_opens(monkeypatch):
    """The kind hands ``serve_open.run`` its harness and shares the
    freeze between warm-up and the stream."""
    import gc

    from hvdbench.drivers import serve_open_state

    seen = []
    real = serve_open_state.StateHarness.submit

    def submit(self, spec, due):
        if not seen:    # once: the count walks the whole frozen list
            seen.append((type(self).__name__, gc.get_freeze_count()))
        return real(self, spec, due)

    monkeypatch.setattr(serve_open_state.StateHarness, "submit", submit)
    before = gc.get_freeze_count()      # what imports froze: a few hundred
    line = rehearse()
    assert line["correct"] is True
    assert seen and seen[0][0] == "StateHarness"
    assert seen[0][1] > before + 10_000     # at the first arrival
    assert gc.get_freeze_count() == before


def test_traced_rehearsal_reports_what_needs_no_device_trace():
    bench = tiny()[0]
    line = rehearse(trace=True)
    per_layer = set(run.metric_names(bench, CELL, "per_layer"))
    assert {"retention_decode_ms.tpot", "retention_decode_roofline.tpot",
            "retention_prefill_ms.itl", "state_gb.tpot"} <= per_layer
    assert set(line["metrics"]) <= per_layer
    # A CPU trace has no device plane: the readers of the scopes find
    # nothing and say nothing; the counters are read all the same.
    assert {"window_compilations.tpot", "slot_occupancy.tpot",
            "state_gb.tpot"} <= set(line["metrics"])
    assert line["metrics"]["window_compilations.tpot"]["value"] == 0
    d, K, L = 16, 2, 2
    assert line["metrics"]["state_gb.tpot"]["value"] == pytest.approx(
        8 * L * K * (d // 2 + 1) * d * (d + 1) * 4 / 1e9)


def test_the_kind_refuses_an_engine_that_holds_blocks():
    bench, cell, config, traffic = run.load_cell("gpt2xl-serve-chat-loaded")
    from hvdbench.tests import tiny as tiny_gpt2

    with pytest.raises(RuntimeError, match="drives a state cache"):
        run.run_cell(bench, cell, tiny_gpt2.config("gpt2-xl"),
                     dict(tiny_gpt2.traffic("chat-loaded"),
                          kind="serve-open-state"),
                     seed=3, seconds=1.0, trace=False, rehearsal=True,
                     t_start=time.monotonic())


def test_answers_longer_than_the_default_cap_are_served_whole(capsys):
    """The batcher a user gets with nothing set cuts an answer at 256
    tokens; the configuration states its deployment's cap and the
    harness sets it, so the traffic's lengths are the lengths served."""
    bench, cell, config, traffic = tiny()
    assert config["run"]["batcher"]["max_new_tokens"] == 768
    traffic["output_len"].update(median=300, min=290, max=310)
    traffic.update(rate_per_s=2.0, check_requests=2)
    line = run.run_cell(bench, cell, config, traffic, seed=5, seconds=3.0,
                        trace=False, rehearsal=True,
                        t_start=time.monotonic())
    assert line["correct"] is True and line["failed"] == 0
    checked = [json.loads(out) for out in capsys.readouterr().out.split("\n")
               if out.startswith('{"check"')][0]
    assert checked["requests"] == 2
    assert 2 * 290 <= checked["tokens"] <= 2 * 310


def test_the_kind_refuses_traffic_longer_than_the_stated_cap():
    bench, cell, config, traffic = tiny()
    traffic["output_len"].update(max=769)
    with pytest.raises(RuntimeError, match="cuts them at 768"):
        run.run_cell(bench, cell, config, traffic, seed=3, seconds=1.0,
                     trace=False, rehearsal=True, t_start=time.monotonic())


def test_the_roofline_counts_the_state_once_in_and_once_out():
    from hvdbench import flops_retention

    cost = flops_retention.decode_cost(8, 10, 40, 8, 128)
    state = 8 * 10 * 8 * 8256 * 129 * 4     # the packed triangle
    assert flops_retention.state_bytes(8, 10, 8, 128) == state
    assert 2 * state < cost["bytes"] < 2 * state * 1.001
    # Memory-bound by three orders of magnitude on a v5e.
    assert cost["bytes"] / 819e9 > 100 * cost["flops"] / 197e12
    assert (flops_retention.decode_cost(4, 10, 40, 8, 128)["bytes"]
            == cost["bytes"] / 2)


def test_serving_control_in_fp8_is_not_correct():
    config = tiny()[2]
    s = ref.sizes(config)
    key = ref.init_params(ref.seed_key(3), s)
    rng = np.random.default_rng(0)
    # Greedy tokens of the float32 reference itself: a sound program.
    seqs = []
    for n in (20, 37, 60):
        prompt = rng.integers(0, s["V"], n).tolist()
        served = []
        for _ in range(12):
            lg = ref.logits(key, np.asarray([prompt + served]), s)
            served.append(int(np.argmax(np.asarray(lg[0, -1]))))
        seqs.append((prompt, served))
    gaps, _ = ref.served_token_gaps(key, seqs, s, pad_to=32)
    long = [(rng.integers(0, s["V"], 30).tolist(),
             rng.integers(0, s["V"], 90).tolist()) for _ in range(4)]
    _, low = ref.served_token_gaps(
        key, long, s, pad_to=32,
        control_precision=config["run"]["control_precision"])
    _, same = ref.served_token_gaps(key, long, s, pad_to=32,
                                    control_precision="bf16")
    limits = config["check"]["limits"]
    assert check.serve_checks(gaps, 3, limits)[0]["ok"]
    assert check.serve_checks(same, 4, limits)[0]["ok"]
    assert not check.serve_checks(low, 4, limits)[0]["ok"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from horovod_tpu.serve import InferenceEngine

    real = InferenceEngine.step

    def altered(self):
        out = real(self)
        vocab = self._model.config.vocab_size
        return {slot: [(t + 1) % vocab for t in toks]
                for slot, toks in out.items()}

    monkeypatch.setattr(InferenceEngine, "step", altered)
    line = rehearse()
    assert line["correct"] is False
    assert line["attempted"] > 0
