"""The benchmark's own arithmetic: percentiles, the stratified
generator, the window, the shape formulas.  Run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest hvdbench/tests -q
"""

import collections
import json
import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hvdbench import flops, generator, stats, window  # noqa: E402
from hvdbench.window import StepRecord  # noqa: E402


def load(kind, name):
    with open(os.path.join(ROOT, "hvdbench", kind, name + ".json")) as f:
        return json.load(f)


# --- percentiles -------------------------------------------------------------

def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([3, 1, 2], 50) == 2
    # Forty samples: the 95th percentile is the third highest.
    assert stats.percentile(list(range(40)), 95) == 37


def test_percentile_refuses_an_empty_sample():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.median([])


def test_quartile_spread_is_pythons_quantiles():
    xs = [100.0, 101.0, 99.0, 100.5, 99.5, 100.2]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))


def test_strict_spread_leaves_out_the_run_farthest_from_the_median():
    from hvdbench.tools import spread

    xs = [100.0, 100.2, 99.9, 100.1, 100.3, 104.0]
    assert spread.strict_spread(xs) == pytest.approx(
        (100.3 - 99.9) / statistics.median(xs))
    assert spread.strict_spread(xs) >= stats.quartile_spread(xs[:5])
    # The rule of README step 5: mean spread / 0.4, up to the next
    # 0.005, never under 0.01.
    assert spread.bound_for(0.0010) == 0.01
    assert spread.bound_for(0.0040) == 0.01
    assert spread.bound_for(0.0041) == pytest.approx(0.015)
    assert spread.bound_for(0.0100) == pytest.approx(0.025)


def test_spread_reads_a_runs_earlier_lines_by_their_key():
    from hvdbench.tools import spread

    said = spread.earlier_lines(
        'noise\n{"facts": {"queue_at_close": 1}}\n{"host_pauses": {"gc": '
        '{"full_collections": 0}}}\n{"correct": true, "failed": 0}\n')
    assert said["facts"]["queue_at_close"] == 1
    assert said["host_pauses"]["gc"]["full_collections"] == 0
    assert "correct" not in said      # the result line is not one of them


def test_a_rate_holds_by_the_rule_the_traffic_files_state():
    from hvdbench.tools import sweep_rate

    held = {"waiting_at_close": 1, "in_flight_at_close": 8,
            "ttft_p50_ms_by_half": [31.6, 31.2],
            "ttft_max_ms_by_half": [80.0, 213.0]}
    assert sweep_rate.why_not(held, 8) == []
    assert sweep_rate.why_not(dict(held, waiting_at_close=8,
                                   in_flight_at_close=16), 8)
    assert sweep_rate.why_not(dict(held, in_flight_at_close=9), 8)
    assert sweep_rate.why_not(dict(held, ttft_p50_ms_by_half=[400, 658]), 8)
    assert sweep_rate.why_not(dict(held, ttft_max_ms_by_half=[90, 1089]), 8)


# --- the stratified generator ------------------------------------------------

SEEDS = (0, 1, 7, 2**31 + 12345, 2**32 + 5)


@pytest.mark.parametrize("name", ["score", "chat-loaded"])
def test_every_seed_offers_the_same_multiset_in_another_order(name):
    traffic = load("traffic", name)
    want = collections.Counter(generator.block_multiset(traffic))
    orders = set()
    for seed in SEEDS:
        for block in range(3):
            reqs = generator.request_block(traffic, seed, block, 50257)
            got = collections.Counter(
                (len(r.prompt), r.max_new_tokens) for r in reqs)
            assert got == want
            assert [r.index for r in reqs] == list(range(
                block * traffic["block"], (block + 1) * traffic["block"]))
            orders.add(tuple(len(r.prompt) for r in reqs))
        assert all(0 <= t < 50257 for r in reqs for t in r.prompt)
    assert len(orders) > len(SEEDS)       # the seed changes the order
    a = generator.request_block(traffic, 3, 0, 50257)
    b = generator.request_block(traffic, 3, 0, 50257)
    assert a == b                         # and nothing else does
    assert a != generator.request_block(traffic, 4, 0, 50257)


def test_score_mix_is_the_one_the_issue_describes():
    pairs = generator.block_multiset(load("traffic", "score"))
    prompts = [p for p, _ in pairs]
    outputs = sorted(o for _, o in pairs)
    assert len(pairs) == 16
    assert min(prompts) >= 128 and max(prompts) <= 1000
    assert statistics.mean(prompts) == pytest.approx(564, abs=1)
    assert sum(p > 256 for p in prompts) == 14     # the 1024 bucket
    assert outputs == [1, 1, 2, 2, 3, 3, 4, 4, 5, 5, 6, 6, 7, 7, 8, 8]
    assert max(p + o for p, o in pairs) < 1024


def test_chat_loaded_offers_the_retired_chat_mix_to_the_letter():
    """``chat-loaded`` took ``chat-steady``'s place at eight times its
    rate (PR 36); the lengths are the retired file's, pair for pair."""
    traffic = load("traffic", "chat-loaded")
    assert generator.block_multiset(traffic) == [
        (25, 19), (45, 37), (64, 62), (87, 26), (115, 43), (155, 86),
        (222, 31), (398, 51)]
    assert traffic["rate_per_s"] > 4 and traffic["kind"] == "serve-open"
    assert (traffic["preroll_s"], traffic["deadline_s"],
            traffic["trace_seconds"], traffic["check_requests"]) == (
                10, 0, 10, 6)


def test_chat_mix_uses_all_three_buckets():
    pairs = generator.block_multiset(load("traffic", "chat-loaded"))
    prompts = [p for p, _ in pairs]
    assert sum(p <= 64 for p in prompts) >= 2
    assert sum(64 < p <= 256 for p in prompts) >= 4
    assert sum(p > 256 for p in prompts) == 1
    assert all(16 <= o <= 96 for _, o in pairs)
    assert statistics.median(prompts) == pytest.approx(100, rel=0.15)


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_has_exactly_one_arrival_in_each_slot(seed):
    traffic = load("traffic", "chat-loaded")
    rate, n = traffic["rate_per_s"], traffic["block"]
    dues = [r.due_s for b in range(4)
            for r in generator.request_block(traffic, seed, b, 50257)]
    assert dues == sorted(dues)
    for k, due in enumerate(dues):
        assert k / rate <= due < (k + 1) / rate
    assert len(dues) == 4 * n


def test_pair_stride_must_be_coprime_with_the_block():
    traffic = dict(load("traffic", "score"), pair_stride=4)
    with pytest.raises(ValueError):
        generator.block_multiset(traffic)


def test_train_batches_differ_by_row_index_and_seed():
    traffic = load("traffic", "lm-8x1024")
    a_in, a_tg = generator.train_batch(traffic, 5, 0, 8, 50257)
    assert a_in.shape == a_tg.shape == (8, 1024)
    assert (a_in[:, 1:] == a_tg[:, :-1]).all()
    assert len({row.tobytes() for row in a_in}) == 8
    b_in, _ = generator.train_batch(traffic, 5, 1, 8, 50257)
    c_in, _ = generator.train_batch(traffic, 6, 0, 8, 50257)
    assert (a_in != b_in).any() and (a_in != c_in).any()
    again, _ = generator.train_batch(traffic, 5, 0, 8, 50257)
    assert (a_in == again).all()


def test_sample_holds_the_longest_and_follows_the_seed():
    a = generator.sample_indices(9, list(range(50)), 6, must_include=17)
    assert a[0] == 17 and len(a) == len(set(a)) == 6
    assert a == generator.sample_indices(9, list(range(50)), 6, 17)
    assert a != generator.sample_indices(10, list(range(50)), 6, 17)
    assert generator.sample_indices(1, [4], 6, 4) == [4]


# --- the window --------------------------------------------------------------

def _steps():
    """Block size 2; a step every second; request i admitted in step i
    with a 10-token prompt; every step also emits 3 tokens."""
    return [StepRecord(t_before=float(i), t_after=i + 0.9,
                       prompt_tokens=10, new_tokens=3, admitted=(i,))
            for i in range(9)]


def test_tokens_are_credited_in_the_step_that_made_them():
    steps = _steps()
    assert window.credited_tokens(steps, 0.0, 0.9) == 13
    assert window.credited_tokens(steps, 0.9, 2.9) == 26
    assert window.credited_tokens(steps, 0.0, 0.89) == 0


def test_the_measured_interval_is_whole_blocks():
    steps = _steps()
    bounds = window.block_boundaries(steps, block=2)
    assert bounds == {0: 0.0, 1: 2.0, 2: 4.0, 3: 6.0, 4: 8.0}
    t0, t1, blocks = window.whole_block_window(steps, 2, first_block=1,
                                               seconds=5.0)
    assert (t0, t1, blocks) == (2.0, 6.0, 2)
    # Every prompt of blocks 1 and 2 is in it, exactly once.
    inside = [s for s in steps if t0 < s.t_after <= t1]
    assert [s.admitted[0] for s in inside] == [2, 3, 4, 5]
    assert window.credited_tokens(steps, t0, t1) == 4 * 13
    assert window.whole_block_window(steps, 2, 1, seconds=1.5) is None
    assert window.whole_block_window(steps, 2, 7, seconds=5.0) is None


def test_gaps_belong_to_the_window_their_later_token_fell_in():
    times = [1.0, 1.2, 1.5, 2.5, 2.6]
    assert window.token_gaps(times, 1.1, 2.5) == pytest.approx(
        [0.2, 0.3, 1.0])
    assert window.token_gaps([1.0], 0.0, 9.0) == []


# --- shapes ------------------------------------------------------------------

def test_gpt2_medium_by_hand():
    cfg = load("configs", "gpt2-medium")
    per_layer = 1024 * 3072 + 1024 * 1024 + 2 * 1024 * 4096
    assert per_layer == 12_582_912
    assert flops.matmul_params(cfg) == 24 * per_layer + 1024 * 50257 \
        == 353_453_056
    assert flops.total_params(cfg) == 353_453_056 + 50257 * 1024 \
        + 1024 * 1024 + 24 * 4 * 1024 + 2 * 1024 == 406_065_152
    want = 6 * 353_453_056 + 6 * 24 * 1024 * 1024
    assert flops.train_flops_per_token(cfg, 1024) == want == 2_271_713_280


def test_gpt2_xl_by_hand():
    cfg = load("configs", "gpt2-xl")
    assert flops.matmul_params(cfg) == 48 * 12 * 1600 * 1600 \
        + 1600 * 50257 == 1_554_971_200
    assert flops.total_params(cfg) == 1_637_331_200


def test_flash_forward_cost_and_roofline():
    cost = flops.flash_fwd_cost(batch=8, heads=16, seq_len=1024,
                                head_dim=64)
    assert cost["flops"] == 2 * 2 * 128 * 1024 * 1024 * 64 / 2
    assert cost["bytes"] == 4 * 128 * 1024 * 64 * 2
    share = flops.roofline_share(cost, seconds=1e-3,
                                 device_kind="TPU v5 lite")
    assert share["bound"] == "compute"
    assert share["percent"] == pytest.approx(
        100 * cost["flops"] / 197e12 / 1e-3)


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
