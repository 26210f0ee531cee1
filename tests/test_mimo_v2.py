"""MiMo-V2-Flash's block on the serving path, at tiny widths with every
ratio kept (``hvdbench/tests/tiny_mimo_v2.py``: keys 24 over values 16,
1 full and 2 window KV heads, a window of 8 over blocks of 4, sinks,
rotary on 8 of a head's 24 numbers at two bases, scaled values, a dense
layer 0 and gated experts, 4 of 16 held): the program against the plain
reference (``hvdbench/reference/mimo_v2.py``), the mixed paged cache
against the dense rows and the reference's full forward, the decode
kernel under the interpreter against the view's arithmetic, the two
allocators (chains and rings), and what a mixed cache refuses.

    JAX_PLATFORMS=cpu python -m pytest tests/test_mimo_v2.py -q
"""

import copy
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from hvdbench.models import mimo_v2 as family              # noqa: E402
from hvdbench.reference import mimo_v2 as ref               # noqa: E402
from hvdbench.tests import tiny_mimo_v2                     # noqa: E402
from horovod_tpu.models import GPT, GPTConfig               # noqa: E402
from horovod_tpu.models.transformer import (                # noqa: E402
    KVKind, _rope, cache_kinds, init_kv_cache)
from horovod_tpu.ops import paged_attention as pa           # noqa: E402
from horovod_tpu.parallel.moe import DroplessExperts        # noqa: E402
from horovod_tpu.serve import (ContinuousBatcher,           # noqa: E402
                               InferenceEngine, SamplingParams)
from horovod_tpu.serve.kv import (BlockPool,                # noqa: E402
                                  KVPoolExhaustedError, RingPool)

SEED = 2**31 + 5
VOCAB = 211


def _config(dtype="float32", **over):
    cfg = tiny_mimo_v2.config(**over)
    cfg["run"].update(activation_dtype=dtype, param_dtype=dtype)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = _config()
    return cfg, family.build_model(cfg, "full"), family.make_params(cfg, SEED)


def _tokens(n, rows=1, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, (rows, n)), jnp.int32)


def _engine(model, params, **kw):
    kw = dict(dict(max_slots=3, prefill_buckets=(16, 64), max_seq_len=128,
                   kv_block=4), **kw)
    return InferenceEngine(model, params, **kw)


def _serve(eng, prompts, n_new):
    """Greedy, every prompt in a slot of its own, all in the same steps."""
    out = {i: [eng.start(i, p, SamplingParams(max_new_tokens=n_new))]
           for i, p in enumerate(prompts)}
    for _ in range(n_new - 1):
        for slot, toks in eng.step().items():
            out[slot] += toks
    return out


PROMPTS = [list(map(int, _tokens(n, seed=n)[0])) for n in (5, 40, 13)]


# --- the model ---------------------------------------------------------------

def test_the_tree_is_the_one_the_model_initialises(tiny):
    cfg, model, params = tiny
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), _tokens(8))["params"])
    shapes = lambda t: jax.tree.map(                      # noqa: E731
        lambda x: (x.shape, str(x.dtype)), t)
    assert shapes(params) == shapes(want)
    attn = params["block_1"]["attn"]
    # q 4 x 24, k 2 x 24, v 2 x 16 in one kernel; the output takes 4 x 16.
    assert attn["qkv"]["kernel"].shape == (32, 96 + 48 + 32)
    assert attn["out"]["kernel"].shape == (64, 32)
    assert attn["sink"].shape == (4,) and attn["sink"].dtype == jnp.float32
    assert "sink" not in params["block_0"]["attn"]
    assert params["block_0"]["attn"]["qkv"]["kernel"].shape == (32, 96 + 40)
    assert set(params["block_1"]["experts"]) == {
        "router", "select_bias", "gate", "up", "down"}
    assert "mlp" in params["block_0"] and "mlp" not in params["block_1"]


@pytest.mark.parametrize("length", [7, 40, 61])
def test_the_forward_is_the_references(tiny, length):
    cfg, model, params = tiny
    tokens = _tokens(length, rows=2, seed=length)
    got = model.apply({"params": params}, tokens)
    want = ref.logits(ref.seed_key(SEED), tokens, ref.sizes(cfg))
    assert float(jnp.std(want)) > 0.05
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("what, change", [
    ("sink", {"add_swa_attention_sink_bias": False}),
    ("value_scale", {"attention_value_scale": 1.0}),
    ("window", {"sliding_window": 64}),
    ("partial_rotary", {"partial_rotary_factor": 1.0}),
    ("window_theta", {"swa_rope_theta": 5000000}),
])
def test_leaving_a_part_of_the_mathematics_out_fails(tiny, what, change):
    """The reference without the part: the program's logits are no
    longer its — by twenty times what the two agree to (rotary
    positions move little where the scores are as small as these)."""
    cfg, model, params = tiny
    tokens = _tokens(40, rows=2, seed=3)
    got = model.apply({"params": params}, tokens)
    wrong = ref.logits(ref.seed_key(SEED), tokens,
                       ref.sizes(dict(copy.deepcopy(cfg), **change)))
    assert float(jnp.max(jnp.abs(got - wrong))) > 4e-5, what


def test_rotary_turns_the_first_numbers_of_a_head_and_passes_the_rest():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 2, 24)),
                    jnp.float32)
    at = jnp.arange(5, dtype=jnp.int32)[None] + 3
    got = _rope(x, at, 1e4, 8)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[..., :8], _rope(x[..., :8], at, 1e4),
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(got[..., :8] - x[..., :8]))) > 0.1
    np.testing.assert_array_equal(_rope(x, at, 1e4, 24), _rope(x, at, 1e4))


def test_the_model_declares_what_each_layer_keeps(tiny):
    cfg, model, _ = tiny
    full, window = KVKind(24, 16, 0), KVKind(48, 32, 8)
    assert cache_kinds(model.config) == (full, window, window, full)
    rows = init_kv_cache(model.config, 2, 16)
    assert rows[0]["k"].shape == (2, 16, 1, 24)
    assert rows[1]["k"].shape == (2, 16, 2, 24)
    assert rows[1]["v"].shape == (2, 16, 2, 16)


def test_a_state_space_layer_is_still_refused():
    cfg = GPTConfig(vocab_size=31, n_layer=2, n_head=2, d_model=16, d_ff=32,
                    layers=("ssm", "attention"), ssm_heads=2, ssm_head_dim=8)
    with pytest.raises(NotImplementedError, match="'ssm' layers is not "
                       "built: a state-space layer needs a state cache"):
        cache_kinds(cfg)
    # ... and an expert layer alone on its residual no longer is.
    cfg = dataclasses.replace(cfg, layers=("experts", "attention"),
                              expert_count=4, expert_d_ff=8)
    assert cache_kinds(cfg) == (None, KVKind(16, 16, 0))


# --- the cache ---------------------------------------------------------------

def test_prefill_then_decode_through_the_mixed_cache_is_the_references(tiny):
    """Three rows at different depths in the same steps, to contexts
    past three windows (5 + 50, 40 + 50, 13 + 50 positions over a window
    of 8): every served token is the one the reference's full forward
    puts first, to float32's rounding, and the paged cache gives the
    dense rows' tokens."""
    cfg, model, params = tiny
    paged = _serve(_engine(model, params), PROMPTS, 50)
    dense = _serve(_engine(model, params, kv_cache="dense"), PROMPTS, 50)
    assert paged == dense
    s = ref.sizes(cfg)
    gaps, _ = ref.served_token_gaps(
        ref.seed_key(SEED), [(p, paged[i]) for i, p in enumerate(PROMPTS)],
        s, pad_to=32)
    assert len(gaps) == 150 and max(gaps) < 1e-5


def test_the_decode_kernel_serves_the_same_tokens(tiny, monkeypatch):
    """The engine's decode step with the kernel under the interpreter
    (bfloat16 pools of whole vectors: the kernel's shapes) against the
    view's arithmetic."""
    cfg = _config("bfloat16", head_dim=128, swa_head_dim=128, v_head_dim=64,
                  swa_v_head_dim=64, num_key_value_heads=2,
                  swa_num_key_value_heads=4, sliding_window=24,
                  partial_rotary_factor=0.25)
    cfg["run"]["engine"].update(kv_block=16)
    model, params = family.build_model(cfg, "full"), family.make_params(
        cfg, SEED)
    view = _serve(_engine(model, params, kv_block=16), PROMPTS, 40)
    decode, calls = pa.paged_decode, []

    def interpreted(*args, **kw):
        calls.append(kw.get("window", 0))
        return decode(*args, interpret=True, **kw)

    monkeypatch.setattr(pa, "paged_decode", interpreted)
    kernel = _serve(_engine(model, params, kv_block=16), PROMPTS, 40)
    assert sorted(set(calls)) == [0, 24]
    # bfloat16 on both sides: a few tokens may differ where two logits
    # lie within rounding; the chains agree at the start and mostly.
    same = sum(a == b for i in view for a, b in zip(view[i], kernel[i]))
    assert all(view[i][:4] == kernel[i][:4] for i in view)
    assert same >= 0.6 * 120


CASES = {
    "full_two_widths": dict(K=2, window=0, sink=False,
                            lengths=[1, 17, 100, 333, 0]),
    "window_with_a_sink": dict(K=4, window=40, sink=True,
                               lengths=[1, 17, 100, 333, 0]),
    "window_of_whole_blocks": dict(K=2, window=128, sink=True,
                                   lengths=[128, 129, 144, 145, 4000]),
    "full_with_a_sink": dict(K=2, window=0, sink=True,
                             lengths=[5, 64, 65]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_the_views_arithmetic(name):
    """Start, sink and two row widths: keys 192 wide over values 128,
    blocks of 16, a ring of ``window / 16 + 1`` blocks (or a table as
    long as the longest row), rows shorter than the window, at its edge
    and far past it, and a row without a request."""
    case = CASES[name]
    H, K, Dk, Dv, block = 8, case["K"], 192, 128, 16
    window, lengths = case["window"], case["lengths"]
    rng = np.random.default_rng(len(name))
    B = len(lengths)
    ring = (-(-window // block) + 1) if window else -(-max(lengths) // block)
    blocks = 1 + B * ring
    k_pool = jnp.asarray(rng.standard_normal((blocks, block, K * Dk)),
                         jnp.bfloat16)
    v_pool = jnp.asarray(rng.standard_normal((blocks, block, K * Dv)),
                         jnp.bfloat16)
    table = np.zeros((B, ring + 1), np.int32)
    free = list(rng.permutation(np.arange(1, blocks)))
    for b, n in enumerate(lengths):
        for c in range(min(-(-n // block), ring)):
            table[b, c] = free.pop()
    positions = jnp.asarray(np.maximum(np.asarray(lengths) - 1, 0), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, Dk)), jnp.bfloat16)
    sink = (jnp.asarray(2 * rng.standard_normal(H), jnp.float32)
            if case["sink"] else None)
    args = (q, k_pool, v_pool, jnp.asarray(table), positions, K)
    kw = dict(v_head_dim=Dv, window=window, sink=sink)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: pa.paged_decode(*a, K, interpret=True, **kw))(*args[:-1]))
    got = np.asarray(pa.paged_decode(*args, interpret=True, **kw), np.float32)
    want = np.asarray(pa.paged_decode(*args, **kw), np.float32)
    live = np.asarray(lengths) > 0
    assert got.shape == (B, H, Dv)
    np.testing.assert_allclose(got[live], want[live], atol=0.03)
    if case["sink"] and min(n for n in lengths if n) < 20:
        # Among few keys a sink takes a visible share.
        without = np.asarray(pa.paged_decode(
            *args, **dict(kw, sink=None)), np.float32)
        assert np.abs(want[live] - without[live]).max() > 0.05


def test_the_ring_view_holds_the_windows_positions():
    """Column ``c`` of a ring of 3 blocks of 4 holds the newest block
    index congruent to ``c``: what a row at position 13 (block 3) and
    one at position 2 see."""
    table = jnp.zeros((2, 4), jnp.int32)
    got = np.asarray(pa.ring_positions(table, jnp.asarray([13, 2]), 4))
    assert got[0].tolist() == [12, 13, 14, 15, 4, 5, 6, 7, 8, 9, 10, 11]
    assert got[1].tolist() == [0, 1, 2, 3, -8, -7, -6, -5, -4, -3, -2, -1]


def test_window_blocks_stay_flat_while_full_blocks_grow(tiny):
    cfg, model, params = tiny
    eng = _engine(model, params)
    eng.start(0, PROMPTS[1], SamplingParams(max_new_tokens=80))   # 40 tokens
    seen = []
    for _ in range(60):
        eng.step()
        stats = eng.kv_stats()
        seen.append((stats["kv_blocks_in_use"],
                     stats["kv_window_blocks_in_use"],
                     stats["kv_window_blocks_given_back"]))
    full, ring, back = zip(*seen)
    assert set(ring) == {3} and stats["kv_window_ring_blocks"] == 3
    assert full[0] == 11 and full[-1] == 25 and list(full) == sorted(full)
    # Ten blocks of the prompt's ten fell behind at once, then one for
    # each block of positions begun.
    assert back[0] == 10 - 3 + 1 and back[-1] == 25 - 3
    assert stats["kv_window_bytes_in_use"] == 3 * 2 * 4 * 2 * 40 * 4
    assert stats["kv_full_bytes_in_use"] == 25 * 2 * 4 * 1 * 40 * 4
    assert stats["paged_live_positions_window"] == 60 * 8
    assert stats["paged_live_positions_full"] == sum(range(41, 101))
    eng.release(0)
    after = eng.kv_stats()
    assert after["kv_blocks_in_use"] == after["kv_window_blocks_in_use"] == 0
    assert after["kv_blocks_cached"] == 0    # nothing kept for a prefix


def test_chains_and_rings_have_an_allocator_each():
    table = np.zeros((2, 9), np.int32)
    ring_table = np.zeros((3, 4), np.int32)
    pool = BlockPool(17, 4, table, lambda s, d: None, index_prefixes=False)
    rings = RingPool(7, ring_table, bytes_per_block=64)

    def writable(slot, start, n):
        pool.ensure_writable(slot, start, n)
        rings.reach(slot, (start + n - 1) // 4)

    assert pool.begin_request(0, list(range(10))) == 0
    rings.begin(0)
    writable(0, 0, 10)
    assert (table[0, :3] > 0).all() and table[0, 3] == 0
    assert (ring_table[0, :3] > 0).all() and ring_table[0, 3] == 0
    first = ring_table[0].copy()
    writable(0, 10, 10)                          # blocks 2..4: two new
    assert (ring_table[0] == first).all()        # the ring's blocks stay
    assert rings.stats()["kv_window_blocks_given_back"] == 2
    assert (pool.blocks_in_use(), rings.blocks_in_use()) == (5, 3)
    pool.index_prompt(0, list(range(10)))
    assert pool.probe(list(range(10))) == 0      # shares no prefix
    pool.begin_request(1, list(range(10)))
    rings.begin(1)
    writable(1, 0, 12)
    assert set(ring_table[0, :3]) & set(ring_table[1, :3]) == set()
    with pytest.raises(RuntimeError, match="slot 1 already has a ring"):
        rings.begin(1)
    pool.release(0)
    rings.release(0)
    assert (ring_table[0] == 0).all()
    assert (pool.blocks_in_use(), rings.blocks_in_use()) == (3, 3)
    rings.reach(0, 5)                            # released: nothing taken
    assert rings.blocks_in_use() == 3 and (ring_table[0] == 0).all()
    rings.begin(0)
    rings.reach(0, 3)
    rings.begin(2)
    with pytest.raises(KVPoolExhaustedError, match="all 6 window-layer "
                       "blocks are held"):
        rings.reach(2, 0)


def test_the_step_counts_what_the_experts_were_sent(tiny):
    cfg, model, params = tiny
    eng = _engine(model, params)
    _serve(eng, PROMPTS, 6)
    stats = eng.kv_stats()
    assert stats["expert_layers"] == 3 and stats["experts_held"] == 4
    # Five steps of three rows (and no row idle), top 4 of 16 in three
    # layers: 180 pairs routed, those to experts 0..3 held here.
    assert 0 < stats["expert_pairs_held"] < 180
    assert 0 < stats["experts_touched"] <= 5 * 3 * 4
    assert stats["experts_touched"] <= stats["expert_pairs_held"]
    again = eng.kv_stats()
    assert again["expert_pairs_held"] == stats["expert_pairs_held"]


def test_a_snapshot_from_another_thread_changes_no_token(tiny):
    """``batcher.snapshot()`` is what a server's handler thread runs
    for every stats request while the batcher thread steps: it reads,
    and what is served is what an undisturbed run serves."""
    import threading

    cfg, model, params = tiny

    def served(poll):
        batcher = ContinuousBatcher(_engine(model, params))
        batcher.max_new_tokens_cap = 64
        stop, polls = threading.Event(), [0]

        def asker():
            while not stop.is_set():
                snap = batcher.snapshot()
                assert snap["expert_pairs_held"] >= 0
                polls[0] += 1

        thread = threading.Thread(target=asker, daemon=True)
        if poll:
            thread.start()
        reqs = [batcher.submit(p, SamplingParams(max_new_tokens=40),
                               deadline_s=0) for p in PROMPTS]
        while not all(r.done.is_set() for r in reqs):
            batcher.step()
        stop.set()
        if poll:
            thread.join(timeout=30)
            assert not thread.is_alive() and polls[0] > 0
        assert [r.error for r in reqs] == [None] * 3
        return [r.tokens for r in reqs], batcher.engine.kv_stats()

    quiet, stats = served(False)
    polled, polled_stats = served(True)
    assert polled == quiet
    for key in ("expert_pairs_held", "experts_touched", "decode_steps"):
        assert polled_stats[key] == stats[key]


# --- what a mixed cache refuses, and what it serves ---------------------------

def test_speculation_is_refused_in_one_sentence(tiny):
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="speculative decoding over window "
                       "layers is not built yet"):
        _engine(model, params, drafter=(model, params))


def test_tensor_parallelism_is_refused_in_one_sentence(tiny):
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="tensor-parallel serving of a "
                       "model with window layers is not built yet"):
        _engine(model, params, tp=2)


def test_migration_frames_are_refused_in_one_sentence(tiny):
    cfg, model, params = tiny
    eng = _engine(model, params)
    eng.start(0, PROMPTS[0], SamplingParams(max_new_tokens=4))
    with pytest.raises(RuntimeError, match="has no migration frame yet: "
                       "export_slot_kv ships one K/V shape"):
        eng.export_slot_kv(0)
    with pytest.raises(RuntimeError, match="has no migration frame yet: "
                       "import_slot_kv ships one K/V shape"):
        eng.import_slot_kv(1, PROMPTS[0], np.zeros((4, 2, 4, 1, 24)),
                           np.zeros((4, 2, 4, 1, 16)), 3,
                           SamplingParams(max_new_tokens=4))


def test_a_preempted_request_resumes_if_it_fits_a_bucket(tiny):
    cfg, model, params = tiny
    whole = _serve(_engine(model, params), PROMPTS[1:2], 20)[0]
    eng = _engine(model, params)
    first = _serve(eng, PROMPTS[1:2], 8)[0]
    assert first == whole[:8] and eng.can_resume(40, 8)
    eng.preempt_slot(0, PROMPTS[1], first)
    assert eng.kv_stats()["kv_window_blocks_in_use"] == 0
    assert eng.resume_slot(2, PROMPTS[1], first,
                           SamplingParams(max_new_tokens=20)) == 0
    rest = []
    for _ in range(12):
        rest += eng.step()[2]
    assert first + rest == whole
    # Past the largest bucket a ring cannot be prefilled in one chunk.
    assert not eng.can_resume(40, 30)
    eng.release(2)
    with pytest.raises(RuntimeError, match="does not resume over window "
                       "layers"):
        eng.resume_slot(1, PROMPTS[1], list(range(30)),
                        SamplingParams(max_new_tokens=40))


def test_the_batcher_serves_requests_whole(tiny):
    cfg, model, params = tiny
    batcher = ContinuousBatcher(_engine(model, params))
    batcher.max_new_tokens_cap = 64
    reqs = [batcher.submit(p, SamplingParams(max_new_tokens=30),
                           deadline_s=0) for p in PROMPTS + PROMPTS[:2]]
    while not all(r.done.is_set() for r in reqs):
        batcher.step()
    assert [r.error for r in reqs] == [None] * 5
    assert [len(r.tokens) for r in reqs] == [30] * 5
    alone = _serve(_engine(model, params), PROMPTS[:1], 30)[0]
    assert reqs[0].tokens == alone and reqs[3].tokens == alone
    assert batcher.engine.kv_stats()["kv_prefix_hits_total"] == 0


# --- the expert layer --------------------------------------------------------

def _experts_layer(held, gated=True):
    return DroplessExperts(d_model=32, d_ff=16, n_experts=16, top_k=4,
                           held=held, gated=gated, dtype=jnp.float32)


def test_four_shares_of_the_gated_experts_add_up_to_the_uncut_layer():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 24, 32)),
                    jnp.float32)
    whole = _experts_layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {"router", "select_bias", "gate", "up", "down"}
    want = whole.apply({"params": params}, x)
    total = 0
    for offset in range(0, 16, 4):
        share = dict(params, **{k: params[k][offset:offset + 4]
                                for k in ("gate", "up", "down")})
        total = total + _experts_layer((offset, 4)).apply(
            {"params": share}, x)
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert float(jnp.max(jnp.abs(want))) > 1e-3


def test_a_gated_expert_is_silu_gate_times_up():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 8, 32)),
                    jnp.float32)
    layer = DroplessExperts(d_model=32, d_ff=16, n_experts=1, top_k=1,
                            gated=True, dtype=jnp.float32)
    p = layer.init(jax.random.PRNGKey(2), x)["params"]
    want = (jax.nn.silu(x @ p["gate"][0]) * (x @ p["up"][0])) @ p["down"][0]
    np.testing.assert_allclose(layer.apply({"params": p}, x), want, atol=1e-6)


@pytest.mark.parametrize("rows", [32, 48])
def test_the_decode_shape_runs_the_training_path(rows):
    """32 or 48 rows of one token (the cell's slots), top 8 of 256 with
    16 held: 256 or 384 pairs, fewer than the usual rows, most held
    experts sent none."""
    from horovod_tpu.parallel.moe import usual_rows

    assert usual_rows(rows * 8, 16, 256) == rows * 8
    x = jnp.asarray(np.random.default_rng(2).standard_normal((rows, 1, 32)),
                    jnp.float32)
    layer = DroplessExperts(d_model=32, d_ff=16, n_experts=256, top_k=8,
                            held=(0, 16), gated=True, dtype=jnp.float32)
    p = layer.init(jax.random.PRNGKey(3), x)["params"]
    out, sown = layer.apply({"params": p}, x, mutable=["intermediates"])
    sizes = np.asarray(sown["intermediates"]["pairs_held"][0])
    assert sizes.shape == (16,) and 0 < sizes.sum() < 2 * rows
    scores = jax.nn.sigmoid(x[:, 0] @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores, 8)
    want = jnp.zeros((rows, 32))
    for e in range(16):
        w = jnp.sum(jnp.where(chosen == e, jnp.take_along_axis(
            scores, chosen, -1) / jnp.take_along_axis(
                scores, chosen, -1).sum(-1, keepdims=True), 0.0), -1)
        h = jax.nn.silu(x[:, 0] @ p["gate"][e]) * (x[:, 0] @ p["up"][e])
        want = want + w[:, None] * (h @ p["down"][e])
    np.testing.assert_allclose(out[:, 0], want, atol=1e-5)
