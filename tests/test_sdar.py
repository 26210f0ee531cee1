"""SDAR's block on the serving path, at tiny widths with every ratio
kept (``hvdbench/tests/tiny_sdar.py``: 4 query heads on 2 KV heads, 16
experts of which a token takes 4, all held, blocks of 4 positions): a
model that generates by diffusion over blocks, against the plain
reference (``hvdbench/reference/sdar.py``) — the forward under the block
mask, the logits of every denoising step of every block through the
paged cache, generation token for token and step for step, the transfer
rules on made logits, the softmax router, the block's queries folded
into the decode kernel — then the scheduler's cases and what such a
model refuses.

    JAX_PLATFORMS=cpu python -m pytest tests/test_sdar.py -q
"""

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

from hvdbench.models import sdar as family                  # noqa: E402
from hvdbench.reference import sdar as ref                   # noqa: E402
from hvdbench.tests import tiny_sdar                         # noqa: E402
from horovod_tpu.models import GPT, GPTConfig                # noqa: E402
from horovod_tpu.obs import trace as trace_mod               # noqa: E402
from horovod_tpu.ops import paged_attention as pa            # noqa: E402
from horovod_tpu.parallel import moe                         # noqa: E402
from horovod_tpu.serve import (ContinuousBatcher,            # noqa: E402
                               InferenceEngine, SamplingParams)
from horovod_tpu.serve import engine as engine_mod           # noqa: E402

SEED = 2**31 + 7
VOCAB, B, MASK = 211, 4, 210
# What float32 leaves between two orders of the same sums: the program
# and the reference agree to a few 1e-7 on logits of 0.1 to 0.5.
ATOL = 2e-6


def _config(dtype="float32", **over):
    cfg = tiny_sdar.config(**over)
    cfg["run"].update(activation_dtype=dtype, param_dtype=dtype)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = _config()
    return cfg, family.build_model(cfg, "full"), family.make_params(cfg, SEED)


def _tokens(n, rows=1, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB - 1, (rows, n)), jnp.int32)


def _prompt(n, seed=None):
    return [int(t) for t in _tokens(n, seed=n if seed is None else seed)[0]]


def _engine(model, params, **kw):
    kw = dict(dict(max_slots=3, prefill_buckets=(16, 64), max_seq_len=128,
                   kv_block=4), **kw)
    return InferenceEngine(model, params, **kw)


@pytest.fixture(scope="module")
def shared(tiny):
    """One engine for the tests that ask nothing special of theirs:
    its programs compile once.  ``seen`` holds every block step's
    logits, as ``_transfer`` was handed them (the spy is in the traced
    program; nothing else of the module sees it)."""
    _, model, params = tiny
    seen, transfer = [], engine_mod._transfer

    def spy(step, logits, mask_token):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return transfer(step, logits, mask_token)

    engine_mod._transfer = spy
    try:
        eng = _engine(model, params)
        eng.start(0, _prompt(8), _static(4, 1))
        eng.step()                      # traced with the spy in it
        eng.release(0)
    finally:
        engine_mod._transfer = transfer
    return eng, seen


@pytest.fixture
def eng(shared):
    """The shared engine, its slots free again afterwards.  Its
    counters run on from test to test: a test reads what they grew by."""
    yield shared[0]
    for slot in shared[0].active_slots():
        shared[0].release(slot)


def _grown(eng, before):
    return {k: v - before[k] for k, v in eng.kv_stats().items()
            if isinstance(v, int) and k in before}


def _static(n_new, steps=0, **kw):
    return SamplingParams(max_new_tokens=n_new, denoising_steps=steps,
                          transfer="static", **kw)


def _drain(eng, slot, n_new):
    """Steps until ``slot`` has delivered ``n_new`` tokens: ``(tokens,
    their denoising steps, what every step delivered)``."""
    tokens, steps, sizes = [], [], []
    while len(tokens) < n_new:
        out = eng.step()[slot]
        tokens += list(out)
        steps += out.steps
        sizes.append(len(out))
    return tokens[:n_new], steps[:n_new], sizes


# --- the model ---------------------------------------------------------------

def test_the_tree_is_the_one_the_model_initialises(tiny):
    cfg, model, params = tiny
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), _tokens(8))["params"])
    shapes = lambda t: jax.tree.map(                      # noqa: E731
        lambda x: (x.shape, str(x.dtype)), t)
    assert shapes(params) == shapes(want)
    attn = params["block_0"]["attn"]
    assert attn["qkv"]["kernel"].shape == (32, 64 + 32 + 32)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert params["block_2"]["experts"]["gate"].shape == (16, 32, 16)
    assert "mlp" not in params["block_0"]


def test_the_defaults_leave_every_other_model_as_it_was():
    cfg = GPTConfig()
    assert cfg.block_length == 0 and cfg.expert_scoring == "sigmoid"
    assert moe.DroplessExperts(8, 8, 4, 2).scoring == "sigmoid"


@pytest.mark.parametrize("length", [7, 40])
def test_the_forward_is_the_references(tiny, length):
    cfg, model, params = tiny
    tokens = _tokens(length, seed=length)
    got = model.apply({"params": params}, tokens)
    want = ref.logits(ref.seed_key(SEED), tokens, ref.sizes(cfg))
    assert float(jnp.std(want)) > 0.05
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("what, change", [
    ("block_mask", {"block_length": 0}),
    ("softmax_router", {"expert_scoring": "sigmoid"}),
    ("qk_norm", {"qk_norm": False}),
])
def test_leaving_a_part_of_the_mathematics_out_fails(tiny, what, change):
    """A program without the part: its logits are no longer the
    reference's, by many times what the two agree to."""
    cfg, model, params = tiny
    tokens = _tokens(40, seed=3)
    if what == "qk_norm":
        params = jax.tree.map(lambda x: x, params)
        for name in list(params):
            if name.startswith("block_"):
                params[name] = dict(params[name], attn={
                    k: v for k, v in params[name]["attn"].items()
                    if not k.endswith("_norm")})
    wrong = GPT(dataclasses.replace(model.config, **change)).apply(
        {"params": params}, tokens)
    want = ref.logits(ref.seed_key(SEED), tokens, ref.sizes(cfg))
    assert float(jnp.max(jnp.abs(wrong - want))) > 20 * ATOL, what


def test_the_two_stream_forward_is_the_plain_one_on_its_clean_stream(tiny):
    """And a noised block sees itself and the clean blocks before it:
    its logits are those of the plain forward over the sequence cut at
    the block's end with the block noised."""
    cfg, _, _ = tiny
    s, key = ref.sizes(cfg), ref.seed_key(SEED)
    clean = np.asarray(_tokens(16, seed=5)[0])
    noisy = clean.copy()
    noisy[[5, 6, 9, 11, 12, 15]] = MASK
    both = jnp.asarray(np.concatenate([clean, noisy])[None], jnp.int32)
    pos = np.concatenate([np.arange(16), np.arange(16)])
    noised = np.arange(32) >= 16
    two = ref.logits(key, both, s, pos=pos, noised=noised)
    np.testing.assert_allclose(
        two[:, :16], ref.logits(key, jnp.asarray(clean[None]), s), atol=ATOL)
    for block in range(4):
        end = 4 * block + 4
        cut = np.concatenate([clean[:end - 4], noisy[end - 4:end]])
        np.testing.assert_allclose(
            two[0, 16 + end - 4:16 + end],
            ref.logits(key, jnp.asarray(cut[None], jnp.int32), s)[0, end - 4:],
            atol=ATOL)


def test_the_view_masks_by_blocks():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 12, 2, 8)), jnp.float32)
               for _ in range(3))
    at = jnp.arange(12, dtype=jnp.int32)[None]
    got = pa.view_attention(q, k, v, at, block=4)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 8 ** -0.5
    seen = (np.arange(12)[None] // 4) <= (np.arange(12)[:, None] // 4)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(
        got, jnp.einsum("bhqk,bkhd->bqhd", probs, v), atol=1e-6)
    causal = pa.view_attention(q, k, v, at)
    assert float(jnp.max(jnp.abs(got - causal))) > 0.01


@pytest.mark.parametrize("attention", ["flash", "ring", "ulysses"])
def test_other_attentions_refuse_a_block_mask_in_one_sentence(tiny,
                                                              attention):
    _, model, params = tiny
    other = GPT(dataclasses.replace(model.config, attention=attention))
    with pytest.raises(ValueError, match="a block mask .* is causal "
                                         "attention='full'"):
        other.apply({"params": params}, _tokens(8))


def test_window_layers_refuse_a_block_mask_in_one_sentence():
    with pytest.raises(ValueError, match="a block mask .* over 'window'"):
        GPTConfig(n_layer=2, attn="window", window=8,
                  block_length=4).attn_kinds


# --- the expert layer --------------------------------------------------------

def test_the_softmax_router_is_the_references(tiny):
    """Softmax over all experts, the top 4, renormalised: the layer's
    output on a random input is the reference's plain sum."""
    cfg, _, _ = tiny
    s = ref.sizes(cfg)
    key = ref.seed_key(SEED)
    lp = {n: ref.make_leaf(key, n, 1, s) for n in ref.LAYER_LEAVES}
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 24, 32)),
                    jnp.float32)
    layer = moe.DroplessExperts(
        d_model=32, d_ff=16, n_experts=16, top_k=4, gated=True,
        scoring="softmax", dtype=jnp.float32)
    params = {"router": {"kernel": lp["router"]}, "gate": lp["e_gate"],
              "up": lp["e_up"], "down": lp["e_down"],
              "select_bias": jnp.zeros(16)}
    np.testing.assert_allclose(
        layer.apply({"params": params}, x),
        ref.experts_layer(x, lp, s, "f32"), atol=1e-6)
    sigmoid = dataclasses.replace(layer, scoring="sigmoid").apply(
        {"params": params}, x)
    assert float(jnp.max(jnp.abs(
        sigmoid - ref.experts_layer(x, lp, s, "f32")))) > 1e-5
    experts, weights = ref.route(x, lp, s)
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)
    got_e, got_w = moe.route(probs, jnp.zeros(16), 4, 1.0)
    np.testing.assert_array_equal(np.sort(got_e, -1), np.sort(experts, -1))
    np.testing.assert_allclose(got_w.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.sort(got_w, -1), np.sort(weights, -1),
                               atol=1e-6)
    with pytest.raises(ValueError, match="Unknown scoring"):
        dataclasses.replace(layer, scoring="top1").apply(
            {"params": params}, x)


def test_grouped_dot_at_twelve_rows_a_group_is_the_plain_sum():
    """Every expert of a layer held (``held=None``), 128 groups, about
    twelve pairs a group — a block step's load — under the interpreter,
    against the sum over experts written out."""
    rng = np.random.default_rng(2)
    groups, rows, d_in, d_out = 128, 1536, 128, 128
    sizes = rng.multinomial(rows, np.ones(groups) / groups).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((rows, d_in)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((groups, d_in, d_out)), jnp.float32)
    got = moe.grouped_dot(x, w, jnp.asarray(sizes), jnp.float32, True)
    group = np.repeat(np.arange(groups), sizes)
    want = sum(jnp.where((group == g)[:, None], x @ w[g], 0.0)
               for g in range(groups))
    assert 0 in sizes or sizes.min() < 6      # uneven, as a router sends
    np.testing.assert_allclose(got, want, atol=1e-3)


# --- the paged cache ---------------------------------------------------------

def _pools(model, blocks, block=4):
    cfg = model.config
    zeros = jnp.zeros((blocks, block, cfg.kv_heads * cfg.head_size),
                      cfg.dtype)
    return [{"k": zeros, "v": zeros} for _ in range(cfg.n_layer)]


@pytest.mark.parametrize("fresh", [True, False])
@pytest.mark.parametrize("length", [8, 20])
def test_a_prefill_chunk_through_the_paged_cache_is_the_references(
        tiny, fresh, length):
    """Both paths a chunk of several tokens may take: its own keys
    (``fresh``) and the gathered view."""
    cfg, model, params = tiny
    tokens = _tokens(length, seed=length + 1)
    pools = _pools(model, 9)
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 0]], jnp.int32)
    caches = [{"k_pool": p["k"], "v_pool": p["v"], "table": table,
               "fresh": fresh} for p in pools]
    got, _ = model.apply({"params": params}, tokens, kv_caches=caches,
                         positions=jnp.arange(length, dtype=jnp.int32)[None])
    want = ref.logits(ref.seed_key(SEED), tokens, ref.sizes(cfg))
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("interpret", [None, True])
def test_the_folded_block_rides_the_decode_kernel(interpret):
    """A row's ``B`` queries as ``B x H`` query heads over the ``K`` KV
    heads, the row's length at the block's end — the kernel under the
    interpreter, and the view's arithmetic off the TPU — against
    ``view_attention`` under the block mask over the gathered view."""
    rng = np.random.default_rng(4)
    H, K, D, block, lengths = 8, 2, 128, 16, [4, 20, 64, 132, 0]
    rows, cols = len(lengths), 10
    blocks = 1 + rows * cols
    dtype = jnp.bfloat16 if interpret else jnp.float32
    k_pool = jnp.asarray(rng.standard_normal((blocks, block, K * D)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((blocks, block, K * D)), dtype)
    table = np.zeros((rows, cols + 1), np.int32)
    free = list(rng.permutation(np.arange(1, blocks)))
    for b, n in enumerate(lengths):
        for c in range(-(-n // block)):
            table[b, c] = free.pop()
    table = jnp.asarray(table)
    q = jnp.asarray(rng.standard_normal((rows, B, H, D)), dtype)
    ends = jnp.asarray(np.maximum(np.asarray(lengths) - 1, 0), jnp.int32)
    got = pa.unfold_block(pa.paged_decode(
        pa.fold_block(q, K), k_pool, v_pool, table, ends, K,
        interpret=interpret), B, K)
    if interpret:
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda q: pa.paged_decode(pa.fold_block(q, K), k_pool, v_pool,
                                      table, ends, K, interpret=True))(q))
    positions = ends[:, None] - (B - 1) + jnp.arange(B)[None]
    want = pa.view_attention(
        q, pa.gathered_view(k_pool, table[:, :-1], K, D),
        pa.gathered_view(v_pool, table[:, :-1], K, D), positions, block=B)
    live = np.asarray(lengths) > 0
    assert got.shape == (rows, B, H, D)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=0.03 if interpret else 1e-5)
    np.testing.assert_array_equal(
        pa.unfold_block(pa.fold_block(q, K), B, K), q)


# --- the engine against generate() -------------------------------------------

@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("length", [8, 9, 11])
def test_generation_through_the_paged_cache_is_generates(tiny, shared, eng,
                                                         length, steps):
    """Prompts with ``P mod 4`` of 0, 1 and 3, ``T`` of 1, 2 and 4: the
    logits of every denoising step of every block (which read the
    prefilled blocks and every committed one), the tokens and the step
    at which each was chosen, against the plain loop without a cache."""
    cfg, model, params = tiny
    prompt, n_new = _prompt(length), 13
    want_tokens, want_steps, trace = ref.generate(
        ref.seed_key(SEED), prompt, ref.sizes(cfg), max_new_tokens=n_new,
        denoising_steps=steps, rule="static")
    spied, before = shared[1], eng.kv_stats()
    jax.effects_barrier()
    spied.clear()
    assert eng.start(0, prompt, _static(n_new, steps)) is None
    tokens, chosen, _ = _drain(eng, 0, n_new)
    jax.effects_barrier()
    assert tokens == want_tokens and chosen == want_steps
    assert set(chosen) == set(range(steps))
    # The engine's forwards: the reference's denoising steps, and one
    # more, all clean, after each block's last.
    denoise = [lg[0] for lg, commit in zip(spied, _commits(trace, len(spied)))
               if not commit]
    assert len(denoise) >= len(trace) - 1
    for (start, t, want, masked), got in zip(trace, denoise):
        np.testing.assert_allclose(got[masked], want[masked], atol=ATOL,
                                   err_msg=f"block at {start}, step {t}")
    stats = _grown(eng, before)
    assert stats["denoise_forwards"] + stats["commit_forwards"] == len(spied)
    assert stats["blocks_committed"] == stats["commit_forwards"]


def _commits(trace, n_forwards):
    """Which of the engine's forwards were commit passes: one after the
    last denoising step of each block."""
    out = []
    for i, (start, _, _, _) in enumerate(trace):
        out.append(False)
        if i + 1 == len(trace) or trace[i + 1][0] != start:
            out.append(True)
    return out[:n_forwards]


def test_a_commit_pass_is_what_the_next_block_reads(tiny, monkeypatch):
    """A program that skips the commit pass — the cache keeps the K/V of
    the block's last denoising step, masks among its inputs — serves
    other logits from the second block on."""
    cfg, model, params = tiny
    prompt = _prompt(8)
    transfer, seen = engine_mod._transfer, []

    def skipping(step, logits, mask_token):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        out = transfer(step, logits, mask_token)
        # A block whose last mask fell opens the next at once.
        final = out["report"][:, -1] > 0
        return dict(
            out, positions=out["positions"] + B * final,
            masked=out["masked"] | final[:, None],
            tokens=jnp.where(final[:, None], mask_token, out["tokens"]))

    monkeypatch.setattr(engine_mod, "_transfer", skipping)
    eng = _engine(model, params)
    eng.start(0, prompt, _static(12, 2))
    for _ in range(4):
        eng.step()
    jax.effects_barrier()
    sound = ref.generate(ref.seed_key(SEED), prompt, ref.sizes(cfg),
                         max_new_tokens=12, denoising_steps=2,
                         rule="static")[2]
    np.testing.assert_allclose(seen[0][0], sound[0][2], atol=ATOL)
    assert float(np.abs(seen[2][0] - sound[2][2]).max()) > 20 * ATOL


# --- the transfer rules on made logits ---------------------------------------

def _made_step(logits, masked, steps, denoise, dynamic, threshold=0.9):
    rows = logits.shape[0]
    return {"key": jax.random.PRNGKey(0),
            "tokens": jnp.full((rows, B), MASK, jnp.int32),
            "masked": jnp.asarray(masked), "active": jnp.ones(rows, bool),
            "chosen": jnp.full((rows, B), -1, jnp.int32),
            "steps": jnp.asarray(steps, jnp.int32),
            "positions": jnp.zeros(rows, jnp.int32),
            "temps": jnp.zeros(rows), "topks": jnp.zeros(rows, jnp.int32),
            "denoise": jnp.asarray(denoise, jnp.int32),
            "dynamic": jnp.asarray(dynamic),
            "threshold": jnp.full(rows, threshold, jnp.float32)}


def _peaked(rng, peaks):
    """Logits ``[B, 32]`` whose best token at position ``i`` has about
    the confidence ``peaks[i]``."""
    lg = 0.01 * rng.standard_normal((B, 32))
    for i, p in enumerate(peaks):
        lg[i, rng.integers(32)] = np.log(p / (1 - p) * 31)
    return lg


@pytest.mark.parametrize("name, peaks, masked, t, T, dynamic", [
    ("static_takes_the_k_highest", [.3, .8, .5, .6], [1, 1, 1, 1], 0, 2, 0),
    ("static_second_step", [.3, .8, .5, .6], [1, 0, 1, 0], 1, 2, 0),
    ("three_steps_take_two_first", [.3, .8, .5, .6], [1, 1, 1, 1], 0, 3, 0),
    ("dynamic_takes_all_over_the_threshold",
     [.95, .97, .5, .93], [1, 1, 1, 1], 0, 4, 1),
    ("dynamic_falls_back_to_k", [.95, .5, .6, .7], [1, 1, 1, 1], 0, 2, 1),
    ("dynamic_with_none_over", [.4, .5, .6, .7], [1, 1, 1, 1], 0, 4, 1),
    ("a_prompts_tail_is_no_candidate", [.99, .99, .5, .6], [0, 0, 1, 1],
     0, 4, 1),
    ("equal_confidences_take_the_earlier", [.5, .5, .5, .5], [1, 1, 1, 1],
     0, 4, 0),
])
def test_a_transfer_is_the_references(name, peaks, masked, t, T, dynamic):
    rng = np.random.default_rng(len(name))
    lg = _peaked(rng, peaks)
    if "equal" in name:
        lg[:] = lg[0]
    masked = np.asarray(masked, bool)
    out = engine_mod._transfer(
        _made_step(lg[None], masked[None], [t], [T], [bool(dynamic)]),
        jnp.asarray(lg[None], jnp.float32), MASK)
    shifted = lg - lg.max(-1, keepdims=True)
    conf = np.exp(shifted.max(-1)) / np.exp(shifted).sum(-1)
    take = ref.transfer(conf, masked, ref.transfer_count(B, T, t),
                        "dynamic" if dynamic else "static", 0.9)
    assert take.any()
    np.testing.assert_array_equal(np.asarray(out["masked"][0]),
                                  masked & ~take)
    np.testing.assert_array_equal(
        np.asarray(out["tokens"][0]), np.where(take, lg.argmax(-1), MASK))
    np.testing.assert_array_equal(np.asarray(out["chosen"][0]),
                                  np.where(take, t, -1))
    assert int(out["report"][0, -1]) == int(not (masked & ~take).any())


def test_generate_on_made_logits_runs_the_dynamic_rule_to_its_end():
    """The plain loop under the dynamic rule, logits made so that one
    position in two is confident: blocks end after one to four steps."""
    rng = np.random.default_rng(3)
    s = {"B": B, "mask": MASK}
    table = np.stack([_peaked(rng, rng.choice([.5, .97], B))
                      for _ in range(16)]).reshape(64, 32)

    def logits_fn(tokens):
        return jnp.asarray(table[None, :tokens.shape[1]])

    tokens, steps, trace = ref.generate(
        None, [1, 2, 3, 4, 5], s, max_new_tokens=40, rule="dynamic",
        logits_fn=logits_fn)
    assert len(tokens) == len(steps) == 40
    per_block = {}
    for start, t, _, _ in trace:
        per_block[start] = t + 1
    assert min(per_block.values()) < 4 and max(per_block.values()) > 1
    assert tokens == [int(table[5 + i].argmax()) for i in range(40)]


def test_sampled_rows_draw_from_their_own_logits(eng):
    """A row with a temperature: tokens of the vocabulary, and not the
    greedy ones throughout."""
    prompt, before = _prompt(8), eng.kv_stats()
    eng.start(0, prompt, _static(24, 2))
    eng.start(1, prompt, _static(24, 2, temperature=2.0))
    greedy, sampled = [], []
    while len(sampled) < 24:
        out = eng.step()
        greedy += list(out[0])
        sampled += list(out[1])
    assert all(0 <= t < VOCAB for t in sampled)
    assert sampled != greedy
    assert _grown(eng, before)["sampling_steps"] > 0


# --- the step state ----------------------------------------------------------

def _assert_device_is_mirror(eng):
    device = jax.device_get(eng._step_state)
    snap = eng._slot_snapshot()
    mirror = dict(snap[7], positions=snap[1], active=snap[0], temps=snap[2],
                  topks=snap[3])
    mirror.pop("final")
    assert set(device) == set(mirror) | {"key"}
    for field, want in mirror.items():
        np.testing.assert_array_equal(device[field], want, err_msg=field)


def test_a_step_yields_none_two_and_four_tokens_to_rows_at_once(eng):
    """Rows in different phases share the step; a steady step uploads
    nothing; the device's state is the host's mirror after every one."""
    before = eng.kv_stats()
    eng.start(0, _prompt(10), _static(12, 1))    # a tail of two, one step
    eng.start(1, _prompt(8), _static(12, 1))     # a whole block, one step
    eng.start(2, _prompt(12), _static(12, 2))    # two steps a block
    out = eng.step()
    assert [len(out[s]) for s in range(3)] == [2, 4, 0]
    assert out[0].steps == [0, 0] and out[2].steps == []
    _assert_device_is_mirror(eng)
    uploads = eng.kv_stats()["step_state_uploads"]
    for _ in range(6):
        eng.step()
        _assert_device_is_mirror(eng)
    assert eng.kv_stats()["step_state_uploads"] == uploads
    stats = _grown(eng, before)
    # Seven steps: slots 0 and 1 made a block every second one, slot 2
    # every third.
    assert stats["block_steps"] == 7
    assert stats["tokens_final"] == (2 + 3 * 4) + 4 * 4 + 2 * 4


def test_forwards_a_block_are_its_steps_and_one(eng):
    for steps in (1, 2, 4):
        before = eng.kv_stats()
        eng.start(0, _prompt(8), _static(64, steps))
        for _ in range(3 * (steps + 1)):
            eng.step()
        eng.release(0)
        stats = _grown(eng, before)
        assert stats["blocks_committed"] == 3
        assert (stats["denoise_forwards"] + stats["commit_forwards"]) \
            / stats["blocks_committed"] == steps + 1
        assert stats["tokens_final"] == 12
        assert stats["paged_live_rows"] == 3 * (steps + 1)


def test_a_block_step_sends_the_next_steps_table_behind_its_dispatch(eng):
    """A committing row opens its next block four positions on, here a
    new cache block: the step that commits allots it and sends the
    table while the device computes (``args.table_ahead``), and the
    step after it has nothing left to send."""
    assert trace_mod.enabled()
    before = eng.kv_stats()
    eng.start(0, _prompt(8), _static(64, 1))
    n = 8
    for _ in range(n):
        eng.step()
    spans = [s["args"] for s in trace_mod.recent(
        "hvd_tpu_engine_decode", n, eng._born_us)]
    # Denoise, commit, denoise, ...: the first step follows a bind.
    assert [a["commit_rows"] for a in spans] == [0, 1] * (n // 2)
    assert [a.get("table_ahead", 0) for a in spans] == [0, 1] * (n // 2)
    assert [a["uploads"] for a in spans[1:]] == [0] * (n - 1)
    stats = _grown(eng, before)
    assert stats["table_uploads_ahead"] == n // 2
    np.testing.assert_array_equal(
        jax.device_get(eng._table_device["full"]), eng._tables["full"])
    _assert_device_is_mirror(eng)


def test_a_table_is_not_sent_ahead_past_the_cache(tiny):
    """The last block a request may open ends where the cache does."""
    _, model, params = tiny
    eng = _engine(model, params, max_seq_len=16, prefill_buckets=(16,))
    eng.start(0, _prompt(8), _static(64, 1))
    for _ in range(2):              # the block at 8, and its commit
        eng.step()
    assert eng.kv_stats()["table_uploads_ahead"] == 1
    assert not eng.slot_full(0)
    out = eng.step()                # the block at 12 ends the cache
    assert len(out[0]) == 4 and eng.slot_full(0)
    eng.step()                      # its commit opens no other
    assert eng.kv_stats()["table_uploads_ahead"] == 1


def test_the_decode_span_says_what_its_rows_did(eng):
    assert trace_mod.enabled()
    eng.start(0, _prompt(8), _static(12, 1))
    eng.start(1, _prompt(8), _static(12, 2))
    for _ in range(2):
        eng.step()
    first, second = (s["args"] for s in trace_mod.recent(
        "hvd_tpu_engine_decode", 2, eng._born_us))
    assert (first["denoise_rows"], first["commit_rows"],
            first["tokens_final"]) == (2, 0, 4)
    assert (second["denoise_rows"], second["commit_rows"],
            second["tokens_final"]) == (1, 1, 4)


def test_a_slot_taken_again_mid_block_holds_no_mask_state(tiny, eng):
    """Released in the middle of a block's denoising and bound again:
    the new request is served as if the slot had never held another."""
    cfg = tiny[0]
    eng.start(0, _prompt(9), _static(20, 4))
    eng.start(1, _prompt(12), _static(20, 4))
    for _ in range(6):           # slot 0: one block done, the next half
        eng.step()
    assert eng._slot_snapshot()[7]["masked"][0].any()
    eng.release(0)
    assert not any(v[0].any() for v in eng._slot_snapshot()[7].values())
    prompt = _prompt(7, seed=70)
    eng.start(0, prompt, _static(9, 2))
    tokens, steps, _ = _drain(eng, 0, 9)
    want = ref.generate(ref.seed_key(SEED), prompt, ref.sizes(cfg),
                        max_new_tokens=9, denoising_steps=2, rule="static")
    assert (tokens, steps) == want[:2]
    _assert_device_is_mirror(eng)


def test_a_request_runs_to_the_end_of_the_cache_and_no_further(tiny):
    """``slot_full`` counts in blocks: the last block a request may
    open ends where the cache does."""
    _, model, params = tiny
    eng = _engine(model, params, max_seq_len=32, prefill_buckets=(16,))
    batcher = ContinuousBatcher(eng)
    batcher.max_new_tokens_cap = 64
    req = batcher.submit(_prompt(9), _static(64, 1))
    while not req.done.is_set():
        batcher.step()
    assert req.error is None and len(req.tokens) == 32 - 9
    # A reach that is no whole number of blocks: the last few positions
    # hold no block.
    short = _engine(model, params, max_seq_len=30, prefill_buckets=(16, 32))
    with pytest.raises(ValueError, match="leaves no room to generate"):
        short.check_prompt(28)
    assert short.check_prompt(27) == 30


# --- the scheduler -----------------------------------------------------------

def test_the_batcher_serves_answers_by_blocks(tiny, eng):
    """A prefill that yields nothing; the first token at the first
    block; a stamp and a step for every token; an answer cut at
    ``max_new_tokens`` inside a block."""
    cfg = tiny[0]
    batcher = ContinuousBatcher(eng)
    prompts = [_prompt(9), _prompt(8), _prompt(14)]
    reqs = [batcher.submit(p, _static(n, 2))
            for p, n in zip(prompts, (10, 7, 6))]
    assert batcher.step() == 0          # admitted, prefilled: no token
    assert reqs[0].admitted_at is not None and reqs[0].tokens == []
    assert reqs[0].first_token_at is None
    while not all(r.done.is_set() for r in reqs):
        batcher.step()
    for req, prompt, n in zip(reqs, prompts, (10, 7, 6)):
        want = ref.generate(ref.seed_key(SEED), prompt, ref.sizes(cfg),
                            max_new_tokens=n, denoising_steps=2,
                            rule="static")
        assert req.error is None
        assert (req.tokens, req.token_steps) == want[:2]
        assert len(req.tokens) == len(req.token_times) \
            == len(req.token_steps) == n
        assert req.first_token_at == req.token_times[0]
        assert req.admitted_at < req.first_token_at <= req.finished_at
        # A block's tokens share a stamp.
        assert len(set(req.token_times)) <= -(-n // B) + 1
    snap = batcher.snapshot()
    assert snap["tokens_out"] == 23 and snap["tokens_final"] >= 23
    assert eng.free_slots() == [0, 1, 2]
    assert snap["kv_prefix_hits_total"] == 0


def test_an_autoregressive_model_keeps_no_token_steps():
    cfg = GPTConfig(vocab_size=64, n_layer=1, n_head=2, d_model=16, d_ff=32,
                    max_seq_len=32)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0), _tokens(4) % 64)["params"]
    batcher = ContinuousBatcher(InferenceEngine(
        model, params, max_slots=1, prefill_buckets=(8,), kv_block=4))
    req = batcher.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
    while not req.done.is_set():
        batcher.step()
    assert len(req.tokens) == 4 and req.token_steps == []


def test_a_schedule_the_model_cannot_run_is_refused_at_admission(eng):
    batcher = ContinuousBatcher(eng)
    with pytest.raises(ValueError, match="denoising_steps 5 is not 1 to"):
        batcher.submit(_prompt(8), SamplingParams(denoising_steps=5))
    with pytest.raises(ValueError, match="unknown transfer rule"):
        batcher.submit(_prompt(8), SamplingParams(transfer="random"))
    assert batcher.queue_depth() == 0
    req = batcher.submit(_prompt(8), SamplingParams(max_new_tokens=4))
    while not req.done.is_set():       # the defaults: T = B, dynamic at 0.9
        batcher.step()
    assert len(req.tokens) == len(req.token_steps) == 4
    assert sorted(req.token_steps) == [0, 1, 2, 3]   # nothing passes 0.9


# --- what such a model refuses -----------------------------------------------

def test_prefixes_are_not_shared(eng):
    prompt = _prompt(12)
    eng.start(0, prompt, _static(4, 1))
    assert eng.prefix_probe(prompt) == 0
    eng.start(1, prompt, _static(4, 1))
    assert eng.prefix_hit_tokens(1) == 0
    assert eng.kv_stats()["kv_prefix_hits_total"] == 0


def test_speculation_is_refused_in_one_sentence(tiny):
    _, model, params = tiny
    with pytest.raises(ValueError, match="speculative decoding of a model "
                                         "that generates by blocks"):
        _engine(model, params, drafter=(model, params))


def test_tensor_parallelism_is_refused_in_one_sentence(tiny):
    _, model, params = tiny
    with pytest.raises(ValueError, match="tensor-parallel serving of a "
                                         "model that generates by blocks"):
        _engine(model, params, tp=2)


@pytest.mark.parametrize("cache", ["dense", "state"])
def test_other_caches_are_refused_in_one_sentence(tiny, cache):
    _, model, params = tiny
    with pytest.raises(ValueError, match="served from the paged cache|"
                                         "does not fit this model"):
        _engine(model, params, kv_cache=cache)


def test_a_cache_block_holds_whole_blocks_of_the_model(tiny):
    _, model, params = tiny
    with pytest.raises(ValueError, match="does not hold whole blocks"):
        _engine(model, params, kv_block=6)


def test_preemption_and_resume_are_refused_in_one_sentence(eng):
    prompt = _prompt(8)
    eng.start(0, prompt, _static(8, 1))
    out = eng.step()[0]
    assert not eng.can_resume(len(prompt), len(out))
    with pytest.raises(RuntimeError, match="is not preempted, resumed or "
                                           "migrated yet: preempt_slot"):
        eng.preempt_slot(0, prompt, list(out))
    with pytest.raises(RuntimeError, match="resume_slot carries a request"):
        eng.resume_slot(1, prompt, list(out), _static(8, 1))


def test_migration_frames_are_refused_in_one_sentence(eng):
    eng.start(0, _prompt(8), _static(8, 1))
    with pytest.raises(RuntimeError, match="export_slot_kv carries a "
                                           "request as its tokens so far"):
        eng.export_slot_kv(0)
    with pytest.raises(RuntimeError, match="import_slot_kv carries"):
        eng.import_slot_kv(1, _prompt(8), None, None, 0, _static(8, 1))
