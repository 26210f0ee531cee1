"""Power retention and the state cache it is served from.

The oracle throughout is ``hvdbench/reference/brumby.py``: the plain
float32 quadratic form with no cache, no chunks and no state, which
imports nothing of the program.  Seeded random weights at a small size
(2 layers, width 64, 4 query / 2 KV heads of 16, vocabulary 97); the
benchmark holds the same comparison at the published widths on the
chip.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from hvdbench.models import brumby as family          # noqa: E402
from hvdbench.reference import brumby as ref           # noqa: E402
from horovod_tpu.models import GPT, GPTConfig          # noqa: E402
from horovod_tpu.models.transformer import (           # noqa: E402
    KVKind, cache_kinds, init_kv_cache, init_state_cache)
from horovod_tpu.ops import retention                  # noqa: E402
from horovod_tpu.serve import (ContinuousBatcher,      # noqa: E402
                               InferenceEngine, SamplingParams)

CONFIG = {
    "name": "brumby-tiny", "vocab_size": 97, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "hidden_act": "silu",
    "tie_word_embeddings": False,
    "run": {"activation_dtype": "float32", "param_dtype": "float32"},
}
SEED = 2**31 + 5
SIZES = ref.sizes(CONFIG)
KEY = ref.seed_key(SEED)


@pytest.fixture(scope="module")
def model():
    return family.build_model(CONFIG, "full")


@pytest.fixture(scope="module")
def params():
    return family.make_params(CONFIG, SEED)


def _tokens(n, stream=0):
    return np.random.default_rng([7, stream]).integers(
        0, CONFIG["vocab_size"], n).tolist()


def _engine(model, params, **kw):
    kw.setdefault("prefill_buckets", (16, 64))
    return InferenceEngine(model, params, seed=3, **kw)


def _greedy(engine, slot, prompt, steps):
    out = [engine.start(slot, prompt, SamplingParams(max_new_tokens=999))]
    for _ in range(steps):
        out.append(engine.step()[slot][0])
    return out


def _gap_to_reference(prompt, served):
    """Widest gap by which a served token's reference logit lies below
    the reference's best at its position."""
    gaps, _ = ref.served_token_gaps(KEY, [(prompt, served)], SIZES,
                                    pad_to=16)
    return max(gaps)


# --- (a) the operator's three forms ------------------------------------------

def _operands(T=37, B=2, H=4, K=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, T, H, d))
    k = jax.random.normal(ks[1], (B, T, K, d))
    v = jax.random.normal(ks[2], (B, T, K, d))
    log_g = jax.nn.log_sigmoid(2.0 * jax.random.normal(ks[3], (B, T, K))
                               + 1.0)
    return q, k, v, log_g


def test_the_feature_map_squares_the_dot_product():
    a, b = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 16))
    got = jnp.sum(retention.expand(a) * retention.expand(b), axis=(-2, -1))
    np.testing.assert_allclose(got, jnp.sum(a * b, -1) ** 2, rtol=1e-5)
    assert retention.expand(a).shape == (5, 9, 16)
    with pytest.raises(ValueError, match="even head size"):
        retention.feature_rows(15)


@pytest.mark.parametrize("chunk", [8, 16, 37, 128])
def test_chunked_equals_quadratic(chunk, monkeypatch):
    """37 tokens: 8 and 16 do not divide the length."""
    monkeypatch.setattr(retention, "CHUNK", chunk)
    q, k, v, log_g = _operands()
    want = retention.retention_quadratic(q, k, v, log_g)
    got, _ = retention.retention_chunked(q, k, v, log_g)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_recurrent_equals_chunked_and_quadratic(monkeypatch):
    monkeypatch.setattr(retention, "CHUNK", 8)
    q, k, v, log_g = _operands()
    B, T, H, d = q.shape
    want = retention.retention_quadratic(q, k, v, log_g)
    _, (S_c, z_c) = retention.retention_chunked(q, k, v, log_g)
    s_shape, z_shape = retention.state_shapes(B, k.shape[2], d)
    state = (jnp.zeros(s_shape), jnp.zeros(z_shape))
    step = jax.jit(retention.retention_step)
    outs = []
    for t in range(T):
        o, state = step(q[:, t], k[:, t], v[:, t], log_g[:, t], state)
        outs.append(o)
    np.testing.assert_allclose(state[0], S_c, atol=1e-5)
    np.testing.assert_allclose(state[1], z_c, atol=1e-5)
    got = np.asarray(jnp.stack(outs, axis=1))
    # The read-out sums d * (d/2 + 1) signed products to a square: where
    # (q . k)^2 is small against |q|^2 |k|^2 the sum cancels, and a few
    # rows lose a digit.  The median row is at float32's own precision.
    assert np.median(np.abs(got - want)) < 1e-6
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("valid", [
    pytest.param([False] * 4, id="none"),
    pytest.param([True, False, False, False], id="first_only"),
    pytest.param([False, False, False, True], id="last_only"),
    pytest.param([False, True, False, True], id="alternating"),
    pytest.param([True] * 4, id="all"),
    pytest.param(None, id="no_mask"),
])
def test_the_step_kernel_equals_the_plain_step(valid):
    """The Pallas kernel (interpreted here; compiled for the chip in
    ``test_tpu_compile.py``) against the same step in ``jax.numpy``:
    the state to float32's precision; a row without a request is not
    visited — its state comes back bit for bit, its read-out is zero
    and not whatever lay in memory nobody wrote — whichever rows those
    are, and with no row valid the one row the grid still visits is the
    identity; the read-out equal to the contraction of the same
    operands rounded to bfloat16, which is what the kernel's MXU
    contraction takes (and XLA's default precision on the chip)."""
    B = 4
    q, k, v, log_g = _operands(T=6, B=B)
    s_shape, z_shape = retention.state_shapes(B, 2, 16)
    # Every row begins with a state, so that a row left alone shows.
    began = tuple(jax.random.normal(key, shape) for key, shape in zip(
        jax.random.split(jax.random.PRNGKey(1)), (s_shape, z_shape)))
    plain = kern = began
    mask = None if valid is None else jnp.asarray(valid)
    step = jax.jit(functools.partial(retention.retention_step,
                                     interpret=True))
    for t in range(6):
        args = (q[:, t], k[:, t], v[:, t], log_g[:, t])
        _, plain = retention.retention_step(*args, plain, mask)
        out, kern = step(*args, kern, mask)
        np.testing.assert_allclose(kern[0], plain[0], atol=1e-6)
        np.testing.assert_allclose(kern[1], plain[1], atol=1e-6)
        assert bool(jnp.isfinite(out).all())
    # One program whatever the rows: ``valid`` is a traced value.
    assert step._cache_size() == 1
    visited = [True] * B if valid is None else valid
    for row, seen in enumerate(visited):
        if seen:
            assert float(jnp.max(jnp.abs(kern[0][row] - began[0][row]))) > 0.1
        else:
            np.testing.assert_array_equal(kern[0][row], began[0][row])
            np.testing.assert_array_equal(kern[1][row], began[1][row])
            np.testing.assert_array_equal(out[row], 0.0)
    # The read-out, on a state that holds six tokens.
    g = jnp.exp(log_g[:, 5])
    phi_k = retention.expand(k[:, 5])
    phi_q = retention.expand(q[:, 5].reshape(B, 2, 2, 16), 1.0 / 16)
    S_new, num = retention._step_pallas(g, v[:, 5], phi_k, phi_q, plain[0],
                                        mask, interpret=True)
    want_S = (g[..., None, None, None] * plain[0]
              + v[:, 5][:, :, None, :, None] * phi_k[:, :, :, None, :])
    rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    want = jnp.einsum("bkgri,bkrvi->bkgv", rounded(phi_q), rounded(want_S))
    if valid is not None and not any(valid):
        visited = [True] + [False] * (B - 1)     # the one row it visits
    rows = np.flatnonzero(visited)
    np.testing.assert_allclose(S_new[rows], want_S[rows], atol=1e-6)
    np.testing.assert_allclose(num[rows], want[rows], rtol=1e-4, atol=1e-5)
    idle = np.flatnonzero(~np.asarray(visited))
    np.testing.assert_array_equal(S_new[idle], plain[0][idle])


def test_chunked_carries_a_state_and_skips_what_is_not_valid(monkeypatch):
    """Two chunks with the state handed over equal one run; padding in
    the middle of the second neither decays nor enters the state."""
    monkeypatch.setattr(retention, "CHUNK", 8)
    q, k, v, log_g = _operands(T=30)
    want, want_state = retention.retention_chunked(q, k, v, log_g)
    _, state = retention.retention_chunked(q[:, :11], k[:, :11], v[:, :11],
                                           log_g[:, :11])
    pad = lambda x: jnp.concatenate(                         # noqa: E731
        [x[:, 11:], jnp.full_like(x[:, :5], 3.0)], axis=1)
    valid = jnp.arange(24)[None] < jnp.full((2, 1), 19)
    got, got_state = retention.retention_chunked(
        pad(q), pad(k), pad(v), -jnp.abs(pad(log_g)), state, valid)
    np.testing.assert_allclose(got[:, :19], want[:, 11:], atol=1e-5)
    for a, b in zip(got_state, want_state):
        np.testing.assert_allclose(a, b, atol=1e-5)
    # A whole chunk of padding (24 tokens in chunks of 4, the last five
    # not valid: chunk 5 is skipped) leaves the state where it was.
    monkeypatch.setattr(retention, "CHUNK", 4)
    _, skipped = retention.retention_chunked(
        pad(q), pad(k), pad(v), -jnp.abs(pad(log_g)), state, valid)
    for a, b in zip(skipped, want_state):
        np.testing.assert_allclose(a, b, atol=1e-5)


# --- (b) the model's forward against the reference ----------------------------

def test_no_cache_forward_equals_the_reference(model, params):
    tokens = jnp.asarray([_tokens(50), _tokens(50, 1)], jnp.int32)
    got = model.apply({"params": params}, tokens)
    want = ref.logits(KEY, tokens, SIZES)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_a_retention_model_has_no_positional_table(params):
    assert "pos_embed" not in params
    assert set(params["block_0"]) == {"ln1", "ln2", "mlp", "retn"}
    assert set(params["block_0"]["retn"]) == {
        "q", "k", "v", "gate", "out", "q_norm", "k_norm"}
    assert set(params["block_0"]["mlp"]) == {"gate", "up", "down"}


def test_prefill_then_decode_logits_at_every_position(model, params):
    """A padded prefill bucket, then decoding through the state: every
    position's logits equal the reference's full forward, so padding
    never entered the state."""
    seq = _tokens(60)
    want = ref.logits(KEY, jnp.asarray([seq], jnp.int32), SIZES)[0]
    n, L = 23, 32
    padded = jnp.zeros((1, L), jnp.int32).at[0, :n].set(
        jnp.asarray(seq[:n]))
    caches = [dict(c, valid=(jnp.arange(L) < n)[None])
              for c in init_state_cache(model.config, 1)]
    logits, state = model.apply(
        {"params": params}, padded, kv_caches=caches,
        positions=jnp.arange(L)[None])
    np.testing.assert_allclose(logits[0, :n], want[:n], atol=1e-4)
    step = jax.jit(lambda st, tok, pos: model.apply(
        {"params": params}, tok, kv_caches=st, positions=pos))
    for t in range(n, 60):
        logits, state = step(state, jnp.asarray([[seq[t]]]),
                             jnp.asarray([[t]]))
        np.testing.assert_allclose(logits[0, 0], want[t], atol=1e-4)


def test_logit_rows_gives_one_position_a_row(model, params):
    tokens = jnp.asarray([_tokens(20), _tokens(20, 1)], jnp.int32)
    full = model.apply({"params": params}, tokens)
    rows = jnp.asarray([4, 19])
    one = model.apply({"params": params}, tokens, logit_rows=rows)
    assert one.shape == (2, 1, CONFIG["vocab_size"])
    np.testing.assert_allclose(one[:, 0], full[jnp.arange(2), rows],
                               atol=1e-5)


def test_attention_with_the_same_vocabulary_decodes_through_its_cache():
    """RMSNorm, rotary positions, grouped KV heads of a stated size,
    q/k norm and the gated feed-forward under the *attention* mixer:
    prefill and decode through the dense cache equal the no-cache
    forward."""
    cfg = GPTConfig(vocab_size=97, n_layer=2, n_head=4, n_kv_head=2,
                    head_dim=16, d_model=48, d_ff=96, max_seq_len=64,
                    norm="rmsnorm", positions="rope", qk_norm=True,
                    mlp="swiglu", dtype=jnp.float32)
    m = GPT(cfg)
    tokens = jnp.asarray([_tokens(31)], jnp.int32)
    p = m.init(jax.random.PRNGKey(0), tokens)["params"]
    assert p["block_0"]["attn"]["qkv"]["kernel"].shape == (48, (4 + 4) * 16)
    # Two KV heads of 16: rows of 32 for keys and for values, no window.
    assert cache_kinds(cfg) == (KVKind(32, 32, 0),) * 2
    want = m.apply({"params": p}, tokens)
    kv = init_kv_cache(cfg, 1, 64)
    assert kv[0]["k"].shape == (1, 64, 2, 16)
    got, kv = m.apply({"params": p}, tokens[:, :30], kv_caches=kv,
                      positions=jnp.arange(30)[None])
    np.testing.assert_allclose(got, want[:, :30], atol=1e-5)
    got, _ = m.apply({"params": p}, tokens[:, 30:], kv_caches=kv,
                     positions=jnp.asarray([[30]]))
    np.testing.assert_allclose(got[:, 0], want[:, 30], atol=1e-5)
    paged = InferenceEngine(m, p, max_slots=2, prefill_buckets=(32,),
                            seed=0)
    dense = InferenceEngine(m, p, max_slots=2, prefill_buckets=(32,),
                            kv_cache="dense", seed=0)
    prompt = _tokens(19)
    assert _greedy(paged, 0, prompt, 12) == _greedy(dense, 0, prompt, 12)


def test_a_per_layer_mixer_is_checked():
    cfg = GPTConfig(n_layer=2, mixer=("attention", "retention"))
    assert cache_kinds(cfg) == (KVKind(768, 768, 0), "state")
    with pytest.raises(ValueError, match="mixer must be"):
        GPTConfig(n_layer=2, mixer=("attention",)).mixers
    with pytest.raises(ValueError, match="mixer must be"):
        GPTConfig(n_layer=2, mixer="convolution").mixers


# --- (c) the engine: prefill, then decode through the state -------------------

@pytest.mark.parametrize("n_prompt,bucket", [(11, 16), (37, 64)])
def test_engine_serves_the_references_tokens(model, params, n_prompt,
                                             bucket):
    """A prompt that does not fill its bucket, then 40 decode steps:
    every served token is the reference's best at its position, to
    1e-4 in the logits."""
    engine = _engine(model, params)
    assert engine.bucket_for(n_prompt) == bucket
    prompt = _tokens(n_prompt)
    served = _greedy(engine, 2, prompt, 40)
    assert len(served) == 41
    assert _gap_to_reference(prompt, served) < 1e-4


def test_two_slots_at_different_depths_in_one_step(model, params):
    engine = _engine(model, params)
    a, b = _tokens(9), _tokens(50, 1)
    out_a = [engine.start(1, a, SamplingParams(max_new_tokens=99))]
    for _ in range(7):
        out_a.append(engine.step()[1][0])
    out_b = [engine.start(6, b, SamplingParams(max_new_tokens=99))]
    for _ in range(12):
        got = engine.step()
        assert sorted(got) == [1, 6]
        out_a.append(got[1][0])
        out_b.append(got[6][0])
    alone = _engine(model, params)
    assert out_a == _greedy(alone, 0, a, 19)
    alone.release(0)
    assert out_b == _greedy(alone, 0, b, 12)
    assert _gap_to_reference(a, out_a) < 1e-4
    assert _gap_to_reference(b, out_b) < 1e-4


def test_a_released_slot_reused_equals_a_fresh_engine(model, params):
    engine = _engine(model, params)
    _greedy(engine, 0, _tokens(40, 3), 9)
    engine.release(0)
    assert engine.free_slots() == list(range(8))
    prompt = _tokens(13, 4)
    assert (_greedy(engine, 0, prompt, 15)
            == _greedy(_engine(model, params), 0, prompt, 15))
    assert engine.kv_stats()["state_resets"] == 2


def test_the_engine_counts_the_rows_a_step_visited(model, params,
                                                   monkeypatch):
    """Two of four slots busy for three steps, then one released and
    five steps more, through the step's kernel (interpreted): the
    tokens are the plain step's, ``state_slots_touched`` is the mean
    number of rows a step's kernel visited — the rows that held a
    request, never the slots there are — ``state_slots_skipped`` the
    rest of the slots, every decode span names its rows, and the
    occupancy built no second decode program."""
    from horovod_tpu.obs import trace

    a, b = _tokens(9), _tokens(12, 1)
    want_a = _greedy(_engine(model, params, max_slots=4), 0, a, 8)
    step = retention.retention_step
    monkeypatch.setattr(retention, "retention_step",
                        lambda *args: step(*args, interpret=True))
    engine = _engine(model, params, max_slots=4)
    assert engine.kv_stats()["state_slots_touched"] == 0.0
    assert engine.kv_stats()["state_slots_skipped"] == 0.0
    out_a = [engine.start(0, a, SamplingParams(max_new_tokens=99))]
    engine.start(2, b, SamplingParams(max_new_tokens=99))
    for _ in range(3):
        out_a.append(engine.step()[0][0])
    engine.release(2)
    for _ in range(5):
        out_a.append(engine.step()[0][0])
    assert out_a == want_a
    stats = engine.kv_stats()
    assert stats["decode_steps"] == 8
    assert stats["state_slots_touched"] == (3 * 2 + 5 * 1) / 8
    assert stats["state_slots_skipped"] == 4 - (3 * 2 + 5 * 1) / 8
    spans = [s for s in trace.snapshot()
             if s["name"] == "hvd_tpu_engine_decode"][-8:]
    assert [s["args"]["rows"] for s in spans] == [2, 2, 2, 1, 1, 1, 1, 1]
    assert engine.trace_counts["decode"] == 1
    assert engine._decode_fn._cache_size() == 1


def test_each_program_is_built_once(model, params):
    """Every key a program is given is the engine's own kind (the
    first, each split of it, and the one a resume restores); a key of
    another kind — committed where the others are not — would build the
    program a second time, inside some request's wait."""
    engine = _engine(model, params)
    prompt = _tokens(9)
    emitted = _greedy(engine, 0, prompt, 3)
    rng = engine.preempt_slot(0, prompt, emitted)
    engine.resume_slot(0, prompt, emitted, SamplingParams(), rng)
    engine.step()
    engine.release(0)
    _greedy(engine, 0, _tokens(9, 1), 3)
    assert engine._prefill_fns[16]._cache_size() == 1
    assert engine._decode_fn._cache_size() == 1
    assert engine.trace_counts == {"prefill_16": 1, "decode": 1}


def test_preempt_and_resume_equal_the_uninterrupted_run(model, params):
    """The resumed sequence (prompt 50 + 29 emitted) is longer than the
    largest bucket, so it is recomputed in two chunks with the state
    carried, into another slot."""
    prompt = _tokens(50, 5)
    whole = _greedy(_engine(model, params), 0, prompt, 45)
    engine = _engine(model, params)
    emitted = _greedy(engine, 0, prompt, 29)
    assert engine.can_resume(len(prompt), len(emitted))
    rng = engine.preempt_slot(0, prompt, emitted)
    assert engine.free_slots() == list(range(8))
    hit = engine.resume_slot(4, prompt, emitted,
                             SamplingParams(max_new_tokens=99), rng)
    assert hit == 0 and engine.prefix_hit_tokens(4) == 0
    rest = [engine.step()[4][0] for _ in range(16)]
    assert emitted + rest == whole


def test_the_batcher_preempts_and_resumes_over_a_state(model, params):
    """Through ``ContinuousBatcher``: a batch request is evicted for an
    interactive one and finishes with the tokens it would have had."""
    prompt = _tokens(20, 6)
    want = _greedy(_engine(model, params, max_slots=1), 0, prompt, 23)
    batcher = ContinuousBatcher(_engine(model, params, max_slots=1),
                                qos_preempt=True, qos_slo_ttft_ms=1.0)
    assert batcher.engine.prefix_probe(prompt) == 0
    victim = batcher.submit(prompt, SamplingParams(max_new_tokens=24),
                            deadline_s=0, qos_class="batch")
    for _ in range(6):
        batcher.step()
    urgent = batcher.submit(_tokens(12, 7), SamplingParams(max_new_tokens=4),
                            deadline_s=5.0, qos_class="interactive")
    for _ in range(200):
        if victim.done.is_set() and urgent.done.is_set():
            break
        batcher.step()
    assert urgent.error is None and len(urgent.tokens) == 4
    assert victim.error is None and victim.preemptions >= 1
    assert victim.tokens == want
    assert "state_bytes" in batcher.snapshot()


# --- (d) what the engine chooses, and what it refuses -------------------------

def test_the_default_engine_of_a_retention_model_holds_a_state(model,
                                                               params):
    engine = InferenceEngine(model, params, seed=1)
    assert engine.kv_mode == "state"
    assert (engine.kv_block, engine.kv_blocks) == (0, 0)
    assert engine.max_slots == 8
    assert engine.prefill_buckets == (64, 256, 1024)
    assert engine.max_seq_len == 4096       # the configuration's positions
    stats = engine.kv_stats()
    d, K, L = 16, 2, 2
    assert stats == {
        "decode_steps": 0, "sampling_steps": 0, "step_state_uploads": 0,
        "staged_uploads": 0, "runtime_pokes": 0,
        "stalled_steps": 0, "stalled_prepare": 0, "stalled_dispatch": 0,
        "stalled_fence": 0, "stalled_prefills": 0, "dispatch_ms_p50": None,
        "dispatch_ms_p99": None, "fence_ms_p50": None, "fence_ms_p99": None,
        "state_bytes": 8 * L * K * (d // 2 + 1) * d * (d + 1) * 4,
        "state_slots_touched": 0.0, "state_slots_skipped": 0.0,
        "state_resets": 0}
    assert engine.prefix_probe(_tokens(5)) == 0
    assert engine.drain_evicted_prefixes() == []
    # max_seq_len is the most positions a request may reach, not a table.
    far = InferenceEngine(model, params, max_slots=1, max_seq_len=100000,
                          prefill_buckets=(16,), seed=1)
    assert far.max_seq_len == 100000
    with pytest.raises(ValueError, match="leaves no room"):
        InferenceEngine(model, params, max_slots=1, max_seq_len=12,
                        prefill_buckets=(16,)).check_prompt(12)


@pytest.mark.parametrize("kwargs,sentence", [
    ({"kv_cache": "paged"}, "keep a retention state, not keys and values"),
    ({"kv_cache": "dense"}, "keep a retention state, not keys and values"),
    ({"tp": 2}, "tensor-parallel serving of a retention state is not "
                "built yet"),
    ({"drafter": "self"}, "speculative decoding over a retention state is "
                          "not built yet"),
])
def test_what_a_state_cache_cannot_do_yet_raises(model, params, kwargs,
                                                 sentence):
    if kwargs.get("drafter") == "self":
        kwargs = {"drafter": (model, params)}
    with pytest.raises(ValueError, match=sentence):
        InferenceEngine(model, params, max_slots=2, prefill_buckets=(16,),
                        **kwargs)


def test_a_state_has_no_migration_frame_yet(model, params):
    engine = _engine(model, params, max_slots=2)
    engine.start(0, _tokens(5), SamplingParams())
    with pytest.raises(RuntimeError, match="no migration frame yet"):
        engine.export_slot_kv(0)
    with pytest.raises(RuntimeError, match="no migration frame yet"):
        engine.import_slot_kv(1, _tokens(5), None, None, 1, SamplingParams())
    with pytest.raises(ValueError, match="requires the paged cache"):
        ContinuousBatcher(engine).adopt(
            {"prompt": _tokens(5), "tokens": [1], "sampling": {
                "max_new_tokens": 2, "temperature": 0.0, "top_k": 0,
                "stop_token": None, "spec": False}}, None, None)


def test_a_kv_model_cannot_ask_for_a_state_and_a_hybrid_is_refused():
    cfg = GPTConfig(vocab_size=97, n_layer=2, n_head=4, d_model=64,
                    d_ff=128, max_seq_len=64, dtype=jnp.float32)
    m = GPT(cfg)
    p = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    with pytest.raises(ValueError, match="keep keys and values"):
        InferenceEngine(m, p, kv_cache="state")
    with pytest.raises(ValueError, match="exceeds the model's positional "
                                         "table"):
        InferenceEngine(m, p, max_seq_len=65)
    hybrid = GPT(GPTConfig(vocab_size=97, n_layer=2, n_head=4, d_model=64,
                           d_ff=128, max_seq_len=64, positions="rope",
                           mixer=("attention", "retention")))
    with pytest.raises(ValueError, match="one kind of cache"):
        InferenceEngine(hybrid, None)


def test_the_prefill_span_names_its_cache(model, params):
    from horovod_tpu.obs import trace

    engine = _engine(model, params, max_slots=2)
    engine.start(1, _tokens(9), SamplingParams())
    spans = [s for s in trace.snapshot()
             if s["name"] == "hvd_tpu_engine_prefill"]
    args = dict(spans[-1]["args"])
    # How long the prefill's dispatch and the fence on its token took.
    assert args.pop("dispatch_us") > 0 and args.pop("fence_us") > 0
    assert args == {"slot": 1, "prompt_len": 9, "cache": "state",
                    "prefix_hit": 0, "bucket": 16}


def test_the_scopes_are_in_the_compiled_programs(model, params):
    """``hvd_tpu_retention_decode`` inside the decode program and
    ``hvd_tpu_retention_prefill`` inside a prefill program, around the
    operator and not around the projections."""
    state = init_state_cache(model.config, 1)
    decode = jax.jit(lambda st: model.apply(
        {"params": params}, jnp.zeros((1, 1), jnp.int32), kv_caches=st,
        positions=jnp.zeros((1, 1), jnp.int32))).lower(state).as_text(
            debug_info=True)
    prefill = jax.jit(lambda st: model.apply(
        {"params": params}, jnp.zeros((1, 16), jnp.int32), kv_caches=st,
        positions=jnp.arange(16)[None])).lower(state).as_text(
            debug_info=True)
    assert "hvd_tpu_retention_decode" in decode
    assert "hvd_tpu_retention_prefill" not in decode
    assert "hvd_tpu_retention_prefill" in prefill
    assert "retn/hvd_tpu_retention_decode/q" not in decode


# --- (e) GPT-2 stands where it stood ------------------------------------------
# Values recorded from the parent commit (78213ae) with this very code.

_GPT2_PATHS = [
    ("['block_0']['attn']['out']['kernel']", (64, 64)),
    ("['block_0']['attn']['qkv']['kernel']", (64, 192)),
    ("['block_0']['ln1']['bias']", (64,)),
    ("['block_0']['ln1']['scale']", (64,)),
    ("['block_0']['ln2']['bias']", (64,)),
    ("['block_0']['ln2']['scale']", (64,)),
    ("['block_0']['mlp']['down']['kernel']", (256, 64)),
    ("['block_0']['mlp']['up']['kernel']", (64, 256)),
    ("['block_1']['attn']['out']['kernel']", (64, 64)),
    ("['block_1']['attn']['qkv']['kernel']", (64, 192)),
    ("['block_1']['ln1']['bias']", (64,)),
    ("['block_1']['ln1']['scale']", (64,)),
    ("['block_1']['ln2']['bias']", (64,)),
    ("['block_1']['ln2']['scale']", (64,)),
    ("['block_1']['mlp']['down']['kernel']", (256, 64)),
    ("['block_1']['mlp']['up']['kernel']", (64, 256)),
    ("['embed']['embedding']", (97, 64)),
    ("['lm_head']['kernel']", (64, 97)),
    ("['ln_f']['bias']", (64,)),
    ("['ln_f']['scale']", (64,)),
    ("['pos_embed']", (96, 64)),
]


@pytest.fixture(scope="module")
def gpt2():
    cfg = GPTConfig(vocab_size=97, n_layer=2, n_head=4, d_model=64,
                    d_ff=256, max_seq_len=96, dtype=jnp.float32)
    m = GPT(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 97)
    return m, m.init(jax.random.PRNGKey(0), tokens)["params"], tokens


def test_gpt2_parameter_tree_is_the_parents(gpt2):
    _, p, _ = gpt2
    got = sorted((jax.tree_util.keystr(k), v.shape) for k, v in
                 jax.tree_util.tree_flatten_with_path(p)[0])
    assert got == _GPT2_PATHS
    assert cache_kinds(gpt2[0].config) == (KVKind(64, 64, 0),) * 2


def test_gpt2_logits_through_prefill_and_decode_are_the_parents(gpt2):
    m, p, tokens = gpt2
    kv = init_kv_cache(m.config, 2, 96)
    lg, kv = m.apply({"params": p}, tokens[:, :20], kv_caches=kv,
                     positions=jnp.broadcast_to(jnp.arange(20), (2, 20)))
    lg2, _ = m.apply({"params": p}, tokens[:, 20:21], kv_caches=kv,
                     positions=jnp.full((2, 1), 20))
    full = m.apply({"params": p}, tokens)
    np.testing.assert_allclose(
        lg[0, 19, :4], [-0.3620021343231201, 0.3578842282295227,
                        0.8765392303466797, -0.21872538328170776],
        atol=2e-6)
    np.testing.assert_allclose(
        lg2[1, 0, :4], [-0.09292297810316086, 0.5315787196159363,
                        0.5804149508476257, -0.2904188632965088], atol=2e-6)
    np.testing.assert_allclose(float(jnp.sum(jnp.abs(full))),
                               3463.605224609375, rtol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(jnp.abs(lg2))),
                               136.313232421875, rtol=1e-6)


def test_gpt2_engine_tokens_are_the_parents(gpt2):
    m, p, tokens = gpt2
    engine = InferenceEngine(m, p, max_slots=2, prefill_buckets=(16, 32),
                             max_seq_len=96, seed=3)
    assert engine.kv_mode == "paged"
    prompt = [int(t) for t in tokens[0, :13]]
    assert _greedy(engine, 0, prompt, 7) == [77, 11, 77, 93, 70, 33, 40, 76]
