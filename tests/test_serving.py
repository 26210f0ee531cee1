"""Inference-serving subsystem (horovod_tpu/serve/): engine decode
correctness against the full forward pass, bounded recompiles via
length buckets, continuous-batching scheduling (backpressure,
deadlines), the wire stack (server + router), and router failover under
injected ``serve.*`` faults.

The chaos class at the bottom is the ISSUE 3 acceptance drill: a
replica killed mid-decode must have its in-flight request complete on
a surviving replica with no lost or duplicated responses
(``scripts/chaos_soak.py --mode serve`` loops it over randomized
injection points)."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
import step_state_oracle
from greedy_oracle import greedy_reference as _greedy_reference
from horovod_tpu import faults
from horovod_tpu.config import parse_fault_spec
from horovod_tpu.models.transformer import GPT, GPTConfig
from horovod_tpu.serve import (
    ContinuousBatcher, InferenceEngine, InferenceServer, PromptTooLongError,
    QueueFullError, ReplicaSpec, Router, SamplingParams,
    replica_slot_groups, register_replica_process_sets,
)
from horovod_tpu.utils.retry import RetryPolicy

pytestmark = pytest.mark.serving

KEY = b"k" * 32
VOCAB = 97


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def model_and_params():
    cfg = GPTConfig(vocab_size=VOCAB, n_layer=2, n_head=2, d_model=32,
                    d_ff=64, max_seq_len=32, dtype=jnp.float32,
                    param_dtype=jnp.float32)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model_and_params, **kw):
    model, params = model_and_params
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_seq_len", 32)
    return InferenceEngine(model, params, **kw)


def _run_engine_greedy(engine, slot, prompt, n_tokens):
    toks = [engine.start(slot, prompt, SamplingParams(
        max_new_tokens=n_tokens))]
    while len(toks) < n_tokens:
        toks.extend(engine.step()[slot])   # 1 token/step (spec: more)
    engine.release(slot)
    return toks[:n_tokens]


class TestEngineDecode:
    def test_greedy_decode_matches_full_forward_argmax(self,
                                                       model_and_params):
        """The KV-cache path must agree with the cache-free full
        forward exactly under greedy sampling — the decode-correctness
        acceptance property."""
        model, params = model_and_params
        engine = _engine(model_and_params)
        for prompt in ([3, 14, 15, 92, 6], [1], list(range(10))):
            got = _run_engine_greedy(engine, 0, prompt, 6)
            want = _greedy_reference(model, params, prompt, 6)
            assert got == want, (prompt, got, want)

    def test_bucketing_bounds_recompiles(self, model_and_params):
        """Prompts of different lengths inside one bucket share a
        compiled program; only a new bucket (or the one decode program)
        traces."""
        engine = _engine(model_and_params)
        _run_engine_greedy(engine, 0, [1, 2, 3], 3)        # bucket 8
        _run_engine_greedy(engine, 0, [4, 5, 6, 7, 8], 3)  # bucket 8 again
        _run_engine_greedy(engine, 1, list(range(12)), 3)  # bucket 16
        assert engine.trace_counts == {"prefill_8": 1, "prefill_16": 1,
                                       "decode": 1}, engine.trace_counts

    def test_mesh_placed_weights_compile_each_program_once(
            self, model_and_params):
        """Weights that come from a trainer carry the mesh's sharding.
        Fresh KV state must start beside them: left uncommitted it
        returned from the first program with another sharding, and the
        first bucket compiled twice (seen on the chip as a 16 s TTFT)."""
        model, params = model_and_params
        params = jax.device_put(params, hvd.global_mesh().replicated())
        engine = _engine((model, params))
        _run_engine_greedy(engine, 0, [1, 2, 3], 3)        # bucket 8
        _run_engine_greedy(engine, 1, list(range(12)), 3)  # bucket 16
        _run_engine_greedy(engine, 0, [4, 5, 6], 3)        # bucket 8 again
        assert engine.trace_counts == {"prefill_8": 1, "prefill_16": 1,
                                       "decode": 1}, engine.trace_counts

    def test_prompt_too_long_raises(self, model_and_params):
        engine = _engine(model_and_params)
        with pytest.raises(PromptTooLongError):
            engine.start(0, list(range(17)), SamplingParams())  # > bucket 16
        with pytest.raises(PromptTooLongError):
            engine.bucket_for(100)

    def test_top_k_one_equals_greedy(self, model_and_params):
        engine = _engine(model_and_params)
        greedy = _run_engine_greedy(engine, 0, [5, 6, 7], 6)
        toks = [engine.start(0, [5, 6, 7], SamplingParams(
            max_new_tokens=6, temperature=1.3, top_k=1))]
        while len(toks) < 6:
            toks.extend(engine.step()[0])
        engine.release(0)
        assert toks == greedy

    def test_seeded_sampling_reproduces(self, model_and_params):
        def run(seed):
            engine = _engine(model_and_params, seed=seed)
            toks = [engine.start(0, [9, 8, 7], SamplingParams(
                max_new_tokens=8, temperature=0.9, top_k=20))]
            while len(toks) < 8:
                toks.extend(engine.step()[0])
            return toks

        assert run(7) == run(7)
        assert run(7) != run(8)   # 8 draws over a 20-wide top-k

    def test_slot_reuse_does_not_leak_stale_cache(self, model_and_params):
        """A released slot's stale keys must be invisible to the next
        request (the position mask is the only isolation)."""
        model, params = model_and_params
        engine = _engine(model_and_params)
        _run_engine_greedy(engine, 0, list(range(10)), 5)   # dirty the slot
        got = _run_engine_greedy(engine, 0, [2, 4, 6], 5)
        assert got == _greedy_reference(model, params, [2, 4, 6], 5)

    def test_mixed_depth_batch_decodes_independently(self,
                                                     model_and_params):
        """Continuous batching's core invariant: slots at different
        depths share one decode dispatch without cross-talk."""
        model, params = model_and_params
        engine = _engine(model_and_params)
        p0, p1 = [3, 1, 4, 1, 5], [9, 2, 6]
        t0 = engine.start(0, p0, SamplingParams(max_new_tokens=8))
        a = [t0]
        for _ in range(3):
            a.extend(engine.step()[0])   # slot 0 is 4 deep
        t1 = engine.start(1, p1, SamplingParams(max_new_tokens=4))
        b = [t1]
        for _ in range(3):
            toks = engine.step()
            a.extend(toks[0])
            b.extend(toks[1])
        assert a[:7] == _greedy_reference(model, params, p0, 7)
        assert b == _greedy_reference(model, params, p1, 4)

    def test_generation_uses_every_cache_position(self, model_and_params):
        """An uncapped generation fills the cache exactly: prompt n in
        an S-position cache yields S - n + 1 tokens (the last token
        needs no K/V write) — off-by-one here silently shrinks every
        request's budget."""
        engine = _engine(model_and_params)
        toks = [engine.start(0, [1, 2], SamplingParams(
            max_new_tokens=10 ** 6))]
        while not engine.slot_full(0):
            toks.extend(engine.step()[0])
        assert len(toks) == engine.max_seq_len - 2 + 1

    def test_timeline_records_serving_phases(self, model_and_params,
                                             tmp_path):
        path = str(tmp_path / "serve_timeline.json")
        hvd.start_timeline(path)
        try:
            engine = _engine(model_and_params)
            _run_engine_greedy(engine, 0, [1, 2, 3], 3)
        finally:
            hvd.stop_timeline()
        text = open(path).read()
        assert "hvd_tpu_engine_prefill" in text
        assert "hvd_tpu_engine_decode" in text


class TestPagedKV:
    """ISSUE 10 tentpole: block-pool paged KV under the engine API —
    token-identical to the dense oracle, COW on divergence, LRU
    eviction under pressure (never stale blocks)."""

    def test_paged_matches_dense_mixed_depth(self, model_and_params):
        """Mixed-depth batches: the paged path must agree token-for-
        token with the dense decode oracle at every interleaving."""
        dense = _engine(model_and_params, kv_cache="dense")
        paged = _engine(model_and_params, kv_cache="paged", kv_block=4)
        p0, p1 = [3, 1, 4, 1, 5], [9, 2, 6]
        out = {}
        for name, eng in (("dense", dense), ("paged", paged)):
            a = [eng.start(0, p0, SamplingParams(max_new_tokens=8))]
            for _ in range(3):
                a.extend(eng.step()[0])
            b = [eng.start(1, p1, SamplingParams(max_new_tokens=4))]
            for _ in range(3):
                toks = eng.step()
                a.extend(toks[0])
                b.extend(toks[1])
            eng.release(0)
            eng.release(1)
            out[name] = (a, b)
        assert out["paged"] == out["dense"], out

    def test_block_not_aligned_to_seq_len(self, model_and_params):
        """A block size that does not divide max_seq_len must still be
        exact (the last chain block is partially used)."""
        model, params = model_and_params
        eng = _engine(model_and_params, kv_cache="paged", kv_block=5)
        got = _run_engine_greedy(eng, 0, [7, 3, 9], 6)
        assert got == _greedy_reference(model, params, [7, 3, 9], 6)

    def test_cow_when_shared_prefix_diverges(self, model_and_params):
        """Two requests share a prompt prefix then diverge: the shared
        tail block is copy-on-write — both decode exactly, and the COW
        counter proves the copy happened (not a recompute)."""
        model, params = model_and_params
        eng = _engine(model_and_params, kv_cache="paged", kv_block=4)
        pre = [11, 12, 13, 14, 15, 16]          # 1.5 blocks
        pa, pb = pre + [1], pre + [2]
        a = _run_engine_greedy(eng, 0, pa, 5)
        assert a == _greedy_reference(model, params, pa, 5)
        stats0 = eng.kv_stats()
        b = _run_engine_greedy(eng, 1, pb, 5)
        assert b == _greedy_reference(model, params, pb, 5)
        stats1 = eng.kv_stats()
        assert stats1["kv_prefix_hits_total"] > stats0["kv_prefix_hits_total"]
        assert stats1["kv_cow_copies_total"] > stats0["kv_cow_copies_total"]

    def test_cow_between_two_live_requests(self, model_and_params):
        """A second request shares the first one's partial tail block
        WHILE the first is still decoding into it — the admission-time
        copy keeps the streams isolated and both stay exact."""
        model, params = model_and_params
        eng = _engine(model_and_params, kv_cache="paged", kv_block=4)
        pa = [5, 6, 7, 8, 9]          # tail block holds 1 prompt token
        pb = [5, 6, 7, 8, 9, 3]       # shares it, then diverges inside
        a = [eng.start(0, pa, SamplingParams(max_new_tokens=8))]
        a.extend(eng.step()[0])       # slot 0 writes INTO the tail block
        b = [eng.start(1, pb, SamplingParams(max_new_tokens=6))]
        assert eng.prefix_hit_tokens(1) == 5   # 1 full block + 1 partial
        for _ in range(4):
            toks = eng.step()
            a.extend(toks[0])
            b.extend(toks[1])
        assert a[:6] == _greedy_reference(model, params, pa, 6)
        assert b[:5] == _greedy_reference(model, params, pb, 5)
        assert eng.kv_stats()["kv_cow_copies_total"] >= 1

    def test_eviction_under_pressure_recomputes(self, model_and_params):
        """A floor-sized pool under sustained distinct-prefix traffic
        must LRU-evict the oldest cached prefix; readmitting it then
        recomputes (probe misses) and stays exact — never stale."""
        model, params = model_and_params
        eng = _engine(model_and_params, kv_cache="paged", kv_block=4,
                      kv_blocks=1 + 2 * 8)     # floor: slots=2, bps=8
        first = [40, 41, 42, 43, 44, 45, 46, 47, 48]
        got = _run_engine_greedy(eng, 0, first, 4)
        assert got == _greedy_reference(model, params, first, 4)
        assert eng.prefix_probe(first) > 0     # resident after release
        for i in range(8):                     # distinct in-vocab prefixes
            p = [(50 + 9 * i + j) % VOCAB for j in range(9)]
            _run_engine_greedy(eng, 0, p, 4)
        stats = eng.kv_stats()
        assert stats["kv_evictions_total"] > 0, stats
        assert eng.prefix_probe(first) == 0    # evicted, not stale
        again = _run_engine_greedy(eng, 0, first, 4)
        assert again == got                    # recomputed exactly

    def test_pool_budget_floor_validated(self, model_and_params):
        with pytest.raises(ValueError, match="floor"):
            _engine(model_and_params, kv_cache="paged", kv_block=4,
                    kv_blocks=8)   # < 1 + 2 slots * 8 blocks/slot

    def test_out_of_vocab_prompt_rejected_at_admission(
            self, model_and_params):
        """An out-of-vocab token embeds as NaN; in a SHARED block pool
        that NaN would outlive the request (trash/prefix blocks) and
        poison later batchmates through 0 x NaN attention sums — the
        engine must kill the poison at admission."""
        eng = _engine(model_and_params, kv_cache="paged", kv_block=4)
        with pytest.raises(ValueError, match="vocabulary"):
            eng.start(0, [1, 2, VOCAB], SamplingParams())
        with pytest.raises(ValueError, match="vocabulary"):
            eng.start(0, [-1], SamplingParams())
        b = _batcher(model_and_params)
        with pytest.raises(ValueError, match="vocabulary"):
            b.submit([1, VOCAB + 3], SamplingParams(max_new_tokens=2))
        assert b.queue_depth() == 0            # rejected before queueing

    def test_batcher_snapshot_carries_kv_and_prefix_stats(
            self, model_and_params):
        model, params = model_and_params
        b = _batcher(model_and_params,
                     engine_kw={"kv_cache": "paged", "kv_block": 4})
        pre = [21, 22, 23, 24, 25, 26, 27, 28]
        r1 = b.submit(pre + [1], SamplingParams(max_new_tokens=3))
        _pump(b, [r1])
        r2 = b.submit(pre + [2], SamplingParams(max_new_tokens=3))
        _pump(b, [r2])
        assert r2.prefix_hit_tokens >= 8       # two full blocks shared
        snap = b.snapshot()
        assert snap["prefix_hits"] == 1
        assert snap["prefix_hit_ratio"] == 0.5
        assert snap["kv_prefix_hits_total"] >= 1
        assert snap["kv_blocks_in_use"] == 0   # both released
        assert r2.tokens == _greedy_reference(model, params, pre + [2], 3)


    @staticmethod
    def _through_the_kernel(monkeypatch):
        """From here on the model's decode steps reach the paged decode
        kernel through the interpreter; returns the list its calls are
        counted in (one per layer and trace)."""
        from horovod_tpu.ops import paged_attention

        decode, calls = paged_attention.paged_decode, []

        def interpreted(*args):
            calls.append(args[1].shape)
            return decode(*args, interpret=True)

        monkeypatch.setattr(paged_attention, "paged_decode", interpreted)
        return calls

    def test_kernel_and_view_serve_the_same_tokens(self, model_and_params,
                                                   monkeypatch):
        """The engine's greedy tokens over a paged cache, decode steps
        through the kernel (interpreted) and through the view: the same
        through admissions at different depths, a prompt that fills
        whole blocks, a release beside a running request and the slot
        used again."""
        model, params = model_and_params

        def serve(eng):
            greedy = SamplingParams(max_new_tokens=16)
            a = [eng.start(0, [3, 1, 4, 1, 5], greedy)]
            for _ in range(4):                  # slot 0 alone, into block 2
                a.extend(eng.step()[0])
            b = [eng.start(1, list(range(20, 36)), greedy)]  # two blocks
            for _ in range(3):
                toks = eng.step()
                a.extend(toks[0])
                b.extend(toks[1])
            eng.release(0)                      # slot 1 runs on beside it
            b.extend(eng.step()[1])
            c = [eng.start(0, [9, 2, 6], greedy)]            # slot 0 again
            for _ in range(5):
                toks = eng.step()
                b.extend(toks[1])
                c.extend(toks[0])
            return a, b, c

        view = serve(_engine(model_and_params, kv_cache="paged",
                             kv_block=8))
        calls = self._through_the_kernel(monkeypatch)
        eng = _engine(model_and_params, kv_cache="paged", kv_block=8)
        assert serve(eng) == view
        # One trace of the decode program, a kernel a layer, on a pool
        # row of whole vectors; prefill chunks never come here.
        assert calls == [eng._pools[0]["k"].shape] * model.config.n_layer
        assert calls[0][-1] == 128
        assert view[0] == _greedy_reference(model, params, [3, 1, 4, 1, 5],
                                            len(view[0]))

    def test_kv_stats_count_the_blocks_a_decode_step_walks(
            self, model_and_params):
        """Two slots, blocks of 8, a table of 4 + 1 columns: the view
        would read 10 blocks a step.  A 5-token prompt decodes at
        positions 5, 6, 7 (one block, and one for the idle row on the
        trash block) and 8 (two, and one)."""
        from horovod_tpu.obs import trace

        trace.configure(enabled=True)
        trace.clear()
        eng = _engine(model_and_params, kv_cache="paged", kv_block=8)
        eng.start(0, [3, 1, 4, 1, 5], SamplingParams(max_new_tokens=8))
        stats = eng.kv_stats()
        assert (stats["paged_decode_steps"], stats["paged_live_blocks"],
                stats["paged_view_blocks"]) == (0, 0, 0)
        for _ in range(4):
            eng.step()
        stats = eng.kv_stats()
        assert stats["paged_decode_steps"] == 4
        assert stats["paged_live_blocks"] == 2 + 2 + 2 + 3
        assert stats["paged_view_blocks"] == 4 * 2 * 5
        walked = [s["args"]["live_blocks"] for s in trace.snapshot()
                  if s["name"] == "hvd_tpu_engine_decode"]
        assert walked == [2, 2, 2, 3]
        trace.clear()
        # A dense cache walks no table and reports none of it.
        dense = _engine(model_and_params, kv_cache="dense")
        dense.start(0, [3, 1, 4], SamplingParams(max_new_tokens=4))
        dense.step()
        assert not any(k.startswith("paged_") for k in dense.kv_stats())
        assert all("live_blocks" not in s["args"] for s in trace.snapshot()
                   if s["name"] == "hvd_tpu_engine_decode")


class TestPagedWriteThenRead:
    """ISSUE 25: the paged programs write the chunk's K/V through the
    block table first and attend over the written pool, so a donated
    pool is updated in place and nothing but the slot's own blocks (and
    the trash block) is ever written."""

    PROGRAMS = ("decode", "prefill_8", "prefill_16", "spec_verify",
                "kv_copy", "kv_import")

    @staticmethod
    def _lowerable(eng, name):
        """``(function, arguments, index of the pools)`` of one paged
        program, as the engine calls it."""
        n, cols = eng.max_slots, eng.blocks_per_slot + 1

        def i32(*shape):
            return jnp.zeros(shape, jnp.int32)

        def f32(*shape):
            return jnp.zeros(shape, jnp.float32)

        rng = jax.random.PRNGKey(0)
        params, pools = eng.params, eng._pools
        if name == "decode":
            return eng._decode_paged_impl, (
                params, pools, {"full": i32(n, cols)}, eng._step_state), 1
        if name.startswith("prefill_"):
            L = int(name.split("_")[1])
            return eng._make_paged_prefill(L).__wrapped__, (
                params, pools, {"full": i32(cols)}, i32(1, L), i32(), i32(),
                rng,
                f32(), i32()), 1
        if name == "spec_verify":
            return eng._spec_verify_impl, (
                params, pools, {"full": i32(n, cols)}, i32(n),
                i32(n, eng.spec_k),
                i32(n), f32(n), i32(n), jnp.zeros(n, bool), rng), 1
        if name == "kv_copy":
            return eng._copy_impl, (pools, i32(), i32()), 0
        cfg = eng._model.config     # the wire's row: heads, no padding
        block = jnp.zeros((len(pools), eng.kv_block,
                           cfg.kv_heads * cfg.head_size),
                          pools[0]["k"].dtype)
        return eng._import_impl, (pools, i32(), block, block), 0

    @pytest.mark.parametrize("program", PROGRAMS)
    def test_donated_pools_are_updated_in_place(self, model_and_params,
                                                program):
        """With the pools donated (the engine turns donation off on the
        CPU, so it is asked for here), the optimised HLO of every paged
        program aliases each pool to an output and holds no copy of a
        whole pool.  A gather of the pool as it came in, beside a later
        scatter into it, makes XLA keep such a copy per pool."""
        import re

        eng = _engine(model_and_params, kv_cache="paged", kv_block=4)
        fn, args, pools_at = self._lowerable(eng, program)
        hlo = jax.jit(fn, donate_argnums=(pools_at,)).lower(
            *args).compile().as_text()
        pool = eng._pools[0]["k"]
        n_pools = 2 * len(eng._pools)
        shape = ",".join(str(d) for d in pool.shape)
        copies = re.findall(
            r"^.*= \w+\[%s\]\S* copy\(.*$" % shape, hlo, re.M)
        assert not copies, copies[:2]
        header = hlo.split("\n", 1)[0]
        aliased = re.findall(
            r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
        assert len(set(aliased)) == n_pools, header[:300]

    @pytest.mark.parametrize("upto", ["prefill", "decode"])
    def test_only_the_slots_chain_and_trash_are_written(
            self, model_and_params, upto):
        """A prefill padded to its bucket (5 tokens in 8) and a decode
        step beside an empty slot write padding and the idle row's K/V
        somewhere: the trash block, and no block of anyone else."""
        from horovod_tpu.serve.kv import TRASH_BLOCK

        model, params = model_and_params
        eng = _engine(model_and_params, kv_cache="paged", kv_block=4)
        fill = jax.random.normal(jax.random.PRNGKey(7),
                                 eng._pools[0]["k"].shape)
        eng._pools = [{"k": fill + i, "v": fill - i}
                      for i in range(len(eng._pools))]
        before = [{n: np.asarray(a) for n, a in p.items()}
                  for p in eng._pools]
        prompt = [3, 1, 4, 1, 5]
        toks = [eng.start(0, prompt, SamplingParams(max_new_tokens=4))]
        if upto == "decode":
            toks.extend(eng.step()[0])
        owned = set(eng._kv.chain_blocks(0)) | {TRASH_BLOCK}
        others = [b for b in range(eng.kv_blocks) if b not in owned]
        assert others and len(owned) == 3    # two chain blocks + trash
        for was, now in zip(before, eng._pools):
            for name in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(now[name])[others], was[name][others])
        # ... and what the other blocks hold never reaches a query.
        assert toks == _greedy_reference(model, params, prompt, len(toks))


@pytest.fixture(scope="module")
def retention_model_and_params():
    """A model whose layers keep a retention state: what the engine
    serves from the ``state`` cache."""
    cfg = GPTConfig(vocab_size=VOCAB, n_layer=2, n_head=4, n_kv_head=2,
                    head_dim=16, d_model=32, d_ff=64, max_seq_len=32,
                    norm="rmsnorm", positions="rope", qk_norm=True,
                    mlp="swiglu", mixer="retention", dtype=jnp.float32,
                    param_dtype=jnp.float32)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


CACHES = ("dense", "paged", "state")
LIFECYCLE_PROMPTS = ([3, 1, 4, 1, 5], [9, 8, 7], list(range(10)))


@pytest.fixture
def engine_of(model_and_params, retention_model_and_params):
    def build(cache, **kw):
        kw.setdefault("seed", 11)
        if cache == "state":
            return _engine(retention_model_and_params, **kw)
        if cache == "paged":
            kw.setdefault("kv_block", 4)
        return _engine(model_and_params, kv_cache=cache, **kw)

    return build


def _sample_before_pr35(logits, rng, temps, topks):
    """``serve/engine.py::_sample`` as it stood before it branched on
    what its rows ask for: every row ranked and drawn, then chosen."""
    greedy = jnp.argmax(logits, axis=-1)
    ranks = jnp.argsort(jnp.argsort(-logits, axis=-1), axis=-1)
    k = jnp.where(topks > 0, topks, logits.shape[-1])[:, None]
    masked = jnp.where(ranks < k, logits, -jnp.inf)
    scaled = masked / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


class TestSampleBranches:
    """ISSUE 35: ``_sample`` ranks the vocabulary and draws only where
    a row that counts asks for it, decided on the device; what a
    sampled row gets is what it got, for the same key."""

    # temperatures of four rows, and which rows hold a request
    BATCHES = {
        "greedy": ([0.0, 0.0, 0.0, 0.0], None),
        "sampled": ([0.7, 1.0, 1.3, 0.2], None),
        "mixed": ([0.0, 0.9, 0.0, 1.2], None),
        "mixed_with_an_idle_row": ([0.0, 0.9, 0.6, 0.0],
                                   [True, True, False, True]),
        "idle_row_with_a_temperature": ([0.0, 0.0, 0.8, 0.0],
                                        [True, True, False, True]),
    }

    @pytest.mark.parametrize("ties", [False, True],
                             ids=["distinct", "ties"])
    @pytest.mark.parametrize("top_k", [0, 1, 20, VOCAB])
    @pytest.mark.parametrize("batch", list(BATCHES))
    def test_tokens_are_the_unbranched_formulas(self, batch, top_k, ties):
        from horovod_tpu.serve.engine import _sample

        temps, rows = self.BATCHES[batch]
        temps = jnp.asarray(temps, jnp.float32)
        # Row 3 always draws from the full vocabulary.
        topks = jnp.asarray([top_k, top_k, top_k, 0], jnp.int32)
        counted = np.ones(4, bool) if rows is None else np.asarray(rows)
        logits = jax.random.normal(jax.random.PRNGKey(5), (4, VOCAB)) * 2
        if ties:
            logits = jnp.round(logits)       # a dozen values a row
        new, old = jax.jit(_sample), jax.jit(_sample_before_pr35)
        for seed in range(4):
            rng = jax.random.PRNGKey(seed)
            got = np.asarray(new(logits, rng, temps, topks,
                                 None if rows is None else jnp.asarray(rows)))
            want = np.asarray(old(logits, rng, temps, topks))
            np.testing.assert_array_equal(got[counted], want[counted])
            assert got.dtype == np.int32
            if not (counted & (np.asarray(temps) > 0)).any():
                # No row that counts samples: nothing was drawn at all.
                np.testing.assert_array_equal(
                    got, np.argmax(np.asarray(logits), axis=-1))

    @pytest.mark.parametrize("cache", CACHES)
    def test_sampled_tokens_do_not_depend_on_greedy_batchmates(
            self, engine_of, cache):
        """The key is split once a call whatever the rows ask.  The
        lifecycle's first request is greedy in one engine and samples in
        the other, so the calls it makes alone take the argmax branch in
        the one and draw in the other; the two requests admitted beside
        it get the same tokens in both.  ``sampling_steps`` counts the
        steps that held a request with a temperature."""
        runs = []
        for first in (SamplingParams(max_new_tokens=30),
                      SamplingParams(max_new_tokens=30, temperature=0.7,
                                     top_k=3)):
            eng = engine_of(cache)
            runs.append(step_state_oracle.drive_lifecycle(
                eng, LIFECYCLE_PROMPTS, first))
            stats = eng.kv_stats()
            assert stats["decode_steps"] == step_state_oracle.LIFECYCLE_STEPS
            greedy_alone = (step_state_oracle.LIFECYCLE_STEPS_FIRST_ALONE
                            if first.temperature <= 0 else 0)
            assert stats["sampling_steps"] == (
                stats["decode_steps"] - greedy_alone)
            assert eng.trace_counts["decode"] == 1
        (_, _, a0), (_, _, b0), (_, _, c0) = runs[0]
        (_, _, a1), (_, _, b1), (_, _, c1) = runs[1]
        assert (b0, c0) == (b1, c1)
        assert a0 != a1 and len(b0) > 5 and len(c0) > 5

    def test_the_decode_span_says_whether_the_step_sampled(
            self, model_and_params):
        from horovod_tpu.obs import trace

        trace.configure(enabled=True)
        trace.clear()
        eng = _engine(model_and_params, seed=3)
        eng.start(0, [1, 2, 3], SamplingParams(max_new_tokens=20))
        eng.step()
        eng.start(1, [4, 5], SamplingParams(max_new_tokens=20,
                                            temperature=0.8))
        eng.step()
        eng.release(1)      # its temperature goes with it
        eng.step()
        said = [s["args"]["sampling"] for s in trace.snapshot()
                if s["name"] == "hvd_tpu_engine_decode"]
        trace.clear()
        assert said == [0, 1, 0]
        assert eng.kv_stats()["sampling_steps"] == 1


class TestStepState:
    """ISSUE 30: the decode step's inputs live on the device and the
    decode program advances them; the host uploads only what it
    changed, and the key is one chain split inside the programs, in
    the order the parent split it on the host."""

    PROMPTS = LIFECYCLE_PROMPTS

    @staticmethod
    def _built_once(eng):
        assert set(eng.trace_counts.values()) == {1}, eng.trace_counts
        assert eng._decode_fn._cache_size() == 1
        for name in eng.trace_counts:
            if name.startswith("prefill_"):
                L = int(name.split("_")[1])
                assert eng._prefill_fns[L]._cache_size() == 1, name

    @pytest.mark.parametrize("cache", CACHES)
    def test_device_state_is_the_mirror_through_a_lifecycle(
            self, engine_of, cache):
        eng = engine_of(cache)
        step_state_oracle.drive_lifecycle(eng, self.PROMPTS)
        assert eng.trace_counts["decode"] == 1
        assert {"prefill_8", "prefill_16"} <= set(eng.trace_counts)
        self._built_once(eng)

    def test_device_state_after_an_import_and_a_speculative_step(
            self, model_and_params, engine_of):
        """The two mutations only a paged cache has: blocks bound from
        the wire stand where a prefill would, and a speculative step
        advances rows on the host alone (its programs are its own)."""
        src = engine_of("paged")
        eng = engine_of("paged", drafter=model_and_params, spec_k=2)
        sp = SamplingParams(max_new_tokens=20, temperature=0.8)
        prompt = self.PROMPTS[0]
        first = src.start(0, prompt, sp)
        _, k, v = src.export_slot_kv(0)
        eng.import_slot_kv(0, prompt, k, v, first, sp,
                           rng=src.export_rng())
        np.testing.assert_array_equal(eng.export_rng(), src.export_rng())
        want = [src.step()[0][0] for _ in range(3)]
        got = [step_state_oracle.step(eng)[0][0] for _ in range(3)]
        assert got == want
        eng.start(1, self.PROMPTS[1],
                  SamplingParams(max_new_tokens=20, spec=True))
        sent = eng.kv_stats()["step_state_uploads"]
        out = eng.step()                        # draft and verify
        assert eng.trace_counts["spec_verify"] == 1
        assert len(out[0]) == 1 and len(out[1]) >= 1
        assert eng.kv_stats()["step_state_uploads"] == sent
        eng.release(1)
        step_state_oracle.step(eng)             # plain again: uploads
        assert eng.kv_stats()["step_state_uploads"] == sent + 1
        self._built_once(eng)

    @pytest.mark.parametrize("cache", CACHES)
    def test_tokens_and_key_are_the_host_carried_chain(
            self, engine_of, model_and_params, retention_model_and_params,
            cache):
        """The parent carried the key on the host: ``key, sub =
        split(key)`` before every prefill and every decode step, in
        call order.  A plain loop that does so, sampling from
        cache-free full forwards, reproduces the engine's tokens for
        greedy, temperature and top-k requests across admissions, and
        after every call the engine's key is the chain's."""
        _sample = _sample_before_pr35

        model, params = (retention_model_and_params if cache == "state"
                         else model_and_params)
        eng = engine_of(cache, max_slots=3)
        forward = jax.jit(lambda t: model.apply({"params": params}, t))

        def logits_after(seq):
            padded = np.zeros((1, model.config.max_seq_len), np.int32)
            padded[0, :len(seq)] = seq
            return np.asarray(forward(padded)[0, len(seq) - 1], np.float32)

        chain = {"key": jax.random.PRNGKey(11), "calls": 0}

        def next_sub():
            chain["key"], sub = jax.random.split(chain["key"])
            chain["calls"] += 1
            return sub

        seqs, temps, topks = {}, np.zeros(3, np.float32), np.zeros(3, np.int32)

        def start(slot, prompt, sp):
            got = eng.start(slot, prompt, sp)
            want = int(_sample(logits_after(prompt)[None], next_sub(),
                               jnp.float32(sp.temperature)[None],
                               jnp.int32(sp.top_k)[None])[0])
            assert got == want, (slot, got, want)
            seqs[slot] = list(prompt) + [got]
            temps[slot], topks[slot] = sp.temperature, sp.top_k
            np.testing.assert_array_equal(eng.export_rng(), chain["key"])

        def step():
            got = eng.step()
            logits = np.zeros((3, VOCAB), np.float32)
            for slot, seq in seqs.items():
                logits[slot] = logits_after(seq)
            want = np.asarray(_sample(jnp.asarray(logits), next_sub(),
                                      jnp.asarray(temps),
                                      jnp.asarray(topks)))
            assert got == {s: [int(want[s])] for s in seqs}, chain["calls"]
            for slot in seqs:
                seqs[slot].append(int(want[slot]))
            np.testing.assert_array_equal(eng.export_rng(), chain["key"])

        start(0, self.PROMPTS[0], SamplingParams(max_new_tokens=20))
        step()
        step()
        start(2, self.PROMPTS[1], SamplingParams(
            max_new_tokens=20, temperature=0.9, top_k=20))
        step()
        step()
        start(1, self.PROMPTS[2], SamplingParams(
            max_new_tokens=20, temperature=1.2))
        for _ in range(3):
            step()
        eng.release(0)
        del seqs[0]
        temps[0] = topks[0] = 0
        step()
        start(0, [5, 5, 5], SamplingParams(max_new_tokens=20,
                                           temperature=0.7, top_k=5))
        step()
        assert chain["calls"] == 4 + 9      # prefills and decode steps

    @pytest.mark.parametrize("cache", CACHES)
    def test_a_staged_bind_that_never_binds_is_sent_back(
            self, engine_of, cache):
        """An admission sends its row behind the prefill, before the
        token fence.  Should the prefill raise there, the device holds
        an active row the host never bound: the next step compares,
        and sends the mirrors' row back."""
        eng = engine_of(cache, max_slots=3)
        sp = SamplingParams(max_new_tokens=20)
        eng.start(0, self.PROMPTS[0], sp)
        step_state_oracle.step(eng)
        eng._stage_bind(2, 7, SamplingParams(max_new_tokens=20,
                                             temperature=0.5, top_k=3))
        device = jax.device_get(eng._step_state)
        assert device["active"][2] and device["positions"][2] == 7
        assert eng.free_slots() == [1, 2]       # the host bound nothing
        out = step_state_oracle.step(eng)       # device == mirrors again
        assert sorted(out) == [0]
        assert not jax.device_get(eng._step_state["active"])[2]
        self._built_once(eng)

    def test_a_slow_dispatch_pokes_the_runtime_and_a_slow_host_does_not(
            self, engine_of):
        """A dispatch that took far longer than they do sends the
        runtime small transfers behind the device's work (and says so
        on the span); the usual time follows a faster call at once and
        a slower host slowly, so a host that stays slow is poked for a
        few steps and then left alone."""
        eng = engine_of("dense")
        eng.start(0, [1, 2, 3], SamplingParams(max_new_tokens=40))
        eng.step()                      # the first dispatch builds
        del eng._usual["dispatch"]
        args = {}

        def dispatched(took_us):
            eng._wake_runtime(args, took_us,
                              eng._follow("dispatch", took_us))

        for took in (1000.0, 1200.0, 900.0, 1100.0):
            dispatched(took)
        assert eng._usual["dispatch"] == pytest.approx(910.0, rel=0.02)
        assert eng.runtime_pokes == 0 and "poked" not in args
        dispatched(2400.0)                          # the slow mode
        assert eng.runtime_pokes == 1 and args["poked"]
        assert len(eng._pokes) == 8
        jax.block_until_ready(eng._pokes)
        for _ in range(40):                         # a host slower for good
            dispatched(2400.0)
        assert eng.runtime_pokes < 20
        assert eng._usual["dispatch"] > 1400.0
        before = eng.runtime_pokes
        out = step_state_oracle.step(eng)           # and steps go on
        assert sorted(out) == [0]
        assert eng.kv_stats()["runtime_pokes"] >= before

    def test_uploads_follow_what_the_host_changed(self, engine_of):
        """A steady step uploads nothing.  An admission sends what it
        binds behind its prefill (``staged_uploads``) and leaves its
        first step the sampled token alone; a finish costs the step
        after it the arrays it changed; the table goes up when a row
        crossed into a new block, once per ``kv_block`` positions a
        row."""
        from horovod_tpu.obs import trace

        trace.configure(enabled=True)
        trace.clear()
        eng = engine_of("paged", max_slots=3)
        sp = SamplingParams(max_new_tokens=30)
        eng.start(0, [1, 2, 3], sp)             # positions 3 ..
        eng.start(1, [4, 5, 6, 7, 8], sp)       # positions 5 ..
        eng.step()
        s0 = eng.kv_stats()
        assert (s0["decode_steps"], s0["step_state_uploads"]) == (1, 1)
        assert s0["staged_uploads"] == 4    # positions and active, twice
        K = 12
        for _ in range(K):
            eng.step()
        s1 = eng.kv_stats()
        assert s1["decode_steps"] == 1 + K
        assert s1["step_state_uploads"] == 1            # steady: none
        # Two rows over 12 positions at 4 a block: three boundaries each.
        assert 1 <= s1["table_uploads"] - s0["table_uploads"] <= 2 * (K // 4)
        eng.start(2, [9, 9], sp)                        # an admission
        eng.step()
        assert eng.kv_stats()["step_state_uploads"] == 2
        assert eng.kv_stats()["staged_uploads"] == 6
        eng.release(0)                                  # a finish
        eng.step()
        eng.step()
        assert eng.kv_stats()["step_state_uploads"] == 3
        uploads = [s["args"]["uploads"] for s in trace.snapshot()
                   if s["name"] == "hvd_tpu_engine_decode"]
        trace.clear()
        assert len(uploads) == 1 + K + 3
        assert uploads[0] == 1                  # the tokens alone
        assert uploads.count(0) >= K - 2 * (K // 4)
        assert set(uploads[1:1 + K]) <= {0, 1}  # the table alone
        # The admission's token; row 0 crossed a block with it.
        assert uploads[1 + K] == 2
        assert uploads[2 + K] == 3              # active, positions, table
        assert uploads[-1] <= 1                 # steady again
        # A cache without a table counts none.
        dense = engine_of("dense")
        dense.start(0, [1, 2, 3], sp)
        dense.step()
        dense.step()
        assert "table_uploads" not in dense.kv_stats()
        assert dense.kv_stats()["step_state_uploads"] == 1


class TestStepPhases:
    """ISSUE 40: a decode step says where its host time went — the
    dispatch call, the wait for the tokens and what came before, as
    args of ``hvd_tpu_engine_decode`` on the span clock — and a step
    that one of them held up names it."""

    @pytest.fixture(autouse=True)
    def _traced(self):
        from horovod_tpu.obs import flight, trace

        trace.configure(enabled=True)
        flight.configure(enabled=True)
        trace.clear()
        yield
        trace.configure(enabled=True)
        trace.clear()

    @staticmethod
    def _spans(name):
        from horovod_tpu.obs import trace

        return [s for s in trace.snapshot() if s["name"] == name]

    @pytest.mark.parametrize("cache", CACHES)
    def test_decode_and_prefill_spans_carry_their_phases(
            self, engine_of, cache):
        eng = engine_of(cache)
        eng.start(0, [1, 2, 3], SamplingParams(max_new_tokens=20))
        for _ in range(5):
            eng.step()
        decode = self._spans("hvd_tpu_engine_decode")
        assert len(decode) == 5
        for span in decode:
            args = span["args"]
            took = [args["prepare_us"], args["dispatch_us"],
                    args["fence_us"]]
            assert min(took) >= 0
            assert sum(took) <= span["dur_us"]
            assert "stalled" not in args
        (prefill,) = self._spans("hvd_tpu_engine_prefill")
        args = prefill["args"]
        assert args["dispatch_us"] >= 0 and args["fence_us"] >= 0
        assert "prepare_us" not in args
        assert args["dispatch_us"] + args["fence_us"] <= prefill["dur_us"]
        stats = eng.kv_stats()
        assert stats["stalled_steps"] == stats["stalled_dispatch"] == 0
        assert stats["stalled_prefills"] == 0
        assert 0 < stats["dispatch_ms_p50"] <= stats["dispatch_ms_p99"]
        assert 0 < stats["fence_ms_p50"] <= stats["fence_ms_p99"]

    def test_a_speculative_step_carries_none(self, model_and_params,
                                             engine_of):
        eng = engine_of("paged", drafter=model_and_params, spec_k=2)
        eng.start(0, [1, 2, 3], SamplingParams(max_new_tokens=20,
                                               spec=True))
        eng.step()
        assert eng.trace_counts["spec_verify"] == 1
        (span,) = self._spans("hvd_tpu_engine_decode")
        assert not {"prepare_us", "dispatch_us", "fence_us",
                    "uploads"} & set(span["args"])

    def test_annotate_is_for_the_profiler_alone(self):
        import contextlib

        from horovod_tpu.obs import trace

        before = len(trace.snapshot())
        with trace.annotate("hvd_tpu_decode_dispatch") as entered:
            assert trace.current() is None      # no ids
        assert not isinstance(entered, contextlib.nullcontext)
        assert len(trace.snapshot()) == before  # no ring entry
        trace.configure(enabled=False)
        assert isinstance(trace.annotate("hvd_tpu_decode_dispatch"),
                          contextlib.nullcontext)

    def test_kv_stats_reads_its_own_steps_from_the_span_ring(
            self, engine_of):
        """The dispatch and fence percentiles are computed when asked
        for, from the decode spans this engine has put into the
        process's ring: an engine made later has none of them."""
        eng = engine_of("dense")
        eng.start(0, [1, 2, 3], SamplingParams(max_new_tokens=20))
        for _ in range(4):
            eng.step()
        took = sorted(s["args"]["dispatch_us"] / 1e3
                      for s in self._spans("hvd_tpu_engine_decode"))
        stats = eng.kv_stats()
        assert stats["dispatch_ms_p50"] in took[1:3]
        assert stats["dispatch_ms_p99"] == took[-1]
        later = engine_of("dense").kv_stats()
        assert later["dispatch_ms_p50"] is later["fence_ms_p99"] is None
        # The ring's newest end, oldest first, and only what is asked.
        from horovod_tpu.obs import trace

        spans = self._spans("hvd_tpu_engine_decode")
        assert trace.recent("hvd_tpu_engine_decode", 2) == spans[-2:]
        assert trace.recent("hvd_tpu_engine_decode", 9,
                            since_us=spans[1]["start_us"]) == spans[1:]
        assert trace.recent("hvd_tpu_no_such_span", 9) == []

    def test_a_stalled_prefill_is_named_by_the_phase_that_held_it(
            self, engine_of):
        """A prefill's dispatch and fence are held to the same rule,
        each bucket against its own usual lengths: the fence on the
        third prompt of a bucket waits 80 ms longer."""
        from horovod_tpu.obs import flight

        eng = engine_of("dense", max_slots=4)
        sp = SamplingParams(max_new_tokens=4)
        seen = len([e for e in flight.events()
                    if e["kind"] == "slow_prefill"])
        for slot, n in enumerate((3, 4, 12)):     # buckets 8, 8, 16
            eng.start(slot, list(range(1, n + 1)), sp)
        spans = self._spans("hvd_tpu_engine_prefill")
        assert not any("stalled" in s["args"] for s in spans)
        bucket = spans[0]["args"]["bucket"]
        usual = eng._usual[("fence", bucket)]
        timed = eng._timed

        def fence(token):
            time.sleep(0.08)
            return int(token)

        eng._timed = lambda args, key, fn, *a: timed(
            args, key, fence if key == "fence_us" else fn, *a)
        eng.start(3, [5, 6, 7], sp)
        args = self._spans("hvd_tpu_engine_prefill")[-1]["args"]
        assert args["stalled"] == "fence" and args["fence_us"] >= 80_000
        stats = eng.kv_stats()
        assert stats["stalled_prefills"] == 1 and stats["stalled_steps"] == 0
        (event,) = [e for e in flight.events()
                    if e["kind"] == "slow_prefill"][seen:]
        assert event["phase"] == "fence" and event["bucket"] == bucket
        assert event["prompt_len"] == 3
        assert event["usual_us"] <= usual * 1.05 + 1
        assert eng._usual[("fence", bucket)] < 2 * usual + 1

    def test_a_profiler_session_sees_the_phases_inside_their_spans(
            self, engine_of, tmp_path):
        """With a session live the four annotations lie on the host
        plane inside the span they belong to, as long as the span's
        args say; the ring gains nothing for them."""
        import glob

        from horovod_tpu.obs import trace

        eng = engine_of("paged")
        sp = SamplingParams(max_new_tokens=20)
        eng.start(0, [1, 2, 3], sp)
        eng.step()                      # programs built outside the trace
        eng.release(0)
        trace.clear()
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.start(0, [1, 2, 3], sp)
            eng.step()
        finally:
            jax.profiler.stop_trace()
        ring = trace.snapshot()
        assert [s["name"] for s in ring] == ["hvd_tpu_engine_prefill",
                                             "hvd_tpu_engine_decode"]
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        (host,) = [p for p in data.planes if p.name == "/host:CPU"]
        events = {ev.name: ev for line in host.lines for ev in line.events
                  if ev.name.startswith("hvd_tpu_")}
        for span, outer, phases in (
                (ring[0], "hvd_tpu_engine_prefill", ("prefill_dispatch",
                                                     "prefill_fence")),
                (ring[1], "hvd_tpu_engine_decode", ("decode_dispatch",
                                                    "decode_fence"))):
            out = events[outer]
            last_end = out.start_ns
            for phase in phases:
                ev = events["hvd_tpu_" + phase]
                assert last_end <= ev.start_ns      # in order, apart
                last_end = ev.start_ns + ev.duration_ns
                assert last_end <= out.start_ns + out.duration_ns
                said = span["args"][phase.split("_")[1] + "_us"]
                assert ev.duration_ns / 1e3 == pytest.approx(
                    said, rel=0.2, abs=200.0)

    def test_tracing_off_times_the_dispatch_alone(self, engine_of):
        from horovod_tpu.obs import trace

        trace.configure(enabled=False)
        eng = engine_of("dense")
        eng.start(0, [1, 2, 3], SamplingParams(max_new_tokens=20))
        for _ in range(3):
            eng.step()
        assert list(eng._usual) == ["dispatch"]     # _wake_runtime's
        assert eng._usual["dispatch"] > 0
        assert eng.kv_stats()["fence_ms_p50"] is None
        assert not trace.snapshot()

    def test_a_stalled_step_is_named_by_the_phase_that_held_it(
            self, engine_of):
        """The decode program's call sleeps once, after thirty steps,
        for 60 ms: the rule asks for four times the usual and 50 ms
        over it, and a sleep of 50 ms would meet the second only by as
        much as it overslept."""
        from horovod_tpu.obs import flight

        eng = engine_of("dense")
        sp = SamplingParams(max_new_tokens=20)
        eng.start(0, [1, 2, 3], sp)
        decode_fn, calls = eng._decode_fn, [0]

        def decode(*args):
            calls[0] += 1
            if calls[0] == 31:
                time.sleep(0.06)
            return decode_fn(*args)

        eng._decode_fn = decode
        seen = [e for e in flight.events()
                if e["kind"] == "slow_decode_step"]
        for i in range(36):
            if i and i % 12 == 0:       # the cache holds 32 positions
                eng.release(0)
                eng.start(0, [1, 2, 3], sp)
            if i == 30:
                usual = eng._usual["dispatch"]
            eng.step()
        spans = self._spans("hvd_tpu_engine_decode")
        assert [i for i, s in enumerate(spans)
                if "stalled" in s["args"]] == [30]
        args = spans[30]["args"]
        assert args["stalled"] == "dispatch"
        assert args["dispatch_us"] >= 60_000
        stats = eng.kv_stats()
        assert stats["stalled_steps"] == stats["stalled_dispatch"] == 1
        assert stats["stalled_fence"] == stats["stalled_prepare"] == 0
        (event,) = [e for e in flight.events()
                    if e["kind"] == "slow_decode_step"][len(seen):]
        assert event["phase"] == "dispatch"
        assert event["us"] == pytest.approx(args["dispatch_us"], abs=0.1)
        assert event["usual_us"] == pytest.approx(usual, abs=0.1)
        assert event["active"] == 1 and event["uploads"] == 0
        # One long step moves the usual by a twentieth of itself.
        assert eng._usual["dispatch"] < 2 * usual


class TestBlockPoolUnit:
    """Host-side allocator invariants (no jax involved)."""

    def _pool(self, blocks=10, block_tokens=4, slots=2):
        import numpy as np

        from horovod_tpu.serve.kv import BlockPool

        table = np.zeros((slots, 4), np.int32)
        copies = []
        pool = BlockPool(blocks, block_tokens, table,
                         lambda s, d: copies.append((s, d)))
        return pool, table, copies

    def test_full_block_sharing_increfs_partial_cows(self):
        pool, table, copies = self._pool()
        p = [1, 2, 3, 4, 5, 6, 7, 8]
        assert pool.begin_request(0, p + [9]) == 0
        pool.ensure_writable(0, 0, 9)
        pool.index_prompt(0, p + [9])
        # Block-aligned sharing: full blocks increfed, no copy — the
        # suffix's first write lands in a FRESH block.
        hit = pool.begin_request(1, p + [9])
        assert hit == 8                      # both full blocks shared
        assert copies == []                  # read-only: no COW
        pool.ensure_writable(1, 8, 1)
        assert copies == []
        pool.release(1)
        # Partial-tail sharing: the shared block's tail rows will be
        # written, so admission copy-on-writes it exactly once.
        hit = pool.begin_request(1, p + [9, 7])
        assert hit == 9                      # 2 full blocks + 1 partial
        assert len(copies) == 1              # COW fired exactly once
        pool.ensure_writable(1, 9, 1)        # owned copy: no second COW
        assert len(copies) == 1
        assert table[0, 3] == 0 and table[1, 3] == 0   # trash column

    def test_release_parks_indexed_blocks_then_evicts_lru(self):
        pool, _, _ = self._pool(blocks=5)    # 4 usable
        pool.begin_request(0, [1, 2, 3, 4, 5])
        pool.ensure_writable(0, 0, 5)
        pool.index_prompt(0, [1, 2, 3, 4, 5])
        pool.release(0)
        assert pool.blocks_in_use() == 0
        assert pool.probe([1, 2, 3, 4, 5]) == 4
        # Demand beyond the free list (3 blocks needed, 2 free) forces
        # LRU eviction of the cached chain — probe must miss after.
        pool.begin_request(0, list(range(10, 19)))
        pool.ensure_writable(0, 0, 9)
        assert pool.stats()["kv_evictions_total"] > 0
        assert pool.probe([1, 2, 3, 4, 5]) == 0

    def test_ensure_writable_after_release_is_noop(self):
        """Router cancel() can release a slot between the batcher's
        active-snapshot and its ensure_writable call — recreating the
        chain there would leak blocks forever (nothing releases a
        ghost chain); the call must no-op instead."""
        pool, table, _ = self._pool()
        pool.begin_request(0, [1, 2, 3, 4, 5])
        pool.ensure_writable(0, 0, 5)
        pool.release(0)                      # concurrent cancel landed
        pool.ensure_writable(0, 5, 1)        # batcher's stale dispatch
        assert pool.blocks_in_use() == 0     # no ghost allocation
        assert (table[0] == 0).all()         # row stays all-trash

    def test_forced_evict_fault_drops_cache(self):
        pool, _, _ = self._pool()
        pool.begin_request(0, [1, 2, 3, 4, 5])
        pool.ensure_writable(0, 0, 5)
        pool.index_prompt(0, [1, 2, 3, 4, 5])
        pool.release(0)
        assert pool.probe([1, 2, 3, 4, 5]) > 0
        with faults.inject("serve:step=0,mode=evict"):
            pool.begin_request(1, [9, 9, 9, 9, 9])
            pool.ensure_writable(1, 0, 5)    # first alloc fires evict
        assert pool.probe([1, 2, 3, 4, 5]) == 0
        assert pool.stats()["kv_evictions_total"] >= 2

    def test_prefix_trie_partial_and_mid_block_divergence(self):
        from horovod_tpu.serve.kv import PrefixIndex

        idx = PrefixIndex(4)
        idx.insert([1, 2, 3, 4, 5, 6], [10, 11])   # 1 full + partial(2)
        blocks, partial = idx.lookup([1, 2, 3, 4, 5, 6, 7])
        assert blocks == [10] and partial == (11, 2)
        # Divergence inside the first block: usable as partial source.
        blocks, partial = idx.lookup([1, 2, 9, 9])
        assert blocks == [] and partial == (10, 2)
        freed = idx.remove_subtree(10)
        assert sorted(freed) == [10, 11]           # subtree pruned
        assert idx.lookup([1, 2, 3, 4, 5, 6]) == ([], None)


class TestSpeculative:
    """ISSUE 10: speculative decoding — accepted-prefix semantics make
    spec greedy decode token-identical to plain greedy decode, for any
    drafter quality."""

    def _spec_engine(self, model_and_params, drafter, k, **kw):
        model, params = model_and_params
        kw.setdefault("max_slots", 2)
        kw.setdefault("prefill_buckets", (8, 16))
        kw.setdefault("max_seq_len", 32)
        return InferenceEngine(model, params, kv_cache="paged",
                               kv_block=4, drafter=drafter, spec_k=k,
                               **kw)

    def _run_spec(self, engine, slot, prompt, n):
        toks = [engine.start(slot, prompt, SamplingParams(
            max_new_tokens=n, spec=True))]
        while len(toks) < n:
            toks.extend(engine.step()[slot])
        engine.release(slot)
        return toks[:n]

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_greedy_identity_self_drafter(self, model_and_params, k):
        """Perfect drafter (the target itself): every draft accepted,
        output identical to plain greedy decode for K in {1,2,4}."""
        model, params = model_and_params
        eng = self._spec_engine(model_and_params, (model, params), k)
        for prompt in ([3, 14, 15], [1], list(range(10))):
            got = self._run_spec(eng, 0, prompt, 7)
            assert got == _greedy_reference(model, params, prompt, 7), \
                (k, prompt)
        stats = eng.kv_stats()
        # Self-drafting accepts the whole draft: > 1 token per verify.
        assert stats["spec_accept_per_verify"] == k + 1, stats

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_greedy_identity_bad_drafter(self, model_and_params, k):
        """Adversarial drafter (unrelated random weights): acceptance
        drops but output identity must hold — a wrong draft costs
        speed, never correctness."""
        import jax
        import jax.numpy as jnp

        model, params = model_and_params
        dcfg = GPTConfig(vocab_size=VOCAB, n_layer=1, n_head=2,
                         d_model=16, d_ff=32, max_seq_len=32,
                         dtype=jnp.float32, param_dtype=jnp.float32)
        dmodel = GPT(dcfg)
        dparams = dmodel.init(jax.random.PRNGKey(99),
                              jnp.zeros((1, 8), jnp.int32))["params"]
        eng = self._spec_engine(model_and_params, (dmodel, dparams), k)
        for prompt in ([3, 14, 15], list(range(10))):
            got = self._run_spec(eng, 0, prompt, 7)
            assert got == _greedy_reference(model, params, prompt, 7), \
                (k, prompt)
        stats = eng.kv_stats()
        assert stats["spec_accept_per_verify"] >= 1.0

    def test_all_rejected_drafts_emit_plain_greedy(self, model_and_params):
        """Every draft wrong: the step emits the one plain greedy token,
        and the rejected rows' K/V, written at positions past the
        slot's new length (ISSUE 25: the write no longer waits for the
        verdict), is hidden by the mask and overwritten before any
        query reaches it — the following steps, with drafts that are
        right again, still equal plain decode."""
        model, params = model_and_params
        K, n = 3, 12
        eng = self._spec_engine(model_and_params, (model, params), K)
        prompt = [3, 14, 15]
        ref = _greedy_reference(model, params, prompt, n + K)
        toks = [eng.start(0, prompt, SamplingParams(max_new_tokens=n,
                                                    spec=True))]
        assert toks == ref[:1]
        draft_fn, wrong = eng._spec_draft_fn, [True]

        def drafts(*args):
            draft, dcaches = draft_fn(*args)
            if wrong[0]:
                at = len(toks)
                bad = [(t + 1) % VOCAB for t in ref[at:at + K]]
                draft = jnp.asarray(draft).at[0].set(
                    jnp.asarray(bad, jnp.int32))
            return draft, dcaches

        eng._spec_draft_fn = drafts
        for _ in range(4):
            out = eng.step()[0]
            assert out == ref[len(toks):len(toks) + 1], (out, toks)
            toks.extend(out)
        assert eng.kv_stats()["spec_accept_per_verify"] == 1.0
        wrong[0] = False
        while len(toks) < n:
            toks.extend(eng.step()[0])
        assert toks[:n] == ref[:n]
        assert eng.kv_stats()["spec_accept_per_verify"] > 1.0

    def test_mixed_spec_and_plain_slots_share_the_batch(
            self, model_and_params):
        """A spec-greedy slot and a temperature slot decode in the same
        dispatch: the spec slot bursts, the sampling slot advances one
        token per step, both stay correct."""
        model, params = model_and_params
        eng = self._spec_engine(model_and_params, (model, params), 3)
        a = [eng.start(0, [3, 1, 4], SamplingParams(max_new_tokens=9,
                                                    spec=True))]
        b = [eng.start(1, [9, 2], SamplingParams(max_new_tokens=9,
                                                 temperature=0.8,
                                                 top_k=10))]
        for _ in range(8):
            toks = eng.step()
            a.extend(toks.get(0, []))
            b.extend(toks.get(1, []))
            if len(a) >= 9 and len(b) >= 3:
                break
        assert a[:9] == _greedy_reference(model, params, [3, 1, 4], 9)
        assert len(b) >= 3 and all(0 <= t < VOCAB for t in b)
        # The temperature slot advanced exactly one token per dispatch.
        assert len(b) < len(a)
        # The ratio measures the DRAFTER, not the batch mix: the
        # plain-sampling batchmate must not dilute it toward 1.0.
        assert eng.kv_stats()["spec_accept_per_verify"] == 4.0

    def test_spec_cap_at_cache_end_is_exact(self, model_and_params):
        """Acceptance is capped so a burst never writes past the cache:
        an uncapped spec generation fills exactly the dense contract's
        ``S - n + 1`` tokens and matches plain greedy throughout."""
        model, params = model_and_params
        # A short cache (S=16) exercises the same cap with far fewer
        # distinct full-forward shapes in the reference oracle.
        eng = self._spec_engine(model_and_params, (model, params), 4,
                                max_seq_len=16, prefill_buckets=(8,))
        prompt = [1, 2]
        toks = [eng.start(0, prompt, SamplingParams(
            max_new_tokens=10 ** 6, spec=True))]
        while not eng.slot_full(0):
            toks.extend(eng.step()[0])
        want_n = eng.max_seq_len - len(prompt) + 1
        assert len(toks) == want_n, (len(toks), want_n)
        assert toks == _greedy_reference(model, params, prompt, want_n)

    def test_spec_requires_paged(self, model_and_params):
        model, params = model_and_params
        with pytest.raises(ValueError, match="paged"):
            InferenceEngine(model, params, max_slots=2,
                            prefill_buckets=(8,), max_seq_len=32,
                            kv_cache="dense", drafter=(model, params))


def _batcher(model_and_params, **kw):
    kw.setdefault("max_queue", 8)
    kw.setdefault("default_deadline_s", 30.0)
    engine_kw = kw.pop("engine_kw", {})
    return ContinuousBatcher(_engine(model_and_params, **engine_kw), **kw)


def _pump(batcher, reqs, max_steps=500):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            return
        batcher.step()
    raise AssertionError("requests did not complete")


class TestBatcher:
    def test_completes_more_requests_than_slots(self, model_and_params):
        model, params = model_and_params
        b = _batcher(model_and_params)   # 2 slots
        reqs = [b.submit([i + 1, i + 2], SamplingParams(max_new_tokens=4))
                for i in range(6)]
        _pump(b, reqs)
        for i, r in enumerate(reqs):
            assert r.error is None, (i, r.error)
            assert r.tokens == _greedy_reference(model, params,
                                                 [i + 1, i + 2], 4)
        snap = b.snapshot()
        assert snap["requests_completed"] == 6
        assert snap["occupancy_mean"] > 0
        assert snap["ttft_ms_p50"] > 0

    def test_backpressure_rejects_when_full(self, model_and_params):
        b = _batcher(model_and_params, max_queue=2)
        b.submit([1], SamplingParams(max_new_tokens=2))
        b.submit([2], SamplingParams(max_new_tokens=2))
        with pytest.raises(QueueFullError):
            b.submit([3], SamplingParams(max_new_tokens=2))
        assert b.snapshot()["requests_rejected"] == 1

    def test_deadline_expires_queued_request(self, model_and_params):
        b = _batcher(model_and_params)
        r = b.submit([1, 2], SamplingParams(max_new_tokens=4),
                     deadline_s=0.01)
        time.sleep(0.05)
        b.step()
        assert r.done.is_set()
        assert r.error == "deadline_exceeded"
        assert b.snapshot()["requests_expired"] == 1

    def test_deadline_expires_inflight_request(self, model_and_params):
        b = _batcher(model_and_params)
        r = b.submit([1, 2], SamplingParams(max_new_tokens=1000),
                     deadline_s=0.2)
        b.step()               # admitted + first token
        assert not r.done.is_set()
        time.sleep(0.25)
        b.step()
        assert r.error == "deadline_exceeded"
        # The slot is free again for new work.
        assert len(b.engine.free_slots()) == b.engine.max_slots

    def test_stop_token_ends_generation(self, model_and_params):
        model, params = model_and_params
        prompt = [3, 14, 15, 92, 6]
        ref = _greedy_reference(model, params, prompt, 8)
        # A stop token the answer has not held before: one that stood
        # earlier would end the generation there ([7, 8] answers 42
        # eight times over).
        at = next(i for i in range(2, len(ref)) if ref[i] not in ref[:i])
        b = _batcher(model_and_params)
        r = b.submit(prompt, SamplingParams(max_new_tokens=8,
                                            stop_token=ref[at]))
        _pump(b, [r])
        assert r.tokens == ref[:at + 1]   # stop token included, then ends

    def test_boundary_length_prompt_rejected_at_submit(self,
                                                       model_and_params):
        """A prompt that fits a (clamped) bucket but leaves no room to
        generate must fail at admission with the proper error class,
        not late inside step() as a generic prefill failure."""
        b = _batcher(model_and_params,
                     engine_kw={"prefill_buckets": (32,)})  # == max_seq_len
        with pytest.raises(PromptTooLongError):
            b.submit(list(range(32)), SamplingParams(max_new_tokens=2))
        assert b.queue_depth() == 0

    def test_cancel_frees_queue_entry_and_slot(self, model_and_params):
        b = _batcher(model_and_params)   # 2 slots
        running = [b.submit([i + 1], SamplingParams(max_new_tokens=10))
                   for i in range(2)]
        b.step()
        b.step()                         # both admitted (1 prefill/step)
        assert len(b.engine.free_slots()) == 0
        queued = b.submit([9], SamplingParams(max_new_tokens=10))
        assert b.cancel(queued.request_id) is True
        assert queued.error == "cancelled" and queued.done.is_set()
        assert b.queue_depth() == 0
        assert b.cancel(running[0].request_id) is True
        assert running[0].error == "cancelled"
        assert b.cancel("no-such-request") is False
        assert len(b.engine.free_slots()) == 1   # slot came back
        _pump(b, [running[1]])
        assert running[1].error is None and len(running[1].tokens) == 10

    def test_max_new_tokens_capped_by_config(self, model_and_params):
        b = _batcher(model_and_params)
        r = b.submit([1], SamplingParams(max_new_tokens=10 ** 6))
        assert r.sampling.max_new_tokens == hvd.config().serve_max_new_tokens

    def test_admission_interleaves_with_decode(self, model_and_params):
        """A queued request is admitted while another is mid-stream —
        the continuous-batching property (no drain barrier)."""
        b = _batcher(model_and_params)
        long_req = b.submit([1, 2, 3], SamplingParams(max_new_tokens=12))
        b.step()
        late = b.submit([4, 5], SamplingParams(max_new_tokens=2))
        b.step()
        assert late.first_token_at is not None   # admitted mid-stream
        assert not long_req.done.is_set()
        _pump(b, [long_req, late])
        assert long_req.error is None and late.error is None


class TestRequestLifecycle:
    """ISSUE 24: the request's own stamps and the program's spans at
    the scheduler and engine boundaries (docs/tracing.md) — recorded
    for every request, not only for one that came in over the wire."""

    @pytest.fixture(autouse=True)
    def _clean_ring(self):
        from horovod_tpu.obs import trace

        trace.configure(enabled=True)
        trace.clear()
        yield
        trace.configure(enabled=True)
        trace.clear()

    def _serve(self, model_and_params, n=3, new_tokens=4):
        b = _batcher(model_and_params)   # 2 slots
        reqs = [b.submit([i + 1, i + 2, i + 3],
                         SamplingParams(max_new_tokens=new_tokens))
                for i in range(n)]
        _pump(b, reqs)
        return b, reqs

    def test_in_process_request_gets_its_phase_spans_under_one_trace(
            self, model_and_params):
        from horovod_tpu.obs import trace

        assert trace.current() is None     # no ambient context here
        _, reqs = self._serve(model_and_params)
        spans = trace.snapshot()
        for r in reqs:
            mine = [s for s in spans if s["trace_id"] == r.trace_ctx[0]]
            names = sorted(s["name"] for s in mine)
            assert names == ["hvd_tpu_serve_decode", "hvd_tpu_serve_prefill",
                             "hvd_tpu_serve_queued",
                             "hvd_tpu_serve_request"]
            (root,) = [s for s in mine
                       if s["name"] == "hvd_tpu_serve_request"]
            assert root["parent_id"] is None
            assert root["args"]["request_id"] == r.request_id
            assert root["args"]["tokens"] == len(r.tokens)
            assert all(s["parent_id"] == root["span_id"]
                       for s in mine if s is not root)
            assert trace.unresolved_parents(mine) == []

    def test_ambient_context_is_kept_and_roots_nothing(
            self, model_and_params):
        from horovod_tpu.obs import trace

        b = _batcher(model_and_params)
        with trace.span("hvd_tpu_rpc_server", kind="server") as ctx:
            r = b.submit([1, 2, 3], SamplingParams(max_new_tokens=2))
        _pump(b, [r])
        assert r.trace_ctx == ctx and r.trace_root is False
        mine = [s["name"] for s in trace.snapshot()
                if s["trace_id"] == ctx[0]]
        assert "hvd_tpu_serve_queued" in mine
        assert "hvd_tpu_serve_request" not in mine

    def test_token_times_and_admission_stamps(self, model_and_params):
        _, reqs = self._serve(model_and_params, n=3, new_tokens=5)
        for r in reqs:
            assert len(r.token_times) == len(r.tokens) == 5
            assert r.token_times == sorted(r.token_times)
            assert r.token_times[0] == r.first_token_at
            assert (r.submitted_at <= r.admitted_at <= r.first_token_at
                    <= r.finished_at)
        # Two slots, three requests: the third waited for a slot.
        assert max(r.admitted_at - r.submitted_at for r in reqs) > 0

    def test_queued_span_is_the_queue_wait_on_the_span_clock(
            self, model_and_params):
        from horovod_tpu.obs import trace

        _, reqs = self._serve(model_and_params)
        spans = trace.snapshot()
        for r in reqs:
            (q,) = [s for s in spans if s["name"] == "hvd_tpu_serve_queued"
                    and s["trace_id"] == r.trace_ctx[0]]
            assert q["start_us"] == pytest.approx(
                trace.mono_us(r.submitted_at))
            assert q["dur_us"] == pytest.approx(
                (r.admitted_at - r.submitted_at) * 1e6, abs=1.0)

    def test_step_span_holds_its_engine_children(self, model_and_params):
        from horovod_tpu.obs import trace

        self._serve(model_and_params)
        spans = trace.snapshot()
        steps = [s for s in spans if s["name"] == "hvd_tpu_serve_step"]
        assert steps
        seen = set()
        for st in steps:
            kids = [s for s in spans if s["parent_id"] == st["span_id"]]
            seen |= {k["name"] for k in kids}
            assert {k["name"] for k in kids} <= {
                "hvd_tpu_engine_prefill", "hvd_tpu_engine_decode"}
            for k in kids:
                assert k["trace_id"] == st["trace_id"]
                assert st["start_us"] <= k["start_us"]
                assert (k["start_us"] + k["dur_us"]
                        <= st["start_us"] + st["dur_us"] + 1.0)
            assert st["dur_us"] - sum(k["dur_us"] for k in kids) >= -1.0
            args = st["args"]
            assert set(args) == {"active", "queued", "admitted", "emitted"}
            assert args["admitted"] == sum(
                k["name"] == "hvd_tpu_engine_prefill" for k in kids)
            assert (args["active"] > 0) == any(
                k["name"] == "hvd_tpu_engine_decode" for k in kids)
        assert seen == {"hvd_tpu_engine_prefill", "hvd_tpu_engine_decode"}
        assert sum(s["args"]["admitted"] for s in steps) == 3
        prefill = [s for s in spans
                   if s["name"] == "hvd_tpu_engine_prefill"]
        assert all({"slot", "bucket", "prompt_len", "prefix_hit"}
                   <= set(s["args"]) for s in prefill)

    def test_idle_step_records_nothing(self, model_and_params):
        from horovod_tpu.obs import trace

        b = _batcher(model_and_params)
        for _ in range(5):
            assert b.step() == 0
        assert trace.snapshot() == []

    def test_tracing_off_records_nothing_and_allocates_no_context(
            self, model_and_params):
        from horovod_tpu.obs import trace

        trace.configure(enabled=False)
        _, reqs = self._serve(model_and_params)
        assert trace.snapshot() == []
        for r in reqs:
            assert r.trace_ctx is None and r.trace_root is False
            # The stamps do not hang on tracing.
            assert len(r.token_times) == len(r.tokens)
            assert r.admitted_at is not None

    def test_failed_request_still_closes_its_trace(self, model_and_params):
        from horovod_tpu.obs import trace

        b = _batcher(model_and_params)
        r = b.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        assert b.cancel(r.request_id)
        (root,) = [s for s in trace.snapshot()
                   if s["name"] == "hvd_tpu_serve_request"]
        assert root["args"]["error"] == "cancelled"
        assert root["trace_id"] == r.trace_ctx[0]

    def test_stats_report_queue_wait_and_inter_token_latency(
            self, model_and_params):
        b, reqs = self._serve(model_and_params, n=3, new_tokens=5)
        snap = b.snapshot()
        for key in ("queue_wait_ms_p50", "queue_wait_ms_p99",
                    "itl_ms_p50", "itl_ms_p99"):
            assert snap[key] is not None and snap[key] >= 0
        gaps = [b2 - a for r in reqs
                for a, b2 in zip(r.token_times, r.token_times[1:])]
        assert snap["itl_ms_p99"] == pytest.approx(max(gaps) * 1e3,
                                                   abs=1e-3)
        waits = sorted(r.admitted_at - r.submitted_at for r in reqs)
        assert snap["queue_wait_ms_p99"] == pytest.approx(
            waits[-1] * 1e3, abs=1e-3)

    def test_profiler_trace_holds_the_programs_spans_on_the_host_plane(
            self, model_and_params, tmp_path):
        """The bridge: two batcher steps under a live ``jax.profiler``
        session leave the program's span names on ``/host:CPU`` of the
        ``.xplane.pb`` — the plane whose clock the device's operations
        share on a chip."""
        import glob

        b = _batcher(model_and_params)
        warm = b.submit([9, 8, 7], SamplingParams(max_new_tokens=2))
        _pump(b, [warm])                     # compile outside the trace
        r = b.submit([1, 2, 3], SamplingParams(max_new_tokens=8))
        jax.profiler.start_trace(str(tmp_path))
        try:
            b.step()
            b.step()
        finally:
            jax.profiler.stop_trace()
        _pump(b, [r])
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        (host,) = [p for p in data.planes if p.name == "/host:CPU"]
        found = {}
        for line in host.lines:
            for ev in line.events:
                if ev.name.startswith("hvd_tpu_"):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
        assert len(found["hvd_tpu_serve_step"]) == 2
        assert len(found["hvd_tpu_engine_prefill"]) == 1
        assert len(found["hvd_tpu_engine_decode"]) == 2
        steps = found["hvd_tpu_serve_step"]
        for name in ("hvd_tpu_engine_prefill", "hvd_tpu_engine_decode"):
            for a, z, _ in found[name]:
                assert any(sa <= a and z <= sz for sa, sz, _ in steps)
        # Scalar args known at entry ride along as the event's stats.
        assert found["hvd_tpu_engine_decode"][0][2]["active"] == 1
        # After-the-fact spans stay in the ring.
        assert "hvd_tpu_serve_queued" not in found


class TestServeFaultSite:
    def test_spec_parses(self):
        c = parse_fault_spec("serve:step=3,mode=kill")["serve"]
        assert (c.step, c.mode) == (3, "kill")
        c = parse_fault_spec("serve:p=0.2,seed=5,mode=drop")["serve"]
        assert (c.p, c.seed, c.mode) == (0.2, 5, "drop")
        c = parse_fault_spec("serve:step=2,mode=evict")["serve"]
        assert (c.step, c.mode) == (2, "evict")

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="mode"):
            parse_fault_spec("serve:step=1,mode=corrupt")

    def test_drop_and_delay_fire_on_requests_only(self):
        with faults.inject("serve:step=0,mode=drop"):
            assert faults.on_serve_decode() is False   # wrong hook: no-op
            assert faults.on_serve_request("GenerateRequest") == "drop"
            assert faults.on_serve_request("GenerateRequest") is None
        with faults.inject("serve:step=0,mode=delay,delay_ms=50"):
            t0 = time.monotonic()
            assert faults.on_serve_request() is None
            assert time.monotonic() - t0 >= 0.05

    def test_kill_fires_on_decode_only(self):
        with faults.inject("serve:step=1,mode=kill"):
            assert faults.on_serve_request() is None   # wrong hook: no-op
            assert faults.on_serve_evict() is False    # wrong hook: no-op
            assert faults.on_serve_decode() is False   # event 0
            assert faults.on_serve_decode() is True    # event 1 fires
            assert faults.on_serve_decode() is False   # one-shot
            assert faults.history() == [("serve", 1, "kill")]

    def test_evict_fires_on_allocation_only(self):
        with faults.inject("serve:step=1,mode=evict"):
            assert faults.on_serve_request() is None   # wrong hook: no-op
            assert faults.on_serve_decode() is False   # wrong hook: no-op
            assert faults.on_serve_evict() is False    # event 0
            assert faults.on_serve_evict() is True     # event 1 fires
            assert faults.on_serve_evict() is False    # one-shot
            assert faults.history() == [("serve", 1, "evict")]


class TestReplicaGroups:
    def test_slot_groups_partition_the_mesh(self):
        groups = replica_slot_groups(2, world_size=8)
        assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
        assert replica_slot_groups(8, world_size=8) == [[i] for i in
                                                        range(8)]
        with pytest.raises(ValueError):
            replica_slot_groups(3, world_size=8)

    def test_register_replica_process_sets_idempotent(self):
        created = register_replica_process_sets(2)
        try:
            assert [list(ps.ranks) for ps in created] == \
                replica_slot_groups(2)
            again = register_replica_process_sets(2)
            assert [ps.process_set_id for ps in again] == \
                [ps.process_set_id for ps in created]
            # The groups are real process sets: axis_index_groups
            # partitions the mesh.
            groups = created[0].axis_index_groups()
            assert sorted(sum(groups, [])) == list(range(hvd.size()))
        finally:
            for ps in created:
                hvd.remove_process_set(ps)


def _replica(model_and_params, name, **batcher_kw):
    b = _batcher(model_and_params, **batcher_kw)
    return InferenceServer(b, key=KEY, name=name, host="127.0.0.1")


def _fast_router(replicas, **kw):
    kw.setdefault("retry_policy", RetryPolicy(attempts=8,
                                              base_delay_s=0.02,
                                              max_delay_s=0.1))
    kw.setdefault("probation_s", 30.0)
    return Router(replicas, KEY, **kw)


class TestServerRouter:
    def test_generate_over_the_wire(self, model_and_params):
        model, params = model_and_params
        srv = _replica(model_and_params, "r0")
        try:
            router = _fast_router([ReplicaSpec("r0",
                                               [("127.0.0.1", srv.port)])])
            resp = router.generate([3, 1, 4], max_new_tokens=5)
            assert resp.error is None
            assert resp.tokens == _greedy_reference(model, params,
                                                    [3, 1, 4], 5)
            assert resp.ttft_ms is not None and resp.ttft_ms > 0
        finally:
            srv.shutdown()

    def test_stats_endpoint(self, model_and_params):
        srv = _replica(model_and_params, "r0")
        try:
            router = _fast_router([ReplicaSpec("r0",
                                               [("127.0.0.1", srv.port)])])
            router.generate([1, 2], max_new_tokens=3)
            stats = router.replica_stats()
            entry = stats["r0"]
            assert entry["healthy"] is True
            assert entry["completed"] == 1
            assert entry["stats"]["requests_completed"] == 1
            assert entry["stats"]["tokens_out"] == 3
        finally:
            srv.shutdown()

    def test_prompt_too_long_is_terminal(self, model_and_params):
        srv = _replica(model_and_params, "r0")
        try:
            router = _fast_router([ReplicaSpec("r0",
                                               [("127.0.0.1", srv.port)])])
            resp = router.generate(list(range(30)), max_new_tokens=2)
            assert resp.error.startswith("prompt_too_long")
        finally:
            srv.shutdown()

    def test_busy_replica_fails_over(self, model_and_params):
        """Backpressure on one replica routes the request to another —
        the reject-when-full signal doing its job."""
        full = _replica(model_and_params, "full", max_queue=1)
        ok = _replica(model_and_params, "ok")
        try:
            # Wedge the 'full' replica: stop its batcher thread first so
            # the queue cannot drain, then fill the queue.
            full._batcher._stop.set()
            full._batcher._thread.join(timeout=5)
            for _ in range(20):
                try:
                    full._batcher.submit([1], SamplingParams())
                except QueueFullError:
                    break
            router = _fast_router(
                [ReplicaSpec("full", [("127.0.0.1", full.port)]),
                 ReplicaSpec("ok", [("127.0.0.1", ok.port)])])
            for i in range(3):
                resp = router.generate([i + 1, 2], max_new_tokens=3)
                assert resp.error is None, (i, resp.error)
        finally:
            full.shutdown()
            ok.shutdown()

    def test_drop_fault_is_absorbed_by_failover(self, model_and_params):
        srv = _replica(model_and_params, "r0")
        try:
            router = _fast_router(
                [ReplicaSpec("r0", [("127.0.0.1", srv.port)])],
                strikes=5, probation_s=0.05)
            with faults.inject("serve:step=0,mode=drop"):
                resp = router.generate([2, 3], max_new_tokens=3)
                assert [h[2] for h in faults.history()] == ["drop:"
                                                            "GenerateRequest"]
            assert resp.error is None and len(resp.tokens) == 3
        finally:
            srv.shutdown()

    def test_delay_fault_slows_but_succeeds(self, model_and_params):
        srv = _replica(model_and_params, "r0")
        try:
            router = _fast_router([ReplicaSpec("r0",
                                               [("127.0.0.1", srv.port)])])
            with faults.inject("serve:step=0,mode=delay,delay_ms=150"):
                t0 = time.monotonic()
                resp = router.generate([2, 3], max_new_tokens=2)
                assert time.monotonic() - t0 >= 0.15
            assert resp.error is None
        finally:
            srv.shutdown()

    def test_empty_prompt_is_terminal_not_a_replica_crash(
            self, model_and_params):
        """A poison request (empty prompt) must come back as a terminal
        error response — an escaped exception would close the socket,
        strike the replica, and bench the healthy fleet retrying it."""
        srv = _replica(model_and_params, "r0")
        try:
            router = _fast_router([ReplicaSpec("r0",
                                               [("127.0.0.1", srv.port)])])
            resp = router.generate([], max_new_tokens=2)
            assert resp.error.startswith("invalid_request"), resp.error
            assert router.replica_stats()["r0"]["healthy"] is True
        finally:
            srv.shutdown()

    def test_half_open_probation_rehabilitates_replica(
            self, model_and_params):
        """A benched replica that recovered rejoins via the single
        half-open probe after its probation window."""
        srv = _replica(model_and_params, "r0")
        try:
            router = _fast_router(
                [ReplicaSpec("r0", [("127.0.0.1", srv.port)])],
                strikes=1, probation_s=0.05)
            rep = router._replicas[0]
            router._strike(rep, fatal=True)       # benched
            assert rep.dead_until is not None
            time.sleep(0.06)                       # probation expires
            resp = router.generate([1, 2], max_new_tokens=2)
            assert resp.error is None
            assert rep.dead_until is None and rep.strikes == 0
        finally:
            srv.shutdown()

    def test_all_replicas_dead_raises(self, model_and_params):
        from horovod_tpu.serve import NoHealthyReplicasError

        srv = _replica(model_and_params, "r0")
        srv.shutdown()   # nobody home
        router = _fast_router(
            [ReplicaSpec("r0", [("127.0.0.1", srv.port)])],
            retry_policy=RetryPolicy(attempts=2, base_delay_s=0.01),
            strikes=1, probation_s=30.0)
        with pytest.raises((NoHealthyReplicasError, ConnectionError)):
            router.generate([1], max_new_tokens=2)


class TestRouterPrefixAffinity:
    """ISSUE 10 satellite: requests whose prefix is resident on a
    replica prefer that replica; benched replicas fall back to the
    least-loaded spread."""

    def test_pick_prefers_resident_replica(self):
        router = _fast_router([ReplicaSpec("r0", [("127.0.0.1", 1)]),
                               ReplicaSpec("r1", [("127.0.0.1", 2)])])
        key = tuple(range(16))
        r1 = router._replicas[1]
        router._note_affinity(key, r1)
        for _ in range(4):                      # beats round-robin
            assert router._pick(key) is r1
        # A benched resident replica falls back to the healthy one.
        r1.dead_until = time.monotonic() + 60.0
        assert router._pick(key) is router._replicas[0]
        r1.dead_until = None
        # A SATURATED resident spills to the spread — one hot system
        # prompt must not pin the fleet to a single replica and bench
        # healthy peers through busy-strikes.
        r1.inflight = router._affinity_slack + 1
        assert router._pick(key) is router._replicas[0]
        r1.inflight = 0
        assert router._pick(key) is r1          # slack restored: warm wins
        # Short prompts have no block-aligned key: no affinity.
        assert router._prefix_key([1, 2, 3]) is None

    def test_same_prefix_requests_land_on_one_replica(self,
                                                      model_and_params):
        a = _replica(model_and_params, "aff-a")
        b = _replica(model_and_params, "aff-b")
        try:
            router = _fast_router(
                [ReplicaSpec("aff-a", [("127.0.0.1", a.port)]),
                 ReplicaSpec("aff-b", [("127.0.0.1", b.port)])])
            prompt = list(range(16))           # one full default block
            for i in range(4):
                resp = router.generate(prompt, max_new_tokens=2,
                                       request_id=f"aff-{i}")
                assert resp.error is None
            done = sorted(r.completed for r in router._replicas)
            assert done == [0, 4], done         # all stuck to one
        finally:
            a.shutdown()
            b.shutdown()


@pytest.mark.chaos
class TestChaosServeFailover:
    """ISSUE 3 acceptance: kill a replica mid-decode; every request
    completes on a survivor, none lost, none duplicated.  Injection
    point and seed come from the soak knobs."""

    def test_replica_kill_mid_decode_fails_over(self, model_and_params):
        fault_step = int(os.environ.get("HVD_TPU_CHAOS_STEP", "3"))
        seed = int(os.environ.get("HVD_TPU_CHAOS_SEED", "0"))
        n_requests, n_tokens = 6, 6
        # The one-shot kill must land inside the run's decode events:
        # ~ (n_tokens - 1) decodes per request across both replicas.
        assert fault_step < n_requests * (n_tokens - 1)
        model, params = model_and_params
        a = _replica(model_and_params, "replica-a")
        b = _replica(model_and_params, "replica-b")
        try:
            router = _fast_router(
                [ReplicaSpec("replica-a", [("127.0.0.1", a.port)]),
                 ReplicaSpec("replica-b", [("127.0.0.1", b.port)])],
                retry_policy=RetryPolicy(attempts=10, base_delay_s=0.02,
                                         max_delay_s=0.2))
            responses = {}
            with faults.inject(f"serve:step={fault_step},seed={seed},"
                               f"mode=kill"):
                for i in range(n_requests):
                    rid = f"chaos-{i}"
                    resp = router.generate([i + 1, i + 2, i + 3],
                                           max_new_tokens=n_tokens,
                                           request_id=rid)
                    # no losses: every request returns a full answer
                    assert resp.error is None, (i, resp.error)
                    assert len(resp.tokens) == n_tokens
                    assert resp.request_id == rid
                    assert rid not in responses   # no duplicates
                    responses[rid] = resp
                kills = [h for h in faults.history() if h[0] == "serve"]
            assert kills == [("serve", fault_step, "kill")], kills
            # Exactly one replica died; the survivor carried the load.
            assert sorted([a.dead, b.dead]) == [False, True]
            # Failover preserved correctness, not just liveness.
            for i in range(n_requests):
                assert responses[f"chaos-{i}"].tokens == _greedy_reference(
                    model, params, [i + 1, i + 2, i + 3], n_tokens)
            # At-most-once delivery: a replayed request id returns the
            # cached response without re-running generation.
            again = router.generate([99], max_new_tokens=2,
                                    request_id="chaos-0")
            assert again is responses["chaos-0"]
        finally:
            a.shutdown()
            b.shutdown()

    def test_replica_kill_mid_spec_decode_fails_over(self,
                                                     model_and_params):
        """ISSUE 10: a replica killed mid-SPECULATIVE-decode completes
        on the survivor with greedy-identical output — failover and
        accepted-prefix semantics compose."""
        seed = int(os.environ.get("HVD_TPU_CHAOS_SEED", "0"))
        # Spec bursts shrink the decode-dispatch count (~2/request
        # here), so fold the soak's step into the in-range window.
        fault_step = int(os.environ.get("HVD_TPU_CHAOS_STEP", "3")) % 10
        model, params = model_and_params
        spec_kw = {"engine_kw": {"kv_cache": "paged", "kv_block": 4,
                                 "drafter": (model, params),
                                 "spec_k": 2}}
        a = _replica(model_and_params, "spec-a", **spec_kw)
        b = _replica(model_and_params, "spec-b", **spec_kw)
        try:
            router = _fast_router(
                [ReplicaSpec("spec-a", [("127.0.0.1", a.port)]),
                 ReplicaSpec("spec-b", [("127.0.0.1", b.port)])],
                retry_policy=RetryPolicy(attempts=10, base_delay_s=0.02,
                                         max_delay_s=0.2))
            with faults.inject(f"serve:step={fault_step},seed={seed},"
                               f"mode=kill"):
                for i in range(6):
                    resp = router.generate([i + 1, i + 2, i + 3],
                                           max_new_tokens=6, spec=True)
                    assert resp.error is None, (i, resp.error)
                    assert resp.tokens == _greedy_reference(
                        model, params, [i + 1, i + 2, i + 3], 6), i
                kills = [h for h in faults.history() if h[0] == "serve"]
            assert kills == [("serve", fault_step, "kill")], kills
            assert sorted([a.dead, b.dead]) == [False, True]
        finally:
            a.shutdown()
            b.shutdown()


@pytest.mark.chaos
class TestChaosServeEvict:
    """ISSUE 10 satellite: seeded page-eviction pressure
    (``serve:mode=evict``) — an evicted-then-readmitted prefix must
    recompute, never serve stale blocks.  ``scripts/chaos_soak.py
    --mode serve`` loops this with randomized injection points."""

    def test_evict_pressure_never_serves_stale_blocks(self,
                                                      model_and_params):
        seed = int(os.environ.get("HVD_TPU_CHAOS_SEED", "0"))
        # Fold the soak's step into the run's allocation-event window
        # (shared prefixes keep the allocation count small).
        fault_step = int(os.environ.get("HVD_TPU_CHAOS_STEP", "3")) % 8
        model, params = model_and_params
        b = _batcher(model_and_params,
                     engine_kw={"kv_cache": "paged", "kv_block": 4})
        pre = [31, 32, 33, 34, 35, 36, 37, 38]    # shared system prompt
        # Prime the cache BEFORE arming: the shared prefix is resident,
        # so whichever allocation event the fault lands on has cached
        # blocks to evict (otherwise a step-0 firing legitimately
        # evicts nothing and the eviction-counter assert below would
        # misread an empty cache as a broken drill).
        prime = b.submit(pre + [88], SamplingParams(max_new_tokens=4))
        _pump(b, [prime])
        # 8 requests x 1 tail-block allocation each = 8 events, so the
        # folded fault_step (mod 8) always lands on a real allocation.
        with faults.inject(f"serve:step={fault_step},seed={seed},"
                           f"mode=evict"):
            for i in range(8):
                prompt = pre + [i + 1]
                r = b.submit(prompt, SamplingParams(max_new_tokens=4))
                _pump(b, [r])
                assert r.error is None, (i, r.error)
                # THE oracle: eviction may cost a recompute, but the
                # tokens must be exactly what a cold cache produces.
                assert r.tokens == _greedy_reference(model, params,
                                                     prompt, 4), i
            evicts = [h for h in faults.history()
                      if h[0] == "serve" and h[2].startswith("evict")]
        assert evicts == [("serve", fault_step, "evict")], evicts
        assert b.snapshot()["kv_evictions_total"] > 0
