"""Tensor-parallel sharded serving replicas (ISSUE 19;
docs/tp_serving.md): the token-identity oracle — a TP=2 and TP=4
engine must emit BIT-identical tokens to TP=1 for greedy and
temperature sampling, through a prefix-cache hit, a COW divergence,
and a speculative-decode batch — plus the plan-level sharding/
ownership helpers, the head-sharded pool geometry, per-shard migration
digests, the swap shard-pull byte math, and the lockstep wire
(serve/tp.py) in-process.  TP=2 (the r19 acceptance gate) runs in
tier-1; the TP=4 twins of the engine-heavy oracle cases ride the slow
tier to keep the tier-1 wall-clock budget."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import step_state_oracle
from horovod_tpu.models.transformer import GPT, GPTConfig
from horovod_tpu.plan import tp_owned_slice, tp_param_spec, tp_plan
from horovod_tpu.serve import (
    ContinuousBatcher, InferenceEngine, ReplicaKilledError, SamplingParams,
    ShardFollower, ShardLockstepError, ShardServer,
)
from horovod_tpu.serve.fleet.migration import (
    MigrationError, block_digests, shard_digests, verify_shard_digests,
)
from horovod_tpu.serve.tp import step_digest

pytestmark = pytest.mark.serving

KEY = b"k" * 32
VOCAB = 97


@pytest.fixture(scope="module")
def model_and_params():
    # n_head=4 so TP in {1, 2, 4} all divide the head count.
    cfg = GPTConfig(vocab_size=VOCAB, n_layer=2, n_head=4, d_model=32,
                    d_ff=64, max_seq_len=32, dtype=jnp.float32,
                    param_dtype=jnp.float32)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model_and_params, tp=1, **kw):
    model, params = model_and_params
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("kv_cache", "paged")
    kw.setdefault("kv_block", 4)
    kw.setdefault("seed", 7)
    return InferenceEngine(model, params, tp=tp, **kw)


def _decode(engine, slot, prompt, n, **sampling_kw):
    sampling_kw.setdefault("max_new_tokens", n)
    toks = [engine.start(slot, prompt, SamplingParams(**sampling_kw))]
    while len(toks) < n:
        toks.extend(engine.step()[slot])
    engine.release(slot)
    return toks[:n]


class TestPlanHelpers:
    """plan/mesh_plan.py: the device-placement spec (bitwise-identity
    constrained) vs the transport-ownership slice (every divisible
    leaf) — two different rules on purpose (docs/tp_serving.md)."""

    def test_param_spec_shards_only_column_parallel_kernels(self):
        w = np.zeros((32, 96))
        b = np.zeros((96,))
        # qkv / up kernels: output dim sharded (full contraction per
        # output element keeps the forward bitwise-identical).
        assert tp_param_spec("h0/attn/qkv/kernel", w, 2) == P(None, "tensor")
        assert tp_param_spec("h0/mlp/up/kernel", w, 2) == P(None, "tensor")
        assert tp_param_spec("h0/attn/qkv/bias", b, 2) == P("tensor")
        # out / down projections contract over the sharded dim — their
        # kernels stay replicated (gather-before-contract).
        assert tp_param_spec("h0/attn/out/kernel", w, 2) == P()
        assert tp_param_spec("h0/mlp/down/kernel", w, 2) == P()
        assert tp_param_spec("wte/embedding", w, 2) == P()
        # tp=1 and non-divisible shapes are always replicated.
        assert tp_param_spec("h0/attn/qkv/kernel", w, 1) == P()
        assert tp_param_spec("h0/attn/qkv/kernel",
                             np.zeros((32, 97)), 2) == P()

    @pytest.mark.parametrize("tp", [2, 4])
    def test_owned_slices_tile_exactly(self, tp):
        shape = (12, 32)
        spans = [tp_owned_slice("any/leaf", shape, tp, r)
                 for r in range(tp)]
        dims = {s[0] for s in spans}
        assert dims == {1}                      # largest divisible dim
        ends = sorted((s[1], s[2]) for s in spans)
        assert ends[0][0] == 0 and ends[-1][1] == 32
        for (a, b), (c, d) in zip(ends, ends[1:]):
            assert b == c                       # contiguous, no overlap
        # Reassembly in rank order is exact.
        arr = np.arange(12 * 32, dtype=np.float32).reshape(shape)
        parts = [arr[:, s[1]:s[2]] for s in sorted(spans,
                                                   key=lambda s: s[1])]
        np.testing.assert_array_equal(np.concatenate(parts, axis=1), arr)

    def test_owned_slice_indivisible_is_unsharded(self):
        assert tp_owned_slice("x", (7, 13), 2, 0) is None
        assert tp_owned_slice("x", (8, 8), 1, 0) is None

    def test_tp_plan_builds_tensor_mesh(self):
        plan = tp_plan(2)
        assert plan.mesh.axis_names == ("tensor",)
        assert plan.mesh.devices.size == 2


class TestTokenIdentityOracle:
    """The r19 acceptance property: TP-sharded decode is BIT-identical
    to TP=1 on the CPU tier-1 mesh, not approximately equal."""

    PROMPT = [5, 6, 7, 8, 9]

    def _greedy_and_temperature(self, model_and_params, degrees):
        """Greedy and seeded temperature + top-k sampling, run as the
        same request sequence on a TP=1 and a TP=N engine: all streams
        identical because the LOGITS are identical (bitwise) and the
        per-slot RNG streams are seed-deterministic."""
        outs = {}
        for deg in degrees:
            eng = _engine(model_and_params, tp=deg)
            outs[deg] = (
                _decode(eng, 0, self.PROMPT, 8),
                _decode(eng, 0, self.PROMPT, 8, temperature=0.8, top_k=10),
            )
        base = outs[degrees[0]]
        assert all(outs[d] == base for d in degrees), outs

    def test_greedy_and_temperature_identity(self, model_and_params):
        self._greedy_and_temperature(model_and_params, (1, 2))

    @pytest.mark.slow
    def test_greedy_and_temperature_identity_tp4(self, model_and_params):
        self._greedy_and_temperature(model_and_params, (1, 4))

    def test_prefix_hit_and_cow_identity(self, model_and_params):
        """The paged-pool flows on one engine pair, same request
        history at both degrees.  Prefix hit: a second request sharing
        the first one's prompt prefix must (a) actually hit the cache
        on the sharded engine and (b) decode identically to TP=1 —
        resident head-sharded blocks are reused, not recomputed.  COW:
        two live requests share a partial tail block then diverge; the
        copy happens on the sharded pool (counter proves it) and both
        streams stay identical to TP=1."""
        pre = [11, 12, 13, 14, 15, 16, 17, 18]     # two full blocks
        pa, pb = pre + [1], pre + [2]
        ca = [5, 6, 7, 8, 9]
        cb = [5, 6, 7, 8, 9, 3]
        outs = {}
        for tp in (1, 2):
            eng = _engine(model_and_params, tp=tp)
            # Prefix-cache hit.
            a = _decode(eng, 0, pa, 5)
            hits0 = eng.kv_stats()["kv_prefix_hits_total"]
            b = _decode(eng, 1, pb, 5)
            assert eng.kv_stats()["kv_prefix_hits_total"] > hits0
            # COW divergence.
            x = [eng.start(0, ca, SamplingParams(max_new_tokens=8))]
            x.extend(eng.step()[0])
            y = [eng.start(1, cb, SamplingParams(max_new_tokens=6))]
            assert eng.prefix_hit_tokens(1) == 5
            for _ in range(4):
                toks = eng.step()
                x.extend(toks[0])
                y.extend(toks[1])
            assert eng.kv_stats()["kv_cow_copies_total"] >= 1
            eng.release(0)
            eng.release(1)
            outs[tp] = (a, b, x, y)
        assert outs[2] == outs[1], outs

    def _spec_identity(self, model_and_params, degrees):
        """Self-drafted speculative decode on the sharded engine: the
        drafter runs unsharded on one device, its draft re-homes onto
        the TP mesh for verification, and the burst is identical to
        TP=1 with the same full-acceptance ratio."""
        model, params = model_and_params
        outs, ratios = {}, {}
        for deg in degrees:
            eng = _engine(model_and_params, tp=deg,
                          drafter=(model, params), spec_k=3)
            toks = [eng.start(0, self.PROMPT,
                              SamplingParams(max_new_tokens=9, spec=True))]
            while len(toks) < 9:
                toks.extend(eng.step()[0])
            eng.release(0)
            outs[deg] = toks[:9]
            ratios[deg] = eng.kv_stats()["spec_accept_per_verify"]
        base = outs[degrees[0]]
        assert all(outs[d] == base for d in degrees), outs
        # Perfect drafter: the whole draft is accepted at every degree.
        assert all(ratios[d] == 4.0 for d in degrees), ratios

    def test_speculative_batch_identity(self, model_and_params):
        self._spec_identity(model_and_params, (1, 2))

    def test_step_state_lifecycle_identity(self, model_and_params):
        """ISSUE 30: the slots' step state lives on the device,
        replicated over the tensor mesh, and the decode program
        advances it.  Through admissions, steady steps, a finish, a
        release from another thread, a preemption and its resume the
        state read back equals the host's mirrors after every step, at
        both degrees; the tokens (greedy, temperature, top-k) and the
        key are TP=1's, and no program is built twice."""
        prompts = ([3, 1, 4, 1, 5], [9, 8, 7], list(range(10)))
        outs = {}
        for tp in (1, 2):
            eng = _engine(model_and_params, tp=tp, seed=11)
            served = step_state_oracle.drive_lifecycle(eng, prompts)
            assert set(eng.trace_counts.values()) == {1}, eng.trace_counts
            assert eng._decode_fn._cache_size() == 1
            for leaf in eng._step_state.values():
                assert leaf.committed
                assert len(leaf.sharding.device_set) == tp
                assert leaf.sharding.is_fully_replicated
            outs[tp] = ([toks for _, _, toks in served],
                        eng.export_rng().tolist())
        assert outs[2] == outs[1], outs

    @pytest.mark.slow
    def test_speculative_batch_identity_tp4(self, model_and_params):
        self._spec_identity(model_and_params, (1, 4))


class TestShardedPoolGeometry:
    """Satellite 1: BlockPool.stats() self-describes the shard layout
    so ``hvd_tpu_serve_kv_blocks_in_use`` stays fleet-comparable —
    block counts are per-REPLICA (rank-invariant), while
    ``bytes_per_block`` reflects the H/tp heads each shard holds."""

    def test_stats_fields_tp1_vs_tp2(self, model_and_params):
        model, _ = model_and_params
        s1 = _engine(model_and_params, tp=1).kv_stats()
        s2 = _engine(model_and_params, tp=2).kv_stats()
        assert s1["tp_degree"] == 1 and s2["tp_degree"] == 2
        assert s1["heads"] == model.config.n_head
        assert s2["heads"] == model.config.n_head // 2
        # Same block budget (host state is rank-invariant); each
        # shard's slab holds half the bytes per block.
        assert s2["bytes_per_block"] * 2 == s1["bytes_per_block"]

    def test_head_divisibility_enforced(self, model_and_params):
        with pytest.raises(ValueError, match="divide"):
            _engine(model_and_params, tp=3)

    def test_tp_requires_paged_kv(self, model_and_params):
        with pytest.raises(ValueError, match="paged"):
            _engine(model_and_params, tp=2, kv_cache="dense")


class TestShardMigrationDigests:
    """Per-shard manifest digests: each TP shard's KV stream verifies
    independently (serve/fleet/migration.py)."""

    def _blocks(self, n_layer=2, n_blocks=3, block=4, heads=4, d=8):
        rng = np.random.default_rng(3)
        shape = (n_layer, n_blocks, block, heads, d)
        return (rng.standard_normal(shape).astype(np.float32),
                rng.standard_normal(shape).astype(np.float32))

    def test_shard_digests_verify_per_shard(self):
        k, v = self._blocks()
        manifest = {"n_blocks": 3,
                    "shard_digests": shard_digests(k, v, 2)}
        hs = k.shape[3] // 2
        for s in range(2):
            ks = k[:, :, :, s * hs:(s + 1) * hs]
            vs = v[:, :, :, s * hs:(s + 1) * hs]
            verify_shard_digests(manifest, s, ks, vs)   # must not raise

    def test_corrupt_shard_rejected_others_pass(self):
        k, v = self._blocks()
        manifest = {"n_blocks": 3,
                    "shard_digests": shard_digests(k, v, 2)}
        hs = k.shape[3] // 2
        bad_k = k[:, :, :, :hs].copy()
        bad_k[0, 1, 0, 0, 0] += 1.0
        with pytest.raises(MigrationError):
            verify_shard_digests(manifest, 0, bad_k, v[:, :, :, :hs])
        verify_shard_digests(manifest, 1, k[:, :, :, hs:],
                             v[:, :, :, hs:])           # untouched shard

    def test_shard_digests_concatenate_to_full(self):
        """The head-wise split loses nothing: re-concatenated shards
        carry exactly the full-pool digests."""
        k, v = self._blocks()
        full = block_digests(k, v)
        hs = k.shape[3] // 2
        rk = np.concatenate([k[:, :, :, :hs], k[:, :, :, hs:]], axis=3)
        rv = np.concatenate([v[:, :, :, :hs], v[:, :, :, hs:]], axis=3)
        assert block_digests(rk, rv) == full


class TestSwapShardPull:
    """Swap economics under TP: a shard pulls only its owned parameter
    slices, so the replica's critical-path pull bytes ~halve at TP=2
    (the bench asserts the <= 0.6 acceptance bound end-to-end;
    this is the byte-math unit test)."""

    def test_owned_bytes_sum_to_full(self):
        shapes = [(32, 96), (96,), (31, 7), (16, 16)]
        total = sum(int(np.prod(s)) * 4 for s in shapes)
        per_shard = [0, 0]
        for shape in shapes:
            for r in range(2):
                span = tp_owned_slice("leaf", shape, 2, r)
                if span is None:
                    per_shard[r] += int(np.prod(shape)) * 4
                else:
                    dim, start, stop = span
                    n = int(np.prod(shape)) // shape[dim] * (stop - start)
                    per_shard[r] += n * 4
        # Divisible leaves split exactly; the indivisible (31, 7) leaf
        # replicates to both shards.
        indivisible = 31 * 7 * 4
        assert per_shard[0] == per_shard[1]
        assert sum(per_shard) == total + indivisible


class TestLockstepWire:
    """serve/tp.py in-process: a follower shard rank driven over real
    HMAC frames stays in lockstep with the leader's batcher; losing it
    mid-decode kills the WHOLE replica (``shard_rank_lost``)."""

    def _pair(self, model_and_params):
        leader = _engine(model_and_params)
        follower = _engine(model_and_params)
        shard = ShardServer(follower, KEY, name="shard-1",
                            host="127.0.0.1")
        batcher = ContinuousBatcher(leader, max_queue=8)
        batcher.set_lockstep(ShardFollower(
            [("shard-1", [("127.0.0.1", shard.port)])], KEY, timeout=30.0))
        return leader, follower, shard, batcher

    def test_follower_mirrors_then_lost_shard_kills_replica(
            self, model_and_params):
        """One pair, the whole lifecycle: a request decodes in lockstep
        (follower state mirrors the leader's, tokens match the
        unsharded oracle), then the shard rank dies mid-decode and the
        WHOLE replica dies with it."""
        leader, follower, shard, batcher = self._pair(model_and_params)
        req = batcher.submit([5, 6, 7, 8, 9],
                             SamplingParams(max_new_tokens=6))
        while not req.done.is_set():
            batcher.step()
        assert req.error is None and len(req.tokens) == 6
        # Lockstep left identical host state on both ranks: the slot
        # was started AND released on the follower too.
        assert follower.free_slots() == leader.free_slots()
        # Identical engines in lockstep emit identical tokens: the
        # (now idle) follower re-decodes the same prompt directly.
        got = _decode(follower, 0, [5, 6, 7, 8, 9], 6)
        assert req.tokens == got
        # Now lose the shard rank mid-decode.
        req2 = batcher.submit([5, 6, 7, 8, 9],
                              SamplingParams(max_new_tokens=16))
        batcher.step()                        # prefill + first decode
        shard.shutdown()                      # the shard rank dies
        with pytest.raises(ReplicaKilledError, match="shard_rank_lost"):
            for _ in range(20):
                batcher.step()
        assert req2.error == "replica_killed"
        with pytest.raises(ReplicaKilledError):
            batcher.submit([1, 2, 3], SamplingParams())

    def test_follower_refusal_kills_replica(self, model_and_params):
        """A not-ok answer (not just a dead socket) is equally fatal:
        the follower's engine state can no longer be trusted."""
        leader, follower, shard, batcher = self._pair(model_and_params)
        try:
            fw = batcher._lockstep
            with pytest.raises(ShardLockstepError, match="refused"):
                fw("start", {"slot": 99, "prompt": [1], "sampling": None})
        finally:
            shard.shutdown()

    def test_step_digest_is_order_invariant(self):
        a = {0: [3, 4], 1: [5]}
        b = {1: [5], 0: [3, 4]}
        assert step_digest(a) == step_digest(b)
        assert step_digest(a) != step_digest({0: [3, 4], 1: [6]})
