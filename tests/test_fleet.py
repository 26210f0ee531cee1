"""Disaggregated prefill/decode fleet (horovod_tpu/serve/fleet/):
live KV migration with per-block digests, the global prefix directory,
role-aware router dispatch, drain-and-retire, and elastic autoscaling.

The migration oracle (ISSUE 11 acceptance): prefill-on-A → migrate →
decode-on-B must be token-identical to single-replica generation for
greedy, temperature, and speculative requests — and the
``serve:mode=migrate`` corrupt drill must never emit a wrong token (it
recovers on a correct recompute path).  The chaos class at the bottom
is the fleet drill: a replica killed mid-migration plus a forced
scale-out + drain-and-retire cycle, with no request lost or
duplicated (``scripts/chaos_soak.py --mode serve`` loops it)."""

import socket
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greedy_oracle import greedy_reference as _greedy_reference
from horovod_tpu import faults
from horovod_tpu.config import parse_fault_spec
from horovod_tpu.models.transformer import GPT, GPTConfig
from horovod_tpu.serve import (
    ContinuousBatcher, FleetController, InferenceEngine, InferenceServer,
    ReplicaDrainingError, ReplicaLauncher, ReplicaSpec, Router,
    SamplingParams,
)
from horovod_tpu.serve.fleet import PrefixDirectory, migration
from horovod_tpu.serve.kv import BlockPool
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
    kw.setdefault("kv_block", 4)
    return InferenceEngine(model, params, **kw)


def _drive(engine, slot, n):
    toks = []
    while len(toks) < n:
        toks.extend(engine.step()[slot])
    return toks[:n]


def _replica(model_and_params, name, role="unified", engine_kw=None,
             **server_kw):
    engine = _engine(model_and_params, **(engine_kw or {}))
    batcher = ContinuousBatcher(engine, max_queue=16,
                                default_deadline_s=60, role=role)
    return InferenceServer(batcher, key=KEY, name=name, host="127.0.0.1",
                           **server_kw)


def _fast_router(replicas, **kw):
    kw.setdefault("retry_policy", RetryPolicy(attempts=8,
                                              base_delay_s=0.02,
                                              max_delay_s=0.2))
    kw.setdefault("probation_s", 30.0)
    return Router(replicas, KEY, **kw)


def _spec(server):
    return ReplicaSpec(server.name, [("127.0.0.1", server.port)],
                       role=server.role)


class TestKvExportImport:
    """Engine-level migration oracle: export on A, import on B,
    continue token-identically."""

    def test_greedy_identity(self, model_and_params):
        model, params = model_and_params
        prompt, n = [3, 1, 4, 1, 5, 9, 2, 6], 6
        a = _engine(model_and_params, seed=7)
        b = _engine(model_and_params, seed=99)   # different seed: greedy
        t0 = a.start(0, prompt, SamplingParams(max_new_tokens=n))
        nb, k, v = a.export_slot_kv(0)
        assert nb == 2 and k.shape[1] == 2       # ceil(8 / 4) live blocks
        b.import_slot_kv(0, prompt, k, v, t0,
                         SamplingParams(max_new_tokens=n))
        got = [t0] + _drive(b, 0, n - 1)
        assert got == _greedy_reference(model, params, prompt, n)

    def test_temperature_identity_with_rng(self, model_and_params):
        """With the sender's post-prefill PRNG key migrated and adopted
        by an idle importer, temperature sampling is bit-identical to
        the single-replica run."""
        prompt, n = [3, 1, 4, 1, 5, 9, 2, 6], 6
        sp = SamplingParams(max_new_tokens=n, temperature=0.8, top_k=5)
        ref = _engine(model_and_params, seed=7)
        want = [ref.start(0, prompt, sp)] + _drive(ref, 0, n - 1)
        a = _engine(model_and_params, seed=7)
        b = _engine(model_and_params, seed=12345)
        t0 = a.start(0, prompt, sp)
        nb, k, v = a.export_slot_kv(0)
        b.import_slot_kv(0, prompt, k, v, t0, sp, rng=a.export_rng())
        got = [t0] + _drive(b, 0, n - 1)
        assert got == want

    def test_spec_identity(self, model_and_params):
        """A migrated-in request decodes speculatively on the importer
        (drafter prefill re-runs at import) and stays greedy-identical."""
        model, params = model_and_params
        prompt, n = [2, 7, 1, 8, 2, 8], 8
        sp = SamplingParams(max_new_tokens=n, spec=True)
        a = _engine(model_and_params)
        b = _engine(model_and_params, drafter=(model, params), spec_k=2)
        t0 = a.start(0, prompt, sp)
        nb, k, v = a.export_slot_kv(0)
        b.import_slot_kv(0, prompt, k, v, t0, sp)
        got = [t0] + _drive(b, 0, n - 1)
        assert got == _greedy_reference(model, params, prompt, n)
        assert b.spec_verify_steps > 0           # really took the spec path

    def test_export_after_prefix_hit_still_complete(self,
                                                    model_and_params):
        """A prefill whose prompt HIT the local prefix cache (shared /
        COW chain) still exports the full prompt's KV — the chain is
        the manifest regardless of how its blocks were produced."""
        model, params = model_and_params
        pre = [11, 12, 13, 14, 15, 16, 17, 18]
        a = _engine(model_and_params)
        a.start(0, pre + [1], SamplingParams(max_new_tokens=2))
        _drive(a, 0, 1)
        a.release(0)                              # prefix stays resident
        prompt, n = pre + [2], 5
        t0 = a.start(0, prompt, SamplingParams(max_new_tokens=n))
        assert a.prefix_hit_tokens(0) >= 8        # the hit really happened
        nb, k, v = a.export_slot_kv(0)
        b = _engine(model_and_params)
        b.import_slot_kv(0, prompt, k, v, t0,
                         SamplingParams(max_new_tokens=n))
        got = [t0] + _drive(b, 0, n - 1)
        assert got == _greedy_reference(model, params, prompt, n)

    def test_digest_verification_rejects_corruption(self,
                                                    model_and_params):
        a = _engine(model_and_params)
        t0 = a.start(0, [5, 6, 7, 8, 9], SamplingParams(max_new_tokens=2))
        nb, k, v = a.export_slot_kv(0)
        manifest = {"n_blocks": nb,
                    "digests": migration.block_digests(k, v)}
        migration.verify_digests(manifest, k, v)   # pristine: passes
        bad = k.copy()
        bad.reshape(-1).view(np.uint8)[:8] ^= 0xFF
        with pytest.raises(migration.MigrationError, match="digest"):
            migration.verify_digests(manifest, bad, v)
        del t0

    def test_import_validates_chain_length(self, model_and_params):
        a = _engine(model_and_params)
        t0 = a.start(0, [5, 6, 7, 8, 9], SamplingParams(max_new_tokens=2))
        nb, k, v = a.export_slot_kv(0)
        b = _engine(model_and_params)
        with pytest.raises(ValueError, match="does not cover"):
            b.import_slot_kv(0, [5, 6, 7, 8, 9], k[:, :1], v[:, :1], t0,
                             SamplingParams(max_new_tokens=2))

    def test_bind_imported_pool_accounting(self):
        table = np.zeros((2, 4), np.int32)
        pool = BlockPool(10, 4, table, lambda s, d: None)
        chain = pool.bind_imported(0, 2)
        assert len(chain) == 2 and pool.blocks_in_use() == 2
        assert list(table[0, :2]) == chain
        with pytest.raises(RuntimeError, match="already has a chain"):
            pool.bind_imported(0, 1)
        pool.index_prompt(0, [1, 2, 3, 4, 5, 6, 7, 8])
        pool.release(0)
        assert pool.blocks_in_use() == 0
        assert pool.probe([1, 2, 3, 4, 5, 6, 7, 8]) == 7  # resident, shared

    def test_bind_imported_rolls_back_on_exhaustion(self):
        """Mid-chain pool exhaustion must not leak the blocks already
        allocated — they are attached to no chain, so nothing would
        ever release them."""
        from horovod_tpu.serve.kv import KVPoolExhaustedError

        table = np.zeros((2, 6), np.int32)
        pool = BlockPool(4, 4, table, lambda s, d: None)   # 3 usable
        with pytest.raises(KVPoolExhaustedError):
            pool.bind_imported(0, 5)                       # 5 > 3
        assert pool.blocks_in_use() == 0                   # rolled back
        assert len(pool.bind_imported(0, 3)) == 3          # all reusable

    def test_frame_planner_bounds_frames(self):
        assert migration.plan_frames(5, 100, 250) == [(0, 2), (2, 4),
                                                      (4, 5)]
        assert migration.plan_frames(3, 100, 10) == [(0, 1), (1, 2),
                                                     (2, 3)]
        assert migration.plan_frames(2, 100, 10 ** 9) == [(0, 2)]


class TestMigrationWire:
    """The admit→prefill→migrate→decode pipeline over real sockets."""

    def test_pipeline_greedy_identity(self, model_and_params):
        model, params = model_and_params
        pre = _replica(model_and_params, "pre-0", role="prefill")
        dec = _replica(model_and_params, "dec-0", role="decode")
        try:
            router = _fast_router([_spec(pre), _spec(dec)])
            prompt = [3, 1, 4, 1, 5, 9, 2, 6]
            resp = router.generate(prompt, max_new_tokens=6)
            assert resp.error is None
            assert resp.tokens == _greedy_reference(model, params,
                                                    prompt, 6)
            # The generation really crossed the fleet: prefill handed
            # off, decode carried it, the response names the target.
            assert resp.migrated_to == "dec-0"
            assert resp.migrate_ms is not None and resp.migrate_ms > 0
            stats = router.replica_stats(timeout=3.0)
            assert stats["pre-0"]["stats"]["requests_completed"] == 1
            assert stats["dec-0"]["stats"]["requests_completed"] == 1
        finally:
            pre.shutdown()
            dec.shutdown()

    def test_pipeline_temperature_identity(self, model_and_params):
        prompt, n = [3, 1, 4, 1, 5, 9, 2, 6], 6
        sp = SamplingParams(max_new_tokens=n, temperature=0.7, top_k=4)
        ref = _engine(model_and_params, seed=7)
        want = [ref.start(0, prompt, sp)] + _drive(ref, 0, n - 1)
        pre = _replica(model_and_params, "pre-t", role="prefill",
                       engine_kw={"seed": 7})
        dec = _replica(model_and_params, "dec-t", role="decode",
                       engine_kw={"seed": 4242})
        try:
            router = _fast_router([_spec(pre), _spec(dec)])
            resp = router.generate(prompt, max_new_tokens=n,
                                   temperature=0.7, top_k=4)
            assert resp.error is None
            assert resp.migrated_to == "dec-t"
            assert resp.tokens == want
        finally:
            pre.shutdown()
            dec.shutdown()

    def test_pipeline_spec_identity(self, model_and_params):
        model, params = model_and_params
        pre = _replica(model_and_params, "pre-s", role="prefill")
        dec = _replica(model_and_params, "dec-s", role="decode",
                       engine_kw={"drafter": (model, params),
                                  "spec_k": 2})
        try:
            router = _fast_router([_spec(pre), _spec(dec)])
            prompt = [2, 7, 1, 8, 2, 8]
            resp = router.generate(prompt, max_new_tokens=8, spec=True)
            assert resp.error is None
            assert resp.migrated_to == "dec-s"
            assert resp.tokens == _greedy_reference(model, params,
                                                    prompt, 8)
            snap = router.replica_stats(timeout=3.0)
            assert snap["dec-s"]["stats"]["spec_verify_steps"] > 0
        finally:
            pre.shutdown()
            dec.shutdown()

    def test_chunked_transfer_identity(self, model_and_params):
        """A 1-byte chunk budget forces one frame per block; assembly +
        digests still reproduce the stream exactly."""
        model, params = model_and_params
        pre = _replica(model_and_params, "pre-c", role="prefill",
                       migrate_chunk_bytes=1)
        dec = _replica(model_and_params, "dec-c", role="decode")
        try:
            router = _fast_router([_spec(pre), _spec(dec)])
            prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1]      # 3 blocks of 4
            resp = router.generate(prompt, max_new_tokens=5)
            assert resp.error is None
            assert resp.migrated_to == "dec-c"
            assert resp.tokens == _greedy_reference(model, params,
                                                    prompt, 5)
        finally:
            pre.shutdown()
            dec.shutdown()

    def test_directory_hit_lands_on_decode_replica(self,
                                                   model_and_params):
        """After a migration the decode replica holds the prefix; the
        next same-prefix request routes THERE via the global directory
        and full-serves against warm KV — no second pipeline pass."""
        model, params = model_and_params
        # Router keys the directory on HVD_TPU_SERVE_KV_BLOCK (16), so
        # prompts must span a full default block; replica engines use
        # kv_block=4 for cheap paging underneath.
        base = list(range(20, 36))                 # one 16-token key
        ekw = {"prefill_buckets": (8, 24)}         # 18-token prompts fit
        pre = _replica(model_and_params, "pre-d", role="prefill",
                       engine_kw=ekw)
        dec = _replica(model_and_params, "dec-d", role="decode",
                       engine_kw=ekw)
        try:
            router = _fast_router([_spec(pre), _spec(dec)])
            first = router.generate(base + [1, 2], max_new_tokens=4,
                                    request_id="dir-0")
            assert first.error is None and first.migrated_to == "dec-d"
            second = router.generate(base + [3, 4], max_new_tokens=4,
                                     request_id="dir-1")
            assert second.error is None
            assert second.migrated_to is None       # no second pipeline
            assert second.tokens == _greedy_reference(
                model, params, base + [3, 4], 4)
            stats = router.replica_stats(timeout=3.0)
            # Both requests finished on dec-d: one migrated in, one
            # directory-routed; the second hit resident prefix blocks.
            assert stats["dec-d"]["stats"]["requests_completed"] == 2
            assert stats["dec-d"]["stats"]["prefix_hits"] >= 1
            assert stats["pre-d"]["stats"]["requests_completed"] == 1
        finally:
            pre.shutdown()
            dec.shutdown()


class TestMigrateFaults:
    """``serve:mode=migrate*`` — damage at the KV-transfer boundary
    must never produce a wrong token."""

    def test_spec_grammar(self):
        for mode in ("migrate", "migrate-drop", "migrate-delay"):
            clause = parse_fault_spec(f"serve:step=0,mode={mode}")["serve"]
            assert clause.mode == mode
        with pytest.raises(ValueError, match="unknown mode"):
            parse_fault_spec("serve:step=0,mode=migrate-corrupt-all")

    def test_migrate_modes_fire_only_at_transfer_boundary(self):
        with faults.inject("serve:p=1.0,mode=migrate"):
            assert faults.on_serve_request("GenerateRequest") is None
            assert faults.on_serve_decode() is False
            assert faults.on_serve_evict() is False
            assert faults.on_serve_migrate() == "migrate"

    def _run_faulted(self, model_and_params, spec_str):
        model, params = model_and_params
        pre = _replica(model_and_params, "pre-f", role="prefill")
        dec = _replica(model_and_params, "dec-f", role="decode")
        try:
            router = _fast_router([_spec(pre), _spec(dec)])
            prompt = [6, 5, 4, 3, 2, 1, 7, 8]
            with faults.inject(spec_str):
                resp = router.generate(prompt, max_new_tokens=6)
                fired = [h for h in faults.history() if h[0] == "serve"]
            assert resp.error is None
            # THE oracle: whatever the wire did, the tokens are exactly
            # the single-replica greedy stream.
            assert resp.tokens == _greedy_reference(model, params,
                                                    prompt, 6)
            return resp, fired, router
        finally:
            pre.shutdown()
            dec.shutdown()

    def test_corrupt_block_fails_digest_and_recomputes(self,
                                                       model_and_params):
        """A corrupted block must fail the receiver's digest check; the
        request finishes on the sender's pristine KV (the recompute
        path) — never with wrong tokens, never bound into the receiving
        pool."""
        resp, fired, _ = self._run_faulted(model_and_params,
                                           "serve:step=0,mode=migrate")
        assert fired == [("serve", 0, "migrate")]
        assert resp.migrated_to is None           # fell back locally

    def test_migrate_drop_falls_back_locally(self, model_and_params):
        resp, fired, _ = self._run_faulted(
            model_and_params, "serve:step=0,mode=migrate-drop")
        assert fired == [("serve", 0, "migrate-drop")]
        assert resp.migrated_to is None

    def test_migrate_delay_slows_but_migrates(self, model_and_params):
        t0 = time.monotonic()
        resp, fired, _ = self._run_faulted(
            model_and_params,
            "serve:step=0,mode=migrate-delay,delay_ms=150")
        assert time.monotonic() - t0 >= 0.15
        assert fired == [("serve", 0, "migrate-delay")]
        assert resp.migrated_to == "dec-f"        # delayed, not failed


class TestReplicaStatsConcurrent:
    """ISSUE 11 satellite: the stats snapshot polls replicas
    concurrently under ONE deadline — N unreachable replicas must not
    stall it N×timeout."""

    def test_dead_replicas_cost_one_timeout_not_each(self,
                                                     model_and_params):
        live = _replica(model_and_params, "live-0")
        dead_socks = []
        dead_specs = []
        for i in range(3):
            # Listening-but-never-answering sockets: a connect succeeds
            # (backlog) and the probe read burns its full timeout — the
            # shape of a wedged, not crashed, replica.
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            s.listen(1)
            dead_socks.append(s)
            dead_specs.append(ReplicaSpec(
                f"wedged-{i}", [("127.0.0.1", s.getsockname()[1])]))
        try:
            router = _fast_router([_spec(live)] + dead_specs,
                                  probe_timeout=1.0)
            t0 = time.monotonic()
            stats = router.replica_stats(timeout=1.0)
            elapsed = time.monotonic() - t0
            # Serial polling would cost >= 3s here (one full timeout
            # per wedged replica); concurrent costs ~one.
            assert elapsed < 2.5, elapsed
            assert "stats" in stats["live-0"]
            for i in range(3):
                assert "stats_error" in stats[f"wedged-{i}"]
                assert stats[f"wedged-{i}"]["role"] == "unified"
        finally:
            live.shutdown()
            for s in dead_socks:
                s.close()


class TestPrefixDirectory:
    def test_record_lookup_lru_and_bounds(self):
        d = PrefixDirectory(4, max_entries=2)
        key = (1, 2, 3, 4)
        assert d.key_for([1, 2, 3]) is None
        assert d.key_for([1, 2, 3, 4, 5]) == key
        d.record(key, "a")
        d.record(key, "b")
        assert d.lookup(key) == ["b", "a"]       # most recent first
        d.record(key, "a")
        assert d.lookup(key) == ["a", "b"]
        d.record((5, 5, 5, 5), "a")
        d.record((6, 6, 6, 6), "a")              # bound 2: evicts LRU key
        assert len(d) == 2
        assert d.lookup((1, 2, 3, 4)) == []

    def test_discard_and_invalidate_replica(self):
        d = PrefixDirectory(4)
        k1, k2 = (1, 1, 1, 1), (2, 2, 2, 2)
        d.record(k1, "a")
        d.record(k1, "b")
        d.record(k2, "a")
        d.discard(k1, "a")
        assert d.lookup(k1) == ["b"]
        assert d.invalidate_replica("a") == 1    # only k2 still named it
        assert d.lookup(k2) == []
        assert d.lookup(k1) == ["b"]

    def test_pool_reports_evicted_leading_keys(self):
        """The piggyback source: a depth-0 block eviction surfaces its
        leading-block key via drain_evicted_keys."""
        table = np.zeros((2, 4), np.int32)
        pool = BlockPool(5, 4, table, lambda s, d: None)   # 4 usable
        pool.begin_request(0, [1, 2, 3, 4, 5])
        pool.ensure_writable(0, 0, 5)
        pool.index_prompt(0, [1, 2, 3, 4, 5])
        pool.release(0)
        assert pool.drain_evicted_keys() == []   # resident: nothing yet
        pool.begin_request(0, list(range(10, 19)))
        pool.ensure_writable(0, 0, 9)            # pressure: evicts chain
        assert pool.drain_evicted_keys() == [(1, 2, 3, 4)]
        assert pool.drain_evicted_keys() == []   # drained = consumed

    def test_router_ingests_piggybacked_evictions(self, model_and_params):
        """An eviction on a replica, piggybacked on its next response,
        drops the directory entry — the router stops routing that
        prefix there."""
        # kv_block matches the router's directory key width (16) so
        # the piggybacked eviction key aligns with the directory key;
        # budget 5 = floor (1 trash + 2 slots x 2 blocks): NO cache
        # headroom, so released chains are reclaimed under the first
        # allocation pressure.
        srv = _replica(model_and_params, "evict-0",
                       engine_kw={"kv_block": 16, "kv_blocks": 5,
                                  "prefill_buckets": (8, 24)})
        try:
            router = _fast_router([_spec(srv)])
            base = list(range(30, 46))            # one 16-token key
            r1 = router.generate(base + [1], max_new_tokens=2,
                                 request_id="ev-0")
            assert r1.error is None
            key = router._prefix_key(base + [1])
            assert router._directory.lookup(key), "entry recorded"
            # A fat unrelated request forces eviction of the cached
            # prefix; its response piggybacks the invalidation.
            r2 = router.generate(list(range(50, 70)), max_new_tokens=2,
                                 request_id="ev-1")
            assert r2.error is None
            deadline = time.monotonic() + 5.0
            while router._directory.lookup(key) and \
                    time.monotonic() < deadline:
                resp = router.generate([1, 2, 3], max_new_tokens=2)
                assert resp.error is None
            assert router._directory.lookup(key) == []
        finally:
            srv.shutdown()

    def test_bench_invalidates_directory(self, model_and_params):
        router = _fast_router([ReplicaSpec("x", [("127.0.0.1", 1)]),
                               ReplicaSpec("y", [("127.0.0.1", 2)])])
        key = tuple(range(16))
        rep = router._replicas[0]
        router._note_affinity(key, rep)
        assert router._directory.lookup(key) == [rep]
        router._strike(rep, fatal=True)          # benched: death signal
        assert router._directory.lookup(key) == []


class TestDrainLifecycle:
    def test_batcher_drain_rejects_new_finishes_inflight(
            self, model_and_params):
        engine = _engine(model_and_params)
        b = ContinuousBatcher(engine, max_queue=8, default_deadline_s=30)
        req = b.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        b.drain()
        with pytest.raises(ReplicaDrainingError):
            b.submit([4, 5], SamplingParams(max_new_tokens=2))
        for _ in range(50):
            if req.done.is_set():
                break
            b.step()
        assert req.error is None and len(req.tokens) == 4
        snap = b.snapshot()
        assert snap["draining"] is True and snap["queue_depth"] == 0

    def test_undrain_reverses_a_drain_end_to_end(self, model_and_params):
        """The abandon path: an undrained replica admits again and the
        router picks it again."""
        srv = _replica(model_and_params, "ud-a")
        try:
            router = _fast_router([_spec(srv)])
            router.drain_replica("ud-a")
            with pytest.raises(Exception):
                # The only replica is draining: nothing can serve.
                router.generate([1, 2], max_new_tokens=2,
                                request_id="ud-0")
            router.undrain_replica("ud-a")
            resp = router.generate([1, 2], max_new_tokens=2,
                                   request_id="ud-1")
            assert resp.error is None and len(resp.tokens) == 2
            assert srv._batcher.draining is False
        finally:
            srv.shutdown()

    def test_router_shifts_load_off_draining_replica(self,
                                                     model_and_params):
        a = _replica(model_and_params, "dr-a")
        b = _replica(model_and_params, "dr-b")
        try:
            router = _fast_router([_spec(a), _spec(b)])
            router.drain_replica("dr-a")
            for i in range(3):
                resp = router.generate([i + 1, 2], max_new_tokens=2)
                assert resp.error is None
            stats = router.replica_stats(timeout=3.0)
            assert stats["dr-a"]["draining"] is True
            assert stats["dr-a"]["stats"]["requests_completed"] == 0
            assert stats["dr-b"]["stats"]["requests_completed"] == 3
            # Voluntary refusal never strikes: the replica stays
            # healthy through its whole drain.
            assert stats["dr-a"]["strikes"] == 0
        finally:
            a.shutdown()
            b.shutdown()


class _FakeRouter:
    """Deterministic stats source for controller policy tests."""

    def __init__(self, entries):
        self.entries = entries               # name -> entry dict
        self.added = []
        self.removed = []
        self.drained = []

    def replica_stats(self, timeout=5.0):
        return {name: dict(e) for name, e in self.entries.items()}

    def add_replica(self, spec):
        self.added.append(spec.name)
        self.entries[spec.name] = _stats_entry(spec.name, spec.role)

    def remove_replica(self, name):
        self.removed.append(name)
        self.entries.pop(name, None)

    def drain_replica(self, name, timeout=5.0):
        self.drained.append(name)
        if name in self.entries:
            self.entries[name]["draining"] = True

    def undrain_replica(self, name, timeout=5.0):
        self.undrained = getattr(self, "undrained", [])
        self.undrained.append(name)
        if name in self.entries:
            self.entries[name]["draining"] = False


def _stats_entry(name, role, queue=0, active=0, ttft_p99=None):
    return {"name": name, "role": role, "healthy": True,
            "draining": False, "strikes": 0, "inflight": 0,
            "completed": 0, "failed": 0,
            "stats": {"queue_depth": queue, "active_slots": active,
                      "max_slots": 2, "ttft_ms_p99": ttft_p99}}


class _FakeLauncher(ReplicaLauncher):
    def __init__(self):
        self.launched = []
        self.retired = []

    def launch(self, role, host=None):
        name = f"{role}-new-{len(self.launched)}"
        self.launched.append((role, host))
        return ReplicaSpec(name, [("127.0.0.1", 1)], role=role)

    def retire(self, name):
        self.retired.append(name)


class TestFleetController:
    def test_scale_out_on_queue_saturation(self):
        router = _FakeRouter({
            "decode-0": _stats_entry("decode-0", "decode", queue=9),
            "prefill-0": _stats_entry("prefill-0", "prefill", queue=0),
        })
        launcher = _FakeLauncher()
        c = FleetController(router, launcher, scale_out_queue=4.0,
                            scale_in_idle_s=3600.0)
        actions = c.poll_once()
        assert [(a["action"], a["role"]) for a in actions] == \
            [("scale_out", "decode")]
        assert launcher.launched == [("decode", None)]
        assert router.added == ["decode-new-0"]

    def test_scale_out_on_ttft(self):
        router = _FakeRouter({
            "prefill-0": _stats_entry("prefill-0", "prefill",
                                      ttft_p99=900.0),
        })
        launcher = _FakeLauncher()
        c = FleetController(router, launcher, scale_out_queue=1e9,
                            scale_out_ttft_ms=500.0,
                            scale_in_idle_s=3600.0)
        c.poll_once()
        assert launcher.launched == [("prefill", None)]

    def test_idle_role_drains_then_retires(self):
        router = _FakeRouter({
            "decode-0": _stats_entry("decode-0", "decode"),
            "decode-1": _stats_entry("decode-1", "decode"),
        })
        launcher = _FakeLauncher()
        c = FleetController(router, launcher, scale_out_queue=100.0,
                            scale_in_idle_s=0.0, min_per_role=1)
        a1 = c.poll_once()
        assert [a["action"] for a in a1] == ["drain"]
        assert router.drained == ["decode-1"]
        assert c.draining() == ["decode-1"]
        a2 = c.poll_once()                       # drained dry: retire
        assert [a["action"] for a in a2] == ["retire"]
        assert router.removed == ["decode-1"]
        assert launcher.retired == ["decode-1"]
        a3 = c.poll_once()                       # min_per_role floor
        assert a3 == []

    def test_drain_deadline_forces_retire(self):
        entries = {
            "unified-0": _stats_entry("unified-0", "unified"),
            "unified-1": _stats_entry("unified-1", "unified", queue=3,
                                      active=2),
        }
        router = _FakeRouter(entries)
        launcher = _FakeLauncher()
        c = FleetController(router, launcher, scale_out_queue=100.0,
                            scale_in_idle_s=3600.0,
                            drain_deadline_s=100.0)
        c.drain_and_retire("unified-1")
        assert c.poll_once() == []               # work in flight: wait
        actions = c.poll_once(now=time.monotonic() + 200.0)
        assert [a["action"] for a in actions] == ["retire"]
        assert actions[0]["forced"] is True

    def test_unreachable_drain_waits_for_deadline(self):
        """A draining replica that misses one stats poll (stats_error)
        is NOT evidence the drain ran dry — only the drain deadline may
        force a retire with work possibly in flight."""
        entries = {
            "unified-0": _stats_entry("unified-0", "unified"),
            "unified-1": _stats_entry("unified-1", "unified"),
        }
        router = _FakeRouter(entries)
        launcher = _FakeLauncher()
        c = FleetController(router, launcher, scale_out_queue=100.0,
                            scale_in_idle_s=3600.0,
                            drain_deadline_s=100.0)
        c.drain_and_retire("unified-1")
        entry = entries["unified-1"]
        del entry["stats"]
        entry["stats_error"] = "timeout after 2.0s"
        assert c.poll_once() == []               # blip: keep waiting
        assert launcher.retired == []
        actions = c.poll_once(now=time.monotonic() + 200.0)
        assert [a["action"] for a in actions] == ["retire"]

    def test_last_replica_retire_refusal_does_not_wedge(self):
        """The router refuses to drop its last replica; the controller
        must abandon that drain (UN-draining the replica — left
        draining with no peers it would starve the fleet) instead of
        raising on every later control round."""
        class _OneReplicaRouter(_FakeRouter):
            def remove_replica(self, name):
                raise ValueError("cannot remove the last replica")

        router = _OneReplicaRouter({
            "unified-0": _stats_entry("unified-0", "unified"),
        })
        launcher = _FakeLauncher()
        c = FleetController(router, launcher, scale_out_queue=100.0,
                            scale_in_idle_s=3600.0)
        c.drain_and_retire("unified-0")
        assert c.poll_once() == []               # abandoned, not raised
        assert c.draining() == []                # entry cleared
        assert launcher.retired == []
        assert getattr(router, "undrained", []) == ["unified-0"]
        c.poll_once()                            # later rounds keep working

    def test_reservation_released_when_host_leaves(self):
        """A departed host took its placed replicas with it; its stale
        reservation must not read the host as full when it rejoins."""
        from horovod_tpu.elastic.driver import ElasticDriver, \
            FixedDiscovery

        disc = FixedDiscovery({"h1": 1})
        driver = ElasticDriver(disc, poll_interval_s=3600.0)
        driver.poll_once()
        assert driver.reserve_slot() == "h1"
        assert driver.reserve_slot() is None
        disc.hosts = {}                   # host crashed out of discovery
        driver.poll_once()
        disc.hosts = {"h1": 1}            # rejoined fresh
        driver.poll_once()
        assert driver.reserved_slots() == 0
        assert driver.reserve_slot() == "h1"   # capacity usable again

    def test_placement_rides_elastic_discovery(self):
        from horovod_tpu.elastic.driver import ElasticDriver, \
            FixedDiscovery

        driver = ElasticDriver(FixedDiscovery({"h1": 1}),
                               poll_interval_s=3600.0)
        driver.poll_once()
        router = _FakeRouter({
            "decode-0": _stats_entry("decode-0", "decode", queue=9),
        })
        launcher = _FakeLauncher()
        c = FleetController(router, launcher, driver=driver,
                            scale_out_queue=4.0, scale_in_idle_s=3600.0)
        spec = c.scale_out("decode")
        assert spec is not None
        assert launcher.launched == [("decode", "h1")]
        assert driver.reserved_slots() == 1
        assert c.scale_out("decode") is None     # capacity exhausted
        assert launcher.launched == [("decode", "h1")]
        # Retiring the placed replica releases its slot (the original
        # replica is no longer saturated, so nothing re-reserves it).
        router.entries["decode-0"]["stats"]["queue_depth"] = 0
        c.drain_and_retire(spec.name)
        router.entries.pop(spec.name, None)
        c.poll_once()
        assert driver.reserved_slots() == 0


class _LocalLauncher(ReplicaLauncher):
    """Real in-process replicas for the e2e scale cycle."""

    def __init__(self, model_and_params):
        self.mp = model_and_params
        self.servers = {}
        self.n = 0

    def launch(self, role, host=None):
        name = f"{role}-x{self.n}"
        self.n += 1
        srv = _replica(self.mp, name, role=role)
        self.servers[name] = srv
        return _spec(srv)

    def retire(self, name):
        srv = self.servers.pop(name, None)
        if srv is not None:
            srv.shutdown()

    def shutdown_all(self):
        for srv in self.servers.values():
            srv.shutdown()
        self.servers.clear()


@pytest.mark.chaos
class TestChaosFleet:
    """ISSUE 11 acceptance drill: bursty load with a replica killed
    mid-migration plus a forced scale-out + drain-and-retire cycle —
    no request lost or duplicated, every token exactly the
    single-replica greedy stream."""

    def test_kill_mid_migration_and_scale_cycle(self, model_and_params):
        import os

        fault_step = int(os.environ.get("HVD_TPU_CHAOS_STEP", "0")) % 12
        seed = int(os.environ.get("HVD_TPU_CHAOS_SEED", "0"))
        model, params = model_and_params
        pre = _replica(model_and_params, "chaos-pre", role="prefill")
        d0 = _replica(model_and_params, "chaos-d0", role="decode")
        d1 = _replica(model_and_params, "chaos-d1", role="decode")
        fleet = [pre, d0, d1]
        launcher = _LocalLauncher(model_and_params)
        try:
            router = _fast_router(
                [_spec(s) for s in fleet],
                retry_policy=RetryPolicy(attempts=10, base_delay_s=0.02,
                                         max_delay_s=0.2))
            responses = {}
            n_requests, n_tokens = 8, 6
            with faults.inject(f"serve:step={fault_step},seed={seed},"
                               f"mode=kill"):
                for i in range(n_requests):
                    rid = f"fleet-{i}"
                    resp = router.generate([i + 1, i + 2, i + 3, i + 4],
                                           max_new_tokens=n_tokens,
                                           request_id=rid)
                    assert resp.error is None, (i, resp.error)
                    assert len(resp.tokens) == n_tokens
                    assert rid not in responses    # no duplicates
                    responses[rid] = resp
                kills = [h for h in faults.history() if h[0] == "serve"]
            # Exactly one replica died (prefill at a handoff dispatch,
            # or a decode mid-decode — the soak randomizes which).
            assert len(kills) == 1, kills
            assert sum(s.dead for s in fleet) == 1
            for i in range(n_requests):
                want = _greedy_reference(model, params,
                                         [i + 1, i + 2, i + 3, i + 4],
                                         n_tokens)
                assert responses[f"fleet-{i}"].tokens == want, i
            # At-most-once: a replayed id returns the cached response.
            again = router.generate([99], max_new_tokens=2,
                                    request_id="fleet-0")
            assert again is responses["fleet-0"]
            # Forced scale-out + drain-and-retire cycle through the
            # controller: the new replica serves, then drains dry and
            # retires with nothing lost.
            controller = FleetController(
                router, launcher, scale_in_idle_s=3600.0,
                drain_deadline_s=30.0, stats_timeout_s=2.0)
            spec = controller.scale_out("decode")
            assert spec is not None
            r = router.generate([41, 42, 43, 44], max_new_tokens=3,
                                request_id="fleet-post")
            assert r.error is None
            assert r.tokens == _greedy_reference(model, params,
                                                 [41, 42, 43, 44], 3)
            controller.drain_and_retire(spec.name)
            deadline = time.monotonic() + 20.0
            while controller.draining() and time.monotonic() < deadline:
                controller.poll_once()
                time.sleep(0.05)
            assert controller.draining() == []
            assert spec.name not in launcher.servers   # really retired
            after = router.generate([7, 7, 7, 7], max_new_tokens=2,
                                    request_id="fleet-after")
            assert after.error is None
        finally:
            launcher.shutdown_all()
            for s in fleet:
                s.shutdown()
