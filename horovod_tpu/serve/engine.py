"""Continuous-batching inference engine over ``models.transformer.GPT``.

The serving hot path is two compiled programs:

* **prefill** — one program per *length bucket* ``L``: run the prompt
  (padded to ``L``) through the model, sample the first token, and
  write its K/V into this request's cache.  Padding prompts to a small
  set of bucket shapes bounds recompiles.  With prefix sharing the
  bucket is chosen for the *suffix*: a prompt whose leading tokens are
  resident in the KV pool recomputes only what is not cached — the
  cache-hit TTFT win.
* **decode** — ONE program for the whole slot batch: every active
  request advances per call, each slot at its own depth.  This is the
  continuous-batching property: admission never waits for the batch to
  drain.  What it reads of the slots (last tokens, positions, active
  flags, temperatures, top-k, the PRNG key) lives on the device and
  the program advances it itself: the host uploads only what it
  changed — a bind, a clear, a block table that moved — and a steady
  step uploads nothing (docs/serving.md "The step protocol").

Three caches live under this one API.  The model declares what each
of its layers keeps (``models.transformer.cache_kinds``): keys and
values, which ``HVD_TPU_SERVE_KV`` lays out as **paged** or **dense**,
or a fixed-size retention **state**:

* **paged** (default) — a key pool and a value pool per layer,
  ``[num_blocks, block, row]``, as wide as the layer's KV heads times
  its key or value width, plus a host-side block table for each kind of
  layer the model declares (``serve/kv/``): *full* layers keep every
  position through the table as wide as the longest request; *window*
  layers keep the last ``window`` positions of a slot in a ring of
  ``window / block + 1`` blocks of pools of their own, so that their
  bytes do not grow with the context (a model with window layers
  shares no prefix, keeps no migration frame and is served without
  speculation or tensor parallelism; a preempted request of it resumes
  if it fits the largest bucket).  Requests map
  onto refcounted fixed-size token blocks, identical prompt prefixes
  share physical blocks (copy-on-write on first divergent write), and
  unreferenced prefix blocks are LRU-evicted under pressure.  The
  jitted programs index the pool *through* a per-slot block-table
  array, so there is still ONE compiled decode program — the table is
  data, not shape.  The model writes the step's K/V through the table,
  then attends over the updated pool, and returns the pools; the
  programs here hand back what it returns, as on the dense tier, and
  a donated pool is updated in place (``docs/serving.md`` has what a
  read before the write, or heads kept apart in the pool, cost).
  Block 0 is a reserved *trash block*: unmapped table entries point at
  it and invalid positions (padding, past-the-cache, idle rows) clamp
  into it, which replaces every masking lattice around
  scatter/gather.
* **dense** — the original per-slot ``[slots, S, H, D]`` rows; kept as
  the token-identity oracle the paged path is tested against.
* **state** — what a model of retention layers gets when nothing is
  set: per slot and layer one float32 ``(S, z)`` pair of a size that
  does not depend on the context (``models.transformer.
  init_state_cache``).  A prefill takes the slot's state in and gives
  it back — zeros when it starts at position 0, the carried state when
  it continues (a resume's later chunks) — and a bucket's padding never
  enters it; decode updates the state in place, in one program
  whatever the occupancy, and reads and writes only the rows that
  hold a request.  There are no
  blocks, no table and no prefix to share; preemption keeps nothing and
  a resume recomputes.  ``max_seq_len`` is then the most positions a
  request may reach, and costs no memory.

**A model that generates by blocks** (``GPTConfig.block_length``:
diffusion over blocks of ``B`` positions) rides the paged tier with a
step state of its own: a block a row — its tokens, which of them are
still masks, the step each was chosen at — in place of a token a row.
Prefill writes the prompt's whole blocks and yields no token; ONE
compiled *block step* forwards every active row's block (written into
the pool, then read by the decode kernel as ``B x H`` query heads with
the row's length at the block's end) and ``_transfer`` moves each row
on: a denoising step that unmasks its most confident positions, or,
for a block that came in all clean, the commit of its K/V and the
opening of the next.  ``step()`` hands back none to ``B`` tokens a row
(docs/serving.md "A model that generates by blocks").

**Speculative decoding** (per-request opt-in via
``SamplingParams(spec=True)``; greedy requests only): a small drafter
model proposes ``HVD_TPU_SERVE_SPEC_K`` tokens per step, the target
model verifies the whole draft in ONE batched forward inside the same
compiled-program regime, and accepted-prefix semantics guarantee the
emitted tokens are identical to plain greedy decode — a wrong draft
costs speed, never correctness (docs/serving.md has the proof sketch).

Neither program contains a cross-replica collective — the per-token hot
path is replica-local by construction; replication happens one level
up, in ``serve/router.py`` over process sets.

Sampling is greedy / temperature / top-k, resolved **per slot** inside
the one decode program (a ``where`` lattice, not a recompile), so mixed
sampling configs batch together.  What only sampled rows need — the
ranking of the vocabulary, the draw — sits behind a ``lax.cond`` on
whether a row asks for it: a step of greedy rows is an argmax.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..models.transformer import (GPT, KVKind, cache_kinds, init_kv_cache,
                                  init_state_cache)
from ..obs import flight as flight_mod
from ..obs import trace as trace_mod
from ..obs.metrics import percentile
from ..ops.pallas_common import _LANES, round_up
from ..utils.logging import get_logger
from .kv import BlockPool, RingPool, TRASH_BLOCK

logger = get_logger(__name__)


def resolved_config():
    """The serving layer's config source: the live Config when this
    process ran ``hvd.init``, else a fresh env parse (same parser, same
    defaults — the network.py convention, so a bare engine in a script
    and a served engine under the launcher read identical knobs)."""
    from .. import basics
    from ..config import Config

    return basics.config() if basics.is_initialized() else Config.from_env()


class PromptTooLongError(ValueError):
    """Prompt exceeds the largest prefill bucket, or the positions a
    request may reach."""


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (greedy when ``temperature == 0``).
    ``spec=True`` opts the request into speculative decoding (engines
    built with a drafter; greedy requests only — temperature rows in
    the same batch keep plain single-token semantics).

    A model that generates by blocks (``GPTConfig.block_length``) reads
    three more, a request's own because they trade its quality against
    its speed and rows of different schedules share a step: a block is
    denoised in ``denoising_steps`` steps (0 = the block's length, a
    position a step), and a step unmasks by ``transfer`` —
    ``'static'``: the ``k_s = B // T`` (+1 in the first ``B mod T``
    steps) masked positions of highest confidence; ``'dynamic'``: every
    masked position whose confidence passes ``threshold`` when those
    are at least ``k_s``, else the ``k_s`` highest."""

    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0                 # 0 = full vocab
    stop_token: Optional[int] = None
    spec: bool = False
    denoising_steps: int = 0
    transfer: str = "dynamic"
    threshold: float = 0.9


def _sample(logits, rng, temps, topks, rows=None):
    """Per-row sampling over ``[B, V]`` float32 logits: greedy rows
    (``temp <= 0``) take argmax; the rest draw from temperature-scaled
    logits restricted to each row's top-k (k per row — ranks against a
    per-row threshold instead of a static ``lax.top_k`` width).

    The program pays for what the rows in front of it ask for, and
    decides on the device (``lax.cond``, one program): with no sampled
    row among ``rows`` (all of them when None) it is the argmax alone;
    the two sorts of the vocabulary that rank it run only where a
    sampled row restricts its draw (``k = V`` masks nothing).  A row
    outside ``rows`` may come back with either token: its caller drops
    it.  ``rng`` is the caller's, split whatever the rows ask."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    asks = temps > 0.0 if rows is None else rows & (temps > 0.0)

    def draw():
        def ranked():
            ranks = jnp.argsort(jnp.argsort(-logits, axis=-1), axis=-1)
            k = jnp.where(topks > 0, topks, logits.shape[-1])[:, None]
            return jnp.where(ranks < k, logits, -jnp.inf)

        masked = jax.lax.cond(jnp.any(asks & (topks > 0)), ranked,
                              lambda: logits)
        scaled = masked / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.random.categorical(rng, scaled, axis=-1)
        return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)

    return jax.lax.cond(jnp.any(asks), draw, lambda: greedy)


def _advance(step, logits):
    """What one decode step changes of the step state (traced; the tail
    of all three decode programs): draw the chain's next subkey, sample
    every row's token from ``logits [B, 1, V]``, and make it the row's
    next input at the next position.  Rows without a request keep
    theirs, and cannot make the step sample with a temperature left
    behind; ``active``, ``temps`` and ``topks`` are not returned, so
    the program holds no copy of them."""
    key, sub = jax.random.split(step["key"])
    active = step["active"]
    nxt = _sample(logits[:, -1].astype(jnp.float32), sub, step["temps"],
                  step["topks"], rows=active)
    return {"key": key,
            "tokens": jnp.where(active, nxt, step["tokens"]),
            "positions": step["positions"] + active.astype(jnp.int32)}


def _transfer(step, logits, mask_token: int):
    """``_advance`` for a model that generates by blocks (traced; the
    tail of the block step): what one forward of every row's block
    changes of the step state.  The state is a block a row: ``tokens
    [S, B]``, ``masked [S, B]`` (a flag, never a comparison with the
    mask's id), ``chosen [S, B]`` (the denoising step at which a
    position was unmasked; -1: it came with the prompt), ``positions``
    (the block's first), ``steps`` (denoising steps the block has had)
    and the row's schedule (``denoise``, ``dynamic``, ``threshold``).

    A row with masks left *transfers*: at every masked position the
    greedy or sampled ``x0`` (the row's ``temps`` / ``topks``) and its
    confidence ``p(x0)``, a float32 softmax over the vocabulary; at
    step ``t`` of ``T`` the ``k = B // T + (t < B mod T)`` masked
    positions of highest confidence take their ``x0`` (equal
    confidences: the earlier position first) — or, under the dynamic
    rule, every masked position over the threshold when those are at
    least ``k``.  A row whose block came in all clean has just written
    the K/V that the cache keeps: it advances ``B`` positions and opens
    a block of masks.  Rows without a request keep theirs.

    Returns the state's next arrays and ``report [S, 3 B + 3]`` int32
    for the host — tokens, chosen, masked, steps, positions as they now
    are, and whether the block's last mask fell in this step."""
    logits = logits.astype(jnp.float32)
    S, B, V = logits.shape
    key, sub = jax.random.split(step["key"])
    masked, active = step["masked"], step["active"]
    denoising = active & masked.any(axis=-1)
    commit = active & ~masked.any(axis=-1)
    flat = logits.reshape(S * B, V)
    x0 = _sample(flat, sub, jnp.repeat(step["temps"], B),
                 jnp.repeat(step["topks"], B),
                 rows=(denoising[:, None] & masked).reshape(-1))
    conf = jnp.exp(jnp.take_along_axis(flat, x0[:, None], axis=-1)[:, 0]
                   - jax.nn.logsumexp(flat, axis=-1))
    x0 = x0.reshape(S, B)
    conf = jnp.where(masked, conf.reshape(S, B), -1.0)
    t, T = step["steps"], jnp.maximum(step["denoise"], 1)
    k = B // T + (t < B % T)
    over = conf > step["threshold"][:, None]
    rank = jnp.argsort(jnp.argsort(-conf, axis=-1, stable=True), axis=-1)
    dynamic = step["dynamic"] & (over.sum(axis=-1) >= k)
    take = (jnp.where(dynamic[:, None], over, rank < k[:, None])
            & masked & denoising[:, None])
    tokens = jnp.where(take, x0, step["tokens"])
    masked = masked & ~take
    chosen = jnp.where(take, t[:, None], step["chosen"])
    final = denoising & ~masked.any(axis=-1)
    opened = commit[:, None]
    out = {"key": key,
           "tokens": jnp.where(opened, mask_token, tokens),
           "masked": masked | opened,
           "chosen": jnp.where(opened, -1, chosen),
           "steps": jnp.where(commit, 0, t + denoising),
           "positions": step["positions"] + B * commit}
    report = jnp.concatenate(
        [out["tokens"], out["chosen"], out["masked"].astype(jnp.int32),
         out["steps"][:, None], out["positions"][:, None],
         final.astype(jnp.int32)[:, None]], axis=1)
    return dict(out, report=report)


def _read_report(report, B: int) -> dict:
    """A block step's report (``_transfer``; numpy) by its fields."""
    return {"tokens": report[:, :B], "chosen": report[:, B:2 * B],
            "masked": report[:, 2 * B:3 * B] != 0,
            "steps": report[:, 3 * B], "positions": report[:, 3 * B + 1],
            "final": report[:, 3 * B + 2] != 0}


class FinalTokens(list):
    """What a block step hands back for a slot: the tokens that became
    final in it, in position order, and in ``steps`` the denoising step
    (0 ... T - 1) at which each was chosen."""

    def __init__(self, tokens=(), steps=()):
        super().__init__(tokens)
        self.steps = list(steps)


# A phase of a decode step (``prepare``, ``dispatch``, ``fence``) or of
# a prefill (``dispatch``, ``fence``) that took more than
# ``_STALL_TIMES`` its usual length and ``_STALL_US`` microseconds over
# it makes the step a stalled one.  A fence holds the device's own
# work, 10-25 ms of a decode step: both together catch a fence of
# 120 ms and no step that is merely slow (PERF.md section 7).
_STALL_TIMES = 4
_STALL_US = 50_000
_DECODE_PHASES = ("prepare", "dispatch", "fence")
# ``kv_stats()`` reads the dispatch and the fence of this many steps.
_RECENT_STEPS = 1024


# What ``InferenceEngine._wake_runtime`` sends: never written to.
_POKE = np.zeros(1, np.int32)
_POKES = 8


def _home(params):
    """The sharding that commits fresh state to where ``params`` live,
    or None (the default device, uncommitted) where they are not
    replicated whole."""
    leaves = jax.tree.leaves(params)
    sharding = getattr(leaves[0], "sharding", None) if leaves else None
    if sharding is None or not sharding.is_fully_replicated:
        return None
    return sharding


def _beside(params, tree):
    """``tree`` (fresh KV state) committed to where ``params`` live.
    Left uncommitted, it comes back from the first compiled program
    committed to the weights' sharding, every later call then misses
    that program's cache entry, and the first prefill bucket compiles
    twice — the second time inside some request's TTFT."""
    return jax.device_put(tree, _home(params))


class InferenceEngine:
    """Slot-based prefill/decode engine; the batcher owns scheduling.

    ``start(slot, prompt, sampling)`` prefixes a request into ``slot``
    and returns its first token; ``step()`` decodes for every active
    slot and returns ``{slot: [tokens]}`` — one token per slot on the
    plain path, up to ``spec_k + 1`` under speculative decoding, and
    from a model that generates by blocks none to a block's worth
    (:class:`FinalTokens`; its ``start`` returns None).
    Each call is one span of ``obs/trace.py``
    (``hvd_tpu_engine_prefill`` / ``hvd_tpu_engine_decode``): in the
    span ring, on a live profiler's host plane, and mirrored onto the
    framework Timeline when one is active (docs/tracing.md).
    """

    def __init__(self, model: GPT, params, *,
                 max_slots: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 max_seq_len: Optional[int] = None,
                 kv_cache: Optional[str] = None,
                 kv_block: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 drafter: Optional[Tuple[GPT, dict]] = None,
                 spec_k: Optional[int] = None,
                 tp: Optional[int] = None,
                 weights_version: int = 0,
                 seed: int = 0):
        cfg = resolved_config()
        self._model = model
        self._params = params
        self.max_slots = int(max_slots or cfg.serve_max_batch)
        self.max_seq_len = int(max_seq_len or model.config.max_seq_len)
        if (model.config.positions == "learned"
                and self.max_seq_len > model.config.max_seq_len):
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"positional table ({model.config.max_seq_len})")
        buckets = tuple(prefill_buckets or cfg.serve_prefill_buckets)
        # Clamp buckets to the most positions a request may reach; keep
        # at least one.
        self.prefill_buckets = tuple(sorted(
            {min(int(b), self.max_seq_len) for b in buckets if b > 0}))
        if not self.prefill_buckets:
            raise ValueError(f"no usable prefill buckets in {buckets}")
        # The model declares what its layers keep; the engine picks the
        # cache from that when the caller sets nothing.
        self._declared = cache_kinds(model.config)
        kept = [k for k in self._declared if k is not None]
        if {isinstance(k, KVKind) for k in kept} == {True, False}:
            raise ValueError(
                "a model that keeps K/V in some layers and a retention "
                "state in others is not servable yet: the engine holds "
                "one kind of cache")
        stateful = "state" in kept
        windows = sorted({k.window for k in kept
                          if isinstance(k, KVKind) and k.window})
        if len(windows) > 1:
            raise ValueError(
                f"window layers of several widths ({windows}) are not "
                f"servable yet: the engine holds one ring a slot")
        # The window layers' window, 0 where the model has none.
        self._window = windows[0] if windows else 0
        # A model that generates by blocks: its block's length, else 0.
        self._block = int(model.config.block_length)
        self.kv_mode = (kv_cache or ("state" if stateful
                                     else cfg.serve_kv)).lower()
        if self.kv_mode not in ("paged", "dense", "state"):
            raise ValueError(f"unknown kv_cache mode {self.kv_mode!r}; "
                             f"expected 'paged', 'dense' or 'state'")
        if stateful and self.kv_mode != "state":
            raise ValueError(
                f"kv_cache={self.kv_mode!r} does not fit this model: its "
                f"layers keep a retention state, not keys and values, and "
                f"are served from kv_cache='state'")
        if self.kv_mode == "state" and not stateful:
            raise ValueError(
                "kv_cache='state' does not fit this model: its layers "
                "keep keys and values, and are served from "
                "kv_cache='paged' or 'dense'")
        # Tensor-parallel replica (docs/tp_serving.md): the forward
        # shards over a 1-D ``tensor`` mesh spanning the first ``tp``
        # local devices — column-parallel qkv/up placement plus the
        # model's gather-before-contract constraints keep the decode
        # bitwise identical to tp=1, so TP is a capacity/latency knob,
        # never a correctness one.  The paged KV pool shards on its
        # rows (heads lie side by side in a row, so each device holds
        # H/tp heads of every block) while
        # the block table and BlockPool bookkeeping stay rank-invariant
        # host state.
        self.tp = int(tp if tp is not None else cfg.serve_tp)
        self._tp_mesh = None
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self._block:
            if self.kv_mode != "paged":
                raise ValueError(
                    f"a model that generates by blocks is served from the "
                    f"paged cache, not kv_cache={self.kv_mode!r}: a block "
                    f"step reads each row's blocks through its table")
            if self.tp > 1:
                raise ValueError(
                    "tensor-parallel serving of a model that generates by "
                    "blocks is not built yet: the block step rides the "
                    "decode kernel, which a head-sharded pool does not take")
            if drafter is not None:
                raise ValueError(
                    "speculative decoding of a model that generates by "
                    "blocks is not built: a block step already yields "
                    "several tokens a row")
        if self.tp > 1:
            if self._window:
                raise ValueError(
                    "tensor-parallel serving of a model with window layers "
                    "is not built yet: full and window layers have "
                    "different KV heads to shard")
            if self.kv_mode == "state":
                raise ValueError(
                    "tensor-parallel serving of a retention state is not "
                    "built yet: the state is not sharded over its heads")
            if self.kv_mode != "paged":
                raise ValueError(
                    "tensor-parallel serving requires the paged KV "
                    "cache (HVD_TPU_SERVE_KV=paged) — the head-sharded "
                    "pool is the TP layout")
            if model.config.kv_heads % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide the model's KV head count "
                    f"({model.config.kv_heads}) for the head-sharded pool")
            from ..plan import tp_plan

            plan = tp_plan(self.tp)
            self._tp_mesh = plan.mesh
            self._model = model = GPT(
                config=dataclasses.replace(model.config,
                                           tp_mesh=plan.mesh,
                                           tp_axis="tensor"),
                mesh=model.mesh)
            self._params = params = self._tp_place_params(params)
        # Slot-state arrays: every mutation goes through the guarded
        # helpers below (_bind_slot / _advance_slot / _clear_slot) so
        # the hvdlint lock checker covers them — release() arrives from
        # RPC handler threads (router cancel) while the batcher thread
        # is mid-step.
        self._slot_lock = threading.Lock()
        self._positions = np.zeros(self.max_slots, np.int32)   # guarded-by: _slot_lock
        self._active = np.zeros(self.max_slots, bool)          # guarded-by: _slot_lock
        self._temps = np.zeros(self.max_slots, np.float32)     # guarded-by: _slot_lock
        self._topks = np.zeros(self.max_slots, np.int32)       # guarded-by: _slot_lock
        self._last_tokens = np.zeros(self.max_slots, np.int32)  # guarded-by: _slot_lock
        self._spec = np.zeros(self.max_slots, bool)            # guarded-by: _slot_lock
        self._prefix_hits = np.zeros(self.max_slots, np.int32)  # guarded-by: _slot_lock
        # A model that generates by blocks: the mirror of a block a row
        # (``_transfer`` has the fields), and the rows whose block's
        # last mask fell in the last step, which commit in the next.
        self._blocks = self._empty_blocks() if self._block else None  # guarded-by: _slot_lock
        self._block_final = np.zeros(self.max_slots, bool)     # guarded-by: _slot_lock
        # What a decode step reads of the slots lives on the device,
        # beside the weights, and the decode program advances it (the
        # token it sampled is the next input, the position the last
        # plus one, the key the chain's next); the arrays above stay
        # the host's mirror.  ``_slots_changed`` counts the mutations
        # the device cannot derive (a bind, a clear); a step whose
        # snapshot carries another count than the one last sent
        # compares the snapshot with ``_device_slots``, the host's copy
        # of what the device holds, and uploads the arrays that differ
        # (``_slots_sent``, ``_device_slots``: batcher thread only).
        # The key is committed from the start: an uncommitted first
        # key makes every program build twice (PERF.md, PR 26).
        self._slots_changed = 0                                # guarded-by: _slot_lock
        self._state_home = (
            NamedSharding(self._tp_mesh, PartitionSpec())
            if self._tp_mesh is not None else _home(params))
        self._step_state = {"key": self._to_device(
            jax.random.PRNGKey(seed))}
        # Expert layers (a sub-layer alone, or a block's feed-forward):
        # a paged decode step hands back what they were sent beside its
        # tokens, and ``_step`` adds it to two counters here (batcher
        # thread; ``kv_stats`` only reads them).
        mc = model.config
        self.expert_layers = sum(
            kind == "experts" or (kind == "block" and ffn == "experts")
            for kind, ffn in zip(mc.layer_kinds, mc.ffns))
        self.expert_pairs = self.experts_touched = 0
        self._device_slots = None
        self._upload_slots(self._slot_arrays(self._slot_snapshot()))
        self._slots_sent = 0
        self.decode_steps = 0
        self.sampling_steps = 0
        # Block steps, and what their rows did: forwards of a block with
        # masks left, forwards of one all clean (whose K/V the cache
        # keeps), blocks so committed, tokens whose block became final.
        self.block_steps = 0
        self.denoise_forwards = 0
        self.commit_forwards = 0
        self.blocks_committed = 0
        self.tokens_final = 0
        self.step_state_uploads = 0    # the constructor's is not counted
        self.staged_uploads = 0
        self.runtime_pokes = 0
        self._pokes = ()
        # Where a decode step's host time went (docs/serving.md "The
        # step protocol"): the dispatch call's two readings of the span
        # clock, each phase's usual length in microseconds (``_follow``;
        # a prefill's by its bucket; batcher thread only), and the
        # steps and prefills that a phase held up.  ``kv_stats()`` reads
        # the spans this engine has put into the ring since now.
        self._dispatched = (0, 0)
        self._usual: Dict[Any, float] = {}
        self.stalled_steps = collections.Counter()
        self.stalled_prefills = collections.Counter()
        self._born_us = trace_mod.now_us()
        # Weight hot-swap state (serve/swap.py; docs/hot_swap.md): the
        # running version (the checkpoint step the params came from —
        # 0 for boot weights that never touched the store) and the
        # staged next version awaiting the batcher's flip barrier.
        # Version is read from RPC/stats threads while the batcher
        # thread flips it, and staging happens on the subscriber thread
        # — both ride the slot lock.
        self._weights_version = int(weights_version)  # guarded-by: _slot_lock
        self._staged_params = None                    # guarded-by: _slot_lock
        self._staged_version = None                   # guarded-by: _slot_lock
        # Trace-time counters: the bounded-recompile contract is
        # testable (each jitted program bumps its key once per trace).
        self.trace_counts = collections.Counter()
        # Donate the engine-wide cache/pool so prefill/decode update it
        # in place — without donation XLA copies the full cache every
        # token, which dominates decode at real cache sizes.  CPU has
        # no donation support (it would only warn), so gate on backend.
        self._donate = (1,) if jax.default_backend() != "cpu" else ()
        kv_heads, head_dim = model.config.kv_heads, model.config.head_size
        self._states = None
        self._ring = None
        self.state_resets = 0
        # Rows the decode steps over the state visited, summed.
        self.state_rows_visited = 0
        if self.kv_mode == "state":
            self.kv_block = 0
            self.kv_blocks = 0
            self._kv = None
            self._caches = None
            self._states = _beside(params, init_state_cache(
                model.config, self.max_slots))
            self._decode_fn = jax.jit(self._decode_state_impl,
                                      donate_argnums=self._donate)
            self._prefill_fns = {L: self._make_state_prefill(L)
                                 for L in self.prefill_buckets}
        elif self.kv_mode == "paged":
            self.kv_block = int(kv_block or cfg.serve_kv_block)
            if self.kv_block < 1:
                raise ValueError(f"kv_block must be >= 1, got "
                                 f"{self.kv_block}")
            if self._block and self.kv_block % self._block:
                raise ValueError(
                    f"kv_block {self.kv_block} does not hold whole blocks "
                    f"of the model's {self._block} positions")
            self.blocks_per_slot = -(-self.max_seq_len // self.kv_block)
            floor = 1 + self.max_slots * self.blocks_per_slot
            budget = int(kv_blocks if kv_blocks is not None
                         else cfg.serve_kv_blocks)
            if budget == 0:
                # Auto: every slot fully servable plus an equal share
                # of prefix-cache headroom — none where window layers
                # rule prefix sharing out.
                budget = 1 + ((1 if self._window or self._block else 2)
                              * self.max_slots * self.blocks_per_slot)
            if budget < floor:
                raise ValueError(
                    f"KV pool budget {budget} below the floor {floor} "
                    f"(1 trash + slots x blocks_per_slot) — active "
                    f"requests could deadlock on allocation")
            self.kv_blocks = budget
            # A window layer's ring: the blocks a window can touch.
            self.ring_blocks = min(-(-self._window // self.kv_block) + 1,
                                   self.blocks_per_slot) if self._window \
                else 0
            ring_budget = 1 + self.max_slots * self.ring_blocks
            # A pool row holds a token's heads side by side, padded to
            # whole vectors of 128 lanes: the decode kernel copies
            # blocks out of the pool, and the chip copies only whole
            # vectors (ops/paged_attention.py).  The chip's tiled
            # layout pads a row so anyway, so the pad costs no memory
            # there.  A head-sharded pool keeps heads alone in its row:
            # a shard's part has to be whole heads, and the
            # tensor-parallel step reads through the view.  Each layer
            # has the rows it declared (``cache_kinds``): keys and
            # values may differ in width, and layers in both.
            self._kv_row = kv_heads * head_dim

            def _pool_zeros(blocks, row):
                z = jnp.zeros(
                    (blocks, self.kv_block,
                     row if self.tp > 1 else round_up(row, _LANES)),
                    model.config.dtype)
                if self._tp_mesh is not None:
                    # Head-sharded pool: each shard device holds only
                    # its H/tp heads' part of every row; the block
                    # table stays whole-pool host state.
                    return jax.device_put(z, NamedSharding(
                        self._tp_mesh,
                        PartitionSpec(None, None, "tensor")))
                return _beside(params, z)

            self._pools = [
                None if kind is None else {
                    "k": _pool_zeros(ring_budget if kind.window else budget,
                                     kind.k_row),
                    "v": _pool_zeros(ring_budget if kind.window else budget,
                                     kind.v_row)}
                for kind in self._declared]
            # Block tables, one for each kind of layer the model
            # declares, each with a trailing trash column the jitted
            # programs clamp invalid positions into (serve/kv/pool.py):
            # a full layer's is as wide as the longest request, a window
            # layer's a slot's ring.  A program is handed all of them
            # and each layer reads its kind's (``_paged_caches``).
            self._tables = {"full": np.full(
                (self.max_slots, self.blocks_per_slot + 1),
                TRASH_BLOCK, np.int32)}
            if self._window:
                self._tables["window"] = np.full(
                    (self.max_slots, self.ring_blocks + 1), TRASH_BLOCK,
                    np.int32)
            # The device's copies and the bytes they were made from: a
            # table goes up when it changed (a block boundary, an
            # admission, a release), not every step.
            self._table_sent = dict.fromkeys(self._tables)
            self._table_device = dict.fromkeys(self._tables)
            self.table_uploads = 0
            self.table_uploads_ahead = 0   # of them, behind a block step
            self.paged_decode_steps = 0
            self.paged_live_blocks = 0
            self.paged_view_blocks = 0
            # Positions a decode step's attention reads, by kind of
            # layer, and blocks in use, each summed over the steps.
            self.paged_live_rows = 0
            self.paged_live_positions = dict.fromkeys(self._tables, 0)
            self.kv_block_steps = dict.fromkeys(self._tables, 0)
            self._copy_fn = jax.jit(
                self._copy_impl,
                donate_argnums=(0,) if self._donate else ())
            self._import_fn = jax.jit(
                self._import_impl,
                donate_argnums=(0,) if self._donate else ())
            dt_size = np.dtype(model.config.dtype).itemsize

            def _block_bytes(windowed: bool) -> int:
                # Per-SHARD bytes of one block: K+V rows for the K/tp
                # heads this shard holds, across the kind's layers.
                return sum((k.k_row + k.v_row) // self.tp
                           for k in self._declared if k is not None
                           and bool(k.window) == windowed
                           ) * self.kv_block * dt_size

            # One allocator a kind: chains that grow and share
            # prefixes, and rings, which rule sharing out for the whole
            # request.
            self._kv = BlockPool(
                budget, self.kv_block, self._tables["full"],
                self._copy_block, heads=kv_heads // self.tp,
                tp_degree=self.tp,
                bytes_per_block=_block_bytes(False),
                index_prefixes=not (self._window or self._block))
            self._ring = RingPool(
                ring_budget, self._tables["window"],
                _block_bytes(True)) if self._window else None
            self._caches = None
            self._decode_fn = jax.jit(
                self._decode_block_impl if self._block
                else self._decode_paged_impl, donate_argnums=self._donate)
            self._prefill_fns = {
                L: (self._make_block_prefill(L) if self._block
                    else self._make_paged_prefill(L))
                for L in self.prefill_buckets}
        else:
            self.kv_block = 0
            self.kv_blocks = 0
            self._kv = None
            self._caches = _beside(params, init_kv_cache(
                model.config, self.max_slots, self.max_seq_len))
            self._decode_fn = jax.jit(self._decode_impl,
                                      donate_argnums=self._donate)
            self._prefill_fns = {L: self._make_prefill(L)
                                 for L in self.prefill_buckets}
        # Speculative decoding: drafter = (small GPT, its params).
        self._drafter = None
        self._drafter_params = None
        self._drafter_caches = None
        self.spec_k = int(spec_k or cfg.serve_spec_k)
        self.spec_verify_steps = 0
        self.spec_accepted_tokens = 0
        if drafter is not None:
            if self._window:
                raise ValueError(
                    "speculative decoding over window layers is not built "
                    "yet: a rejected draft's writes have already pushed "
                    "live positions out of the ring")
            if self.kv_mode == "state":
                raise ValueError(
                    "speculative decoding over a retention state is not "
                    "built yet: a rejected draft cannot be taken back out "
                    "of the state")
            if self.kv_mode != "paged":
                raise ValueError("speculative decoding requires the "
                                 "paged KV cache (HVD_TPU_SERVE_KV=paged)")
            dmodel, dparams = drafter
            if dmodel.config.max_seq_len < self.max_seq_len:
                raise ValueError(
                    f"drafter positional table "
                    f"({dmodel.config.max_seq_len}) shorter than the "
                    f"serving cache ({self.max_seq_len})")
            self._drafter = dmodel
            self._drafter_params = dparams
            self._drafter_caches = _beside(dparams, init_kv_cache(
                dmodel.config, self.max_slots, self.max_seq_len))
            self._draft_prefill_fns = {L: self._make_draft_prefill(L)
                                       for L in self.prefill_buckets}
            self._spec_draft_fn = jax.jit(
                self._spec_draft_impl, donate_argnums=self._donate)
            self._spec_verify_fn = jax.jit(
                self._spec_verify_impl, donate_argnums=self._donate)

    # --- tensor-parallel placement ------------------------------------------

    def _tp_place_params(self, tree):
        """Place a host/device param tree on the TP mesh per the
        planner's device rule (``plan.tp_param_spec``): qkv/up kernels
        column-sharded, everything else replicated.  Used at
        construction AND by :meth:`stage_params` so a hot-swapped tree
        lands with the layout the compiled programs were traced for —
        a swap never costs a recompile."""
        from ..ckpt.snapshot import path_string
        from ..plan import tp_param_spec

        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        placed = [
            jax.device_put(leaf, NamedSharding(
                self._tp_mesh,
                tp_param_spec(path_string(path), leaf, self.tp)))
            for path, leaf in flat
        ]
        return jax.tree_util.tree_unflatten(treedef, placed)

    def _to_device(self, tree):
        """Host arrays committed to where the weights live (replicated
        over the tensor mesh of a TP replica): plain transfers, no
        compiled program."""
        return jax.device_put(tree, self._state_home)

    @staticmethod
    def _slot_arrays(snap: tuple) -> dict:
        """A slot snapshot as the step state's five arrays: copies
        nothing else holds, so a caller may write a row before it
        sends them."""
        act, pos, temps, topks, last_tokens, _, _, blocks = snap
        slots = {"positions": np.where(act, pos, 0).astype(np.int32),
                 "active": act, "temps": temps, "topks": topks}
        if blocks is None:
            return dict(slots, tokens=last_tokens)
        # A block a row in place of a token a row; which rows commit
        # next the device knows from ``masked``.
        return dict(slots, **{k: v for k, v in blocks.items()
                              if k != "final"})

    def _upload_slots(self, slots: dict) -> int:
        """Into the device's step state those of ``slots`` that differ
        from what the device holds (``_device_slots``: all of them
        while that is unknown); the key stays: only a program advances
        it, only an import replaces it.  Returns how many transfers
        that was.  Nothing mutates an array once it is sent: a
        transfer may read its source later, or alias it."""
        held = self._device_slots
        send = {k: v for k, v in slots.items()
                if held is None or not np.array_equal(v, held[k])}
        if send:
            self._step_state = dict(self._step_state,
                                    **self._to_device(send))
            self._device_slots = dict(held or {}, **send)
        return len(send)

    def _stage_bind(self, slot: int, n_prompt: int,
                    sampling: SamplingParams,
                    row: Optional[dict] = None) -> None:
        """Between a prefill's dispatch and its token fence: send what
        ``_bind_slot`` is about to change, but for the token still
        being sampled, and the block table the prefill wrote, while
        the device computes.  The first decode step then has one array
        left to send, not five and a table, with the device waiting.
        The next step compares again whatever happens until then (a
        prefill that raises leaves the row to be sent back).  ``row``
        (``_block_row``): the first block of a model that generates by
        blocks, which awaits no token and is sent whole."""
        slots = self._slot_arrays(self._slot_snapshot())
        if row is None:
            del slots["tokens"]
            row = {"positions": n_prompt}
        for field, value in row.items():
            slots[field][slot] = value
        slots["active"][slot] = True
        slots["temps"][slot] = sampling.temperature
        slots["topks"][slot] = sampling.top_k
        self.staged_uploads += self._upload_slots(slots)
        self._slots_sent = None
        if self.kv_mode == "paged":
            self._device_table()

    @property
    def _rng(self):
        """The PRNG key, on the device: every program that samples
        takes it, splits it once and hands back the next (one chain, in
        call order: prefill, decode, prefill, ...)."""
        return self._step_state["key"]

    @_rng.setter
    def _rng(self, key):
        self._step_state = dict(self._step_state, key=key)

    # --- paged-view geometry ------------------------------------------------

    @property
    def _view_len(self) -> int:
        """Gathered per-slot view length: chain blocks + the trash
        column — always > max_seq_len, so clamped-invalid positions
        land in trash rows no valid query can see."""
        return (self.blocks_per_slot + 1) * self.kv_block

    # --- compiled programs: dense tier --------------------------------------

    def _make_prefill(self, L: int):
        model, n_layer = self._model, self._model.config.n_layer

        def prefill(params, caches, tokens, length, slot, key, temp, topk):
            self.trace_counts[f"prefill_{L}"] += 1  # trace-time only
            key, rng = jax.random.split(key)
            positions = jnp.arange(L, dtype=jnp.int32)[None]
            row = init_kv_cache(model.config, 1, L)
            logits, row = model.apply({"params": params}, tokens,
                                      kv_caches=row, positions=positions)
            last = jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                                axis=0, keepdims=False)
            token = _sample(last[None].astype(jnp.float32), rng,
                            temp[None], topk[None])[0]

            def write(big, chunk):
                return jax.lax.dynamic_update_slice(
                    big, chunk.astype(big.dtype), (slot, 0, 0, 0))

            new = [{"k": write(caches[i]["k"], row[i]["k"]),
                    "v": write(caches[i]["v"], row[i]["v"])}
                   for i in range(n_layer)]
            return token, new, key

        return jax.jit(prefill, donate_argnums=self._donate)

    def _decode_impl(self, params, caches, step):
        # ``step``: the slots' device-resident state (``_step_state``);
        # every decode program returns what it advanced (``_advance``).
        self.trace_counts["decode"] += 1  # trace-time only
        logits, new = self._model.apply(
            {"params": params}, step["tokens"][:, None], kv_caches=caches,
            positions=step["positions"][:, None])
        return new, _advance(step, logits)

    # --- compiled programs: paged tier --------------------------------------

    def _paged_caches(self, pools, tables, fresh: bool = False,
                      block_step: bool = False):
        """The model's ``kv_caches`` over the pools: each layer with the
        table of its kind (``tables``: ``{kind: table}``), a ring with
        the position from which a row is invalid.  ``fresh``: the
        chunk begins its rows, as every prefill over a ring does.
        ``block_step``: the chunk is a block a row of a model that
        generates by blocks, and reads as a decode step does."""
        return [None if p is None else
                {"k_pool": p["k"], "v_pool": p["v"], "fresh": fresh,
                 "block_step": block_step,
                 **({"table": tables["window"], "limit": self.max_seq_len}
                    if kind.window else {"table": tables["full"]})}
                for p, kind in zip(pools, self._declared)]

    def _copy_impl(self, pools, src, dst):
        self.trace_counts["kv_copy"] += 1  # trace-time only
        return [None if p is None else
                {"k": p["k"].at[dst].set(p["k"][src]),
                 "v": p["v"].at[dst].set(p["v"][src])} for p in pools]

    def _copy_block(self, src: int, dst: int) -> None:
        """Device block copy (COW / partial-prefix admission) — the
        callback :class:`BlockPool` drives."""
        self._pools = self._copy_fn(self._pools, jnp.int32(src),
                                    jnp.int32(dst))

    def _import_impl(self, pools, blk, k, v):
        """Write one wire-received block's K/V (``[n_layer, block,
        H * D]``) into every layer's pool at block ``blk`` — the binding
        half of live KV migration (ONE compiled program: the block id
        is data, not shape)."""
        self.trace_counts["kv_import"] += 1  # trace-time only
        heads = self._kv_row        # the rest of a pool row is padding
        return [{"k": pools[i]["k"].at[blk, :, :heads].set(
                     k[i].astype(pools[i]["k"].dtype)),
                 "v": pools[i]["v"].at[blk, :, :heads].set(
                     v[i].astype(pools[i]["v"].dtype))}
                for i in range(self._model.config.n_layer)]

    def _make_paged_prefill(self, L: int):
        model = self._model
        S, SV = self.max_seq_len, self._view_len

        def prefill(params, pools, table_row, tokens, start, length,
                    key, temp, topk):
            # ``start`` = resident-prefix length (the suffix's first
            # absolute position); ``length`` = real suffix tokens in
            # the L-padded chunk.  Both are traced values: one compiled
            # program per bucket regardless of hit depth.
            self.trace_counts[f"prefill_{L}"] += 1  # trace-time only
            key, rng = jax.random.split(key)
            idx = jnp.arange(L, dtype=jnp.int32)
            valid = (idx < length) & (start + idx < S)
            # Invalid rows (padding, past the cache) take the view's
            # last position: the table's trash column.
            positions = jnp.where(valid, start + idx, SV - 1)
            caches = self._paged_caches(
                pools, jax.tree.map(lambda t: t[None], table_row),
                fresh=bool(self._window))
            logits, new = model.apply(
                {"params": params}, tokens, kv_caches=caches,
                positions=positions[None])
            last = jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                                axis=0, keepdims=False)
            token = _sample(last[None].astype(jnp.float32), rng,
                            temp[None], topk[None])[0]
            return token, new, key

        return jax.jit(prefill, donate_argnums=self._donate)

    def _decode_paged_impl(self, params, pools, tables, step):
        self.trace_counts["decode"] += 1  # trace-time only
        logits, new, sent = self._forward_step(
            params, self._paged_caches(pools, tables),
            step["tokens"][:, None], step["positions"][:, None])
        return new, dict(_advance(step, logits), **sent)

    def _forward_step(self, params, caches, tokens, positions):
        """A decode step's forward: ``(logits, the caches it wrote,
        what rides back beside the step state)``.  A model with expert
        layers hands back what each sowed of the step's routing
        (``pairs_held``: the pairs each held expert was sent) as
        ``experts_sent`` — pairs held, held experts sent a pair — for
        the host to add up (``_step``)."""
        if not self.expert_layers:
            logits, new = self._model.apply(
                {"params": params}, tokens, kv_caches=caches,
                positions=positions)
            return logits, new, {}
        (logits, new), sown = self._model.apply(
            {"params": params}, tokens, kv_caches=caches,
            positions=positions, mutable=["intermediates"])
        sizes = jnp.stack([
            leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
                sown)[0]
            if any(getattr(k, "key", None) == "pairs_held" for k in path)])
        return logits, new, {
            "experts_sent": jnp.stack([sizes.sum(), (sizes > 0).sum()])}

    # --- compiled programs: a model that generates by blocks ----------------

    def _make_block_prefill(self, L: int):
        model, B = self._model, self._block
        S, SV = self.max_seq_len, self._view_len

        def prefill(params, pools, table_row, tokens, length):
            # The prompt's whole blocks (``length`` tokens, a multiple
            # of the block's length) go into the cache under the block
            # mask, from position 0 — the chunk's own keys are all a
            # query sees (``fresh``) — and no token comes out: the
            # prompt's tail opens the first block (``_block_row``).
            # What comes back beside the pools is a number to fence on.
            self.trace_counts[f"prefill_{L}"] += 1  # trace-time only
            idx = jnp.arange(L, dtype=jnp.int32)
            valid = (idx < length) & (idx < S)
            positions = jnp.where(valid, idx, SV - 1)
            caches = self._paged_caches(
                pools, jax.tree.map(lambda t: t[None], table_row),
                fresh=True)
            x, new = model.apply(
                {"params": params}, tokens, kv_caches=caches,
                positions=positions[None], return_hidden=True)
            return x[0, 0, 0].astype(jnp.float32), new

        return jax.jit(prefill, donate_argnums=self._donate)

    def _decode_block_impl(self, params, pools, tables, step):
        """The block step: every active row forwards its current block
        — written at its positions, then read by the decode kernel with
        the row's length at the block's end — and ``_transfer`` turns
        the block's logits into the row's next state.  Rows in
        different phases share it; the shapes are fixed."""
        self.trace_counts["decode"] += 1  # trace-time only
        positions = step["positions"][:, None] + jnp.arange(
            self._block, dtype=jnp.int32)
        logits, new, sent = self._forward_step(
            params, self._paged_caches(pools, tables, block_step=True),
            step["tokens"], positions)
        with jax.named_scope("hvd_tpu_block_transfer"):
            advanced = _transfer(step, logits,
                                 self._model.config.mask_token)
        return new, dict(advanced, **sent)

    # --- compiled programs: state tier --------------------------------------

    def _make_state_prefill(self, L: int):
        model = self._model

        def prefill(params, states, tokens, start, length, slot, key,
                    temp, topk):
            # ``start`` = positions already in the slot's state (0 for a
            # new request, which therefore begins from zeros whatever
            # the slot held; a resume's later chunks continue);
            # ``length`` = real tokens in the L-padded chunk.  Both are
            # traced: one program per bucket.
            self.trace_counts[f"prefill_{L}"] += 1  # trace-time only
            key, rng = jax.random.split(key)
            idx = jnp.arange(L, dtype=jnp.int32)
            carried = start > 0

            def row(big):
                mine = jax.lax.dynamic_slice_in_dim(big, slot, 1, axis=0)
                return jnp.where(carried, mine, jnp.zeros_like(mine))

            caches = [{"s": row(st["s"]), "z": row(st["z"]),
                       "valid": (idx < length)[None]} for st in states]
            logits, new = model.apply(
                {"params": params}, tokens, kv_caches=caches,
                positions=(start + idx)[None],
                logit_rows=jnp.reshape(length - 1, (1,)))
            token = _sample(logits[:, 0].astype(jnp.float32), rng,
                            temp[None], topk[None])[0]

            def write(big, mine):
                return jax.lax.dynamic_update_slice_in_dim(
                    big, mine, slot, axis=0)

            return token, [{"s": write(st["s"], n["s"]),
                            "z": write(st["z"], n["z"])}
                           for st, n in zip(states, new)], key

        return jax.jit(prefill, donate_argnums=self._donate)

    def _decode_state_impl(self, params, states, step):
        # A row without a request (the step state's ``active``) leaves
        # its state as it is: a dense row's stale keys hide behind the
        # position mask; a state has no such mask.
        self.trace_counts["decode"] += 1  # trace-time only
        caches = [dict(st, valid=step["active"][:, None]) for st in states]
        logits, new = self._model.apply(
            {"params": params}, step["tokens"][:, None], kv_caches=caches,
            positions=step["positions"][:, None])
        return new, _advance(step, logits)

    def _state_prefill(self, slot: int, seq: List[int],
                       sampling: SamplingParams, span_args: dict,
                       stage: bool = False) -> int:
        """``seq`` into ``slot``'s state from position 0, in
        bucket-sized chunks with the state carried (a prompt is one
        chunk; a resumed sequence may be longer than the largest
        bucket).  Returns the token sampled after the last chunk;
        ``stage`` sends the bind's arrays while it is being sampled."""
        top = self.prefill_buckets[-1]
        pos, n, token = 0, len(seq), None
        while pos < n:
            ns = min(n - pos, top)
            L = self.bucket_for(ns)
            padded = np.zeros((1, L), np.int32)
            padded[0, :ns] = np.asarray(seq[pos:pos + ns], np.int32)
            span_args["bucket"] = L     # the last chunk's
            args = (self._params, self._states, jnp.asarray(padded),
                    jnp.int32(pos), jnp.int32(ns), jnp.int32(slot),
                    self._rng, jnp.float32(sampling.temperature),
                    jnp.int32(sampling.top_k))
            with trace_mod.annotate("hvd_tpu_prefill_dispatch"):
                token, self._states, self._rng = self._timed(
                    span_args, "dispatch_us", self._prefill_fns[L], *args)
            pos += ns
        self.state_resets += 1
        if stage:
            self._stage_bind(slot, n, sampling)
        with trace_mod.annotate("hvd_tpu_prefill_fence"):
            return self._timed(span_args, "fence_us", int, token)

    # --- compiled programs: speculative tier --------------------------------

    def _make_draft_prefill(self, L: int):
        drafter = self._drafter
        n_layer = drafter.config.n_layer

        def dprefill(dparams, dcaches, tokens, slot):
            self.trace_counts[f"draft_prefill_{L}"] += 1  # trace-time
            positions = jnp.arange(L, dtype=jnp.int32)[None]
            row = init_kv_cache(drafter.config, 1, L)
            _, row = drafter.apply({"params": dparams}, tokens,
                                   kv_caches=row, positions=positions)

            def write(big, chunk):
                return jax.lax.dynamic_update_slice(
                    big, chunk.astype(big.dtype), (slot, 0, 0, 0))

            return [{"k": write(dcaches[i]["k"], row[i]["k"]),
                     "v": write(dcaches[i]["v"], row[i]["v"])}
                    for i in range(n_layer)]

        return jax.jit(dprefill, donate_argnums=self._donate)

    def _spec_draft_impl(self, dparams, dcaches, tokens, positions):
        """Greedy-draft ``spec_k`` tokens for every slot in ONE program
        (a ``lax.scan`` over the drafter's own dense decode).  The scan
        runs ``K + 1`` iterations: the extra step feeds the last draft
        token so its K/V lands too — with a fully accepted draft the
        next step starts at ``p + K + 1``, and a gap at ``p + K`` would
        silently degrade every later draft (the verify path would still
        be exact; only acceptance would rot).  Entries past the
        accepted prefix go stale but are overwritten sequentially
        before any query can see them (same argument as slot reuse)."""
        self.trace_counts["spec_draft"] += 1  # trace-time only
        drafter = self._drafter

        def body(carry, _):
            caches, toks, pos = carry
            logits, caches = drafter.apply(
                {"params": dparams}, toks[:, None], kv_caches=caches,
                positions=pos[:, None])
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            return (caches, nxt, pos + 1), nxt

        (dcaches, _, _), drafts = jax.lax.scan(
            body, (dcaches, tokens, positions), None,
            length=self.spec_k + 1)
        return jnp.moveaxis(drafts[:self.spec_k], 0, 1), dcaches

    def _spec_verify_impl(self, params, pools, tables, tokens, draft,
                          positions, temps, topks, spec_ok, key):
        """Verify the whole draft in one batched target forward.

        Chunk ``[t0, d1..dK]`` runs at positions ``p..p+K``; the
        accepted prefix is the longest run of drafts matching the
        target's own greedy chain, so the emitted tokens are exactly
        what plain greedy decode would produce (docs/serving.md).  The
        model writes every chunk row's K/V at its position before it
        attends (positions past the cache go to the trash block), so
        ``accepted`` decides only how far the slot advances.  A
        rejected row is left at a position beyond the slot's new
        length: the ``<= position`` mask hides it, and the next step
        writes that position before any query can see it.  Rows with
        ``spec_ok`` false (no opt-in, or temperature sampling) accept
        nothing and emit one plain-sampled token."""
        self.trace_counts["spec_verify"] += 1  # trace-time only
        key, rng = jax.random.split(key)
        K = self.spec_k
        S, SV = self.max_seq_len, self._view_len
        chunk_toks = jnp.concatenate([tokens[:, None], draft], axis=1)
        idx = jnp.arange(K + 1, dtype=jnp.int32)[None]
        pos = positions[:, None] + idx
        pos_safe = jnp.where(pos < S, pos, SV - 1)
        caches = self._paged_caches(pools, tables)
        logits, new = self._model.apply(
            {"params": params}, chunk_toks, kv_caches=caches,
            positions=pos_safe)
        logits = logits.astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        matches = (draft == greedy[:, :K]).astype(jnp.int32)
        accepted = jnp.cumprod(matches, axis=1).sum(axis=1)
        accepted = jnp.where(spec_ok, accepted, 0)
        # The last emitted token needs no K/V write, but every ACCEPTED
        # draft does — cap acceptance at the cache's remaining rows.
        accepted = jnp.minimum(accepted,
                               jnp.maximum(S - 1 - positions, 0))
        first = _sample(logits[:, 0], rng, temps, topks)
        out = greedy.at[:, 0].set(first)   # argmax already, unless temp>0
        return out, accepted, new, key

    # --- host-side slot API -------------------------------------------------

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        raise PromptTooLongError(
            f"prompt of {prompt_len} tokens exceeds the largest prefill "
            f"bucket ({self.prefill_buckets[-1]})")

    def check_prompt(self, prompt_len: int) -> int:
        """Full admission-time validation (the batcher calls this so an
        unservable prompt fails before it costs a queue entry): bucket
        fit AND room to generate.  Returns the bucket."""
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if prompt_len >= self.max_seq_len or (
                self._block and prompt_len - prompt_len % self._block
                + self._block > self.max_seq_len):
            raise PromptTooLongError(
                f"prompt of {prompt_len} tokens leaves no room to "
                f"generate (a request may reach {self.max_seq_len} "
                f"positions)")
        return self.bucket_for(prompt_len)

    def check_sampling(self, sampling: SamplingParams) -> None:
        """What a model that generates by blocks asks of a request's
        schedule (the batcher calls this at admission): 1 to
        ``block_length`` denoising steps a block (0 = the block's
        length) and a transfer rule it knows."""
        if not self._block:
            return
        steps = sampling.denoising_steps or self._block
        if not 1 <= steps <= self._block:
            raise ValueError(
                f"denoising_steps {sampling.denoising_steps} is not 1 to "
                f"the block's length ({self._block})")
        if sampling.transfer not in ("static", "dynamic"):
            raise ValueError(f"unknown transfer rule "
                             f"{sampling.transfer!r}; expected 'static' or "
                             f"'dynamic'")

    def check_prompt_tokens(self, prompt: Sequence[int]) -> int:
        """:meth:`check_prompt` plus token-ID range validation.  An
        out-of-vocab id embeds as NaN (``jnp.take`` fill semantics),
        and the paged pool is a SHARED structure: one poison request's
        NaN rows would outlive it in the trash/prefix blocks and
        contaminate every later batchmate through the ``0 x NaN``
        attention sum — so the poison must die at admission, not in
        the pool."""
        bucket = self.check_prompt(len(prompt))
        vocab = self._model.config.vocab_size
        lo, hi = min(prompt), max(prompt)   # C-speed single pass
        if lo < 0 or hi >= vocab:
            raise ValueError(
                f"prompt token id {lo if lo < 0 else hi} outside the "
                f"model vocabulary [0, {vocab})")
        return bucket

    def free_slots(self) -> List[int]:
        with self._slot_lock:
            return [int(s) for s in np.nonzero(~self._active)[0]]

    def active_slots(self) -> List[int]:
        with self._slot_lock:
            return [int(s) for s in np.nonzero(self._active)[0]]

    def slot_full(self, slot: int) -> bool:
        """True when the next decode would pass ``max_seq_len`` (the
        next decode works at index ``_positions[slot]``, valid while it
        is ``< max_seq_len``: the cache's last row, or with a retention
        state the last position a request may reach)."""
        with self._slot_lock:
            if self._block:
                # The next block: behind the one whose commit is due.
                return (int(self._positions[slot]) + self._block * (
                    1 + bool(self._block_final[slot]))) > self.max_seq_len
            return int(self._positions[slot]) >= self.max_seq_len

    def _device_table(self):
        """The block tables on the device, ``{kind: table}``, each sent
        again only when the host's differs from the bytes last sent
        (``_ensure_writable``, ``_begin_request`` and ``release``
        change it: a row crosses a block boundary one step in
        ``kv_block``; a full ring changes no more)."""
        for kind, table in self._tables.items():
            if (self._table_sent[kind] is None
                    or not np.array_equal(table, self._table_sent[kind])):
                self._table_sent[kind] = table.copy()
                self._table_device[kind] = self._to_device(
                    self._table_sent[kind])
                self.table_uploads += 1
        return dict(self._table_device)

    def _table_rows(self, slot: int):
        """``slot``'s row of each table, for a prefill."""
        return {kind: jnp.asarray(t[slot])
                for kind, t in self._tables.items()}

    def _begin_request(self, slot: int, seq: List[int]) -> int:
        """``slot``'s blocks for a new request: a chain over whatever
        prefix of ``seq`` is resident (its length is returned) and,
        beside window layers, an empty ring."""
        hit = self._kv.begin_request(slot, seq)
        if self._ring is not None:
            self._ring.begin(slot)
        return hit

    def _ensure_writable(self, slot: int, start: int, n: int) -> None:
        """Blocks of every kind for positions ``[start, start + n)``."""
        self._kv.ensure_writable(slot, start, n)
        if self._ring is not None:
            self._ring.reach(slot, (start + n - 1) // self.kv_block)

    def _slot_snapshot(self):
        """Locked copy of the decode-relevant slot arrays: the step
        paths read ONE consistent view instead of racing router-thread
        release()/adopt() mutations field by field (hvdsan read-site
        catch — max_slots-sized copies, nanoseconds)."""
        with self._slot_lock:
            blocks = None
            if self._blocks is not None:
                blocks = dict({k: v.copy() for k, v in self._blocks.items()},
                              final=self._block_final.copy())
            return (self._active.copy(), self._positions.copy(),
                    self._temps.copy(), self._topks.copy(),
                    self._last_tokens.copy(), self._spec.copy(),
                    self._slots_changed, blocks)

    # --- guarded slot-state mutation ----------------------------------------
    # The ONE place slot state changes (the hvdlint lock checker holds
    # every annotated mutation to a lexical ``with _slot_lock`` block):
    # prefill used to write these fields inline next to the cache-chunk
    # write, which left router-thread release() racing the batcher.

    def _empty_blocks(self) -> Dict[str, np.ndarray]:
        """The block mirror of an engine without a request (``_transfer``
        has the fields); a cleared row goes back to this."""
        rows, block = self.max_slots, (self.max_slots, self._block)
        return {"tokens": np.zeros(block, np.int32),
                "masked": np.zeros(block, bool),
                "chosen": np.zeros(block, np.int32),
                "steps": np.zeros(rows, np.int32),
                "denoise": np.zeros(rows, np.int32),
                "dynamic": np.zeros(rows, bool),
                "threshold": np.zeros(rows, np.float32)}

    def _block_row(self, prompt: List[int], sampling: SamplingParams) -> dict:
        """The first block of a request, as a row of the step state:
        blocks lie on absolute positions, so the prompt's tail (its
        last ``len(prompt) mod B`` tokens) opens the block as positions
        already clean, and the rest are masks."""
        B = self._block
        tail = prompt[len(prompt) - len(prompt) % B:]
        return {"positions": len(prompt) - len(tail),
                "tokens": np.asarray(
                    tail + [self._model.config.mask_token] * (B - len(tail)),
                    np.int32),
                "masked": np.arange(B) >= len(tail),
                "chosen": np.full(B, -1, np.int32), "steps": 0,
                "denoise": sampling.denoising_steps or B,
                "dynamic": sampling.transfer == "dynamic",
                "threshold": sampling.threshold}

    def _bind_slot(self, slot: int, n_prompt: int, token: Optional[int],
                   sampling: SamplingParams, prefix_hit: int,
                   row: Optional[dict] = None) -> None:
        with self._slot_lock:
            self._active[slot] = True
            self._positions[slot] = n_prompt   # first generated index
            self._temps[slot] = sampling.temperature
            self._topks[slot] = sampling.top_k
            if row is None:
                self._last_tokens[slot] = token    # first decode consumes it
            else:
                # A block a row: the block's first position, and no
                # token awaited.
                self._positions[slot] = row["positions"]
                self._block_final[slot] = False
                for field in self._blocks:
                    self._blocks[field][slot] = row[field]
            self._spec[slot] = bool(sampling.spec)
            self._prefix_hits[slot] = prefix_hit
            self._slots_changed += 1

    def _advance_slot(self, slot: int, tokens: List[int]) -> None:
        with self._slot_lock:
            if not self._active[slot]:
                return   # released concurrently (cancel): drop
            # No mark: the decode program advanced the device's copy by
            # the same token (a speculative step, which did not, says
            # so itself).
            self._last_tokens[slot] = tokens[-1]
            self._positions[slot] += len(tokens)

    def _advance_blocks(self, slots: List[int], now: dict) -> None:
        """The mirror of ``slots`` after a block step, from the step's
        report (``_read_report``): all rows under one lock."""
        with self._slot_lock:
            # A row released concurrently (cancel) is dropped.
            rows = np.zeros(self.max_slots, bool)
            rows[slots] = True
            rows &= self._active
            for field in ("tokens", "chosen", "masked"):
                np.copyto(self._blocks[field], now[field],
                          where=rows[:, None])
            np.copyto(self._blocks["steps"], now["steps"], where=rows)
            np.copyto(self._positions, now["positions"], where=rows)
            np.copyto(self._block_final, now["final"], where=rows)

    def _clear_slot(self, slot: int) -> None:
        with self._slot_lock:
            self._active[slot] = False
            self._positions[slot] = 0
            self._temps[slot] = 0.0
            self._topks[slot] = 0
            self._spec[slot] = False
            self._prefix_hits[slot] = 0
            if self._blocks is not None:
                # No mask state is left behind for the next request.
                self._block_final[slot] = False
                for mirror in self._blocks.values():
                    mirror[slot] = 0
            self._slots_changed += 1

    # --- prefix sharing -----------------------------------------------------

    def prefix_probe(self, prompt: Sequence[int]) -> int:
        """Resident-prefix length for ``prompt`` right now (no side
        effects) — the batcher's admission-time lookup; 0 on the dense
        tier, and always 0 over a retention state (a state holds no
        prefix apart from the rest)."""
        if self._kv is None:
            return 0
        return self._kv.probe(list(prompt))

    def prefix_hit_tokens(self, slot: int) -> int:
        """Prefix tokens the last ``start()`` on ``slot`` reused."""
        with self._slot_lock:
            return int(self._prefix_hits[slot])

    # --- request lifecycle --------------------------------------------------

    def start(self, slot: int, prompt: Sequence[int],
              sampling: SamplingParams) -> Optional[int]:
        """Prefill ``prompt`` into ``slot``; returns the first sampled
        token — or None from a model that generates by blocks, whose
        prefill writes the prompt's whole blocks and yields no token.
        One compiled program per (bucket, slot-batch) shape —
        on the paged tier the bucket covers only the non-resident
        suffix.  The whole call is one ``hvd_tpu_engine_prefill``
        span: ``args.dispatch_us`` and ``args.fence_us`` say how long
        the program's dispatch and the wait for its token took,
        ``args.stalled`` which of them held a stalled prefill."""
        span_args = {"slot": int(slot), "prompt_len": len(prompt),
                     "cache": self.kv_mode}
        with trace_mod.span("hvd_tpu_engine_prefill", args=span_args):
            token = self._start(slot, prompt, sampling, span_args)
            if trace_mod.enabled():
                self._note_prefill(span_args)
            return token

    def _start(self, slot: int, prompt: Sequence[int],
               sampling: SamplingParams, span_args: dict) -> Optional[int]:
        with self._slot_lock:
            if self._active[slot]:
                raise RuntimeError(f"slot {slot} is already active")
        prompt = [int(t) for t in prompt]
        n = len(prompt)
        self.check_prompt_tokens(prompt)
        self.check_sampling(sampling)
        if self._block:
            return self._start_blocks(slot, prompt, sampling, span_args)
        if self.kv_mode == "state":
            hit = 0
            span_args["prefix_hit"] = 0
            token = self._state_prefill(slot, prompt, sampling, span_args,
                                        stage=True)
        elif self.kv_mode == "paged":
            hit = self._begin_request(slot, prompt)
            ns = n - hit
            L = self.bucket_for(ns)
            self._ensure_writable(slot, hit, ns)
            padded = np.zeros((1, L), np.int32)
            padded[0, :ns] = np.asarray(prompt[hit:], np.int32)
            fn = self._prefill_fns[L]
            span_args.update(bucket=L, prefix_hit=hit)
            if self._window:
                span_args["window_blocks"] = min(
                    -(-n // self.kv_block), self.ring_blocks)
            args = (self._params, self._pools,
                    self._table_rows(slot), jnp.asarray(padded),
                    jnp.int32(hit), jnp.int32(ns), self._rng,
                    jnp.float32(sampling.temperature),
                    jnp.int32(sampling.top_k))
            with trace_mod.annotate("hvd_tpu_prefill_dispatch"):
                token, self._pools, self._rng = self._timed(
                    span_args, "dispatch_us", fn, *args)
            self._stage_bind(slot, n, sampling)
            with trace_mod.annotate("hvd_tpu_prefill_fence"):
                token = self._timed(span_args, "fence_us", int, token)
            self._kv.index_prompt(slot, prompt)
        else:
            hit = 0
            L = self.bucket_for(n)
            padded = np.zeros((1, L), np.int32)
            padded[0, :n] = np.asarray(prompt, np.int32)
            fn = self._prefill_fns[L]
            span_args.update(bucket=L, prefix_hit=0)
            args = (self._params, self._caches, jnp.asarray(padded),
                    jnp.int32(n), jnp.int32(slot), self._rng,
                    jnp.float32(sampling.temperature),
                    jnp.int32(sampling.top_k))
            with trace_mod.annotate("hvd_tpu_prefill_dispatch"):
                token, self._caches, self._rng = self._timed(
                    span_args, "dispatch_us", fn, *args)
            self._stage_bind(slot, n, sampling)
            with trace_mod.annotate("hvd_tpu_prefill_fence"):
                token = self._timed(span_args, "fence_us", int, token)
        if self._drafter is not None:
            # The drafter recomputes the full prompt (its dense cache
            # shares nothing) — it is the small model by construction.
            Lf = self.bucket_for(n)
            dp = np.zeros((1, Lf), np.int32)
            dp[0, :n] = np.asarray(prompt, np.int32)
            self._drafter_caches = self._draft_prefill_fns[Lf](
                self._drafter_params, self._drafter_caches,
                jnp.asarray(dp), jnp.int32(slot))
        self._bind_slot(slot, n, token, sampling, hit)
        return token

    def _start_blocks(self, slot: int, prompt: List[int],
                      sampling: SamplingParams, span_args: dict) -> None:
        """``_start`` for a model that generates by blocks: the
        prompt's whole blocks through the prefill program of their
        bucket (a prompt shorter than a block still runs it, on no
        token), the tail and the masks into the slot's first block."""
        self._begin_request(slot, prompt)
        row = self._block_row(prompt, sampling)
        ns = row["positions"]
        L = self.bucket_for(max(ns, 1))
        if ns:
            self._ensure_writable(slot, 0, ns)
        padded = np.zeros((1, L), np.int32)
        padded[0, :ns] = np.asarray(prompt[:ns], np.int32)
        span_args.update(bucket=L, prefix_hit=0)
        with trace_mod.annotate("hvd_tpu_prefill_dispatch"):
            done, self._pools = self._timed(
                span_args, "dispatch_us", self._prefill_fns[L],
                self._params, self._pools, self._table_rows(slot),
                jnp.asarray(padded), jnp.int32(ns))
        self._stage_bind(slot, len(prompt), sampling, row)
        with trace_mod.annotate("hvd_tpu_prefill_fence"):
            self._timed(span_args, "fence_us", float, done)
        self._bind_slot(slot, len(prompt), None, sampling, 0, row)

    @staticmethod
    def _timed(span_args: dict, key: str, fn, *args):
        """``fn(*args)``, the call's microseconds on the span clock
        added to ``span_args[key]``: a prefill's dispatch (summed over
        the chunks of a prompt that goes in several) and the fence on
        its token."""
        t = time.monotonic_ns()
        out = fn(*args)
        span_args[key] = (span_args.get(key, 0.0)
                          + (time.monotonic_ns() - t) / 1e3)
        return out

    def step(self) -> Dict[int, List[int]]:
        """One decode step for every active slot → ``{slot: [tokens]}``
        (one token per slot on the plain path; up to ``spec_k + 1``
        under speculative decoding; from a model that generates by
        blocks the tokens whose block's last mask fell in this step, in
        position order, none in most steps: :class:`FinalTokens`, whose
        ``steps`` says at which denoising step each was chosen, and the
        span's ``args.denoise_rows``, ``args.commit_rows`` and
        ``args.tokens_final`` what the rows did).  Inactive rows ride
        along masked
        and write into the trash block.  A step with an active slot is
        one ``hvd_tpu_engine_decode`` span: the host's table building,
        whatever it had to upload (``args.uploads``), the dispatch, the
        device's work and the token fence — ``args.prepare_us``,
        ``args.dispatch_us`` and ``args.fence_us`` say how long each
        took, ``args.stalled`` which of them held a stalled step."""
        snap = self._slot_snapshot()
        active = [int(s) for s in np.nonzero(snap[0])[0]]
        if not active:
            return {}
        args = {"active": len(active)}     # _step adds what it learns
        with trace_mod.span("hvd_tpu_engine_decode", args=args):
            return self._step(active, snap, args)

    def _step(self, active: List[int], snap: tuple,
              span_args: dict) -> Dict[int, List[int]]:
        act, pos, temps, topks, _, spec, changed, blocks = snap
        if self._drafter is not None and any(
                spec[s] and temps[s] <= 0 for s in active):
            return self._step_spec(active, snap)
        # With tracing on the step says where its time went; off, the
        # dispatch alone is timed (``_wake_runtime`` needs it).
        traced = trace_mod.enabled()
        if traced:
            began = time.monotonic_ns()
        # A steady step uploads nothing: the device holds the slots'
        # state as the last step left it, and the table as last sent.
        uploads = 0
        if changed != self._slots_sent:
            uploads = self._upload_slots(self._slot_arrays(snap))
            self._slots_sent = changed
            self.step_state_uploads += bool(uploads)
        if self.kv_mode == "state":
            # The step's kernel reads and rewrites the state of the
            # rows that hold a request and of no other
            # (ops/retention.py): as many as ``valid`` marks, never
            # none here.
            span_args["rows"] = len(active)
            self.state_rows_visited += len(active)
            self._states, advanced = self._dispatch_decode(
                self._params, self._states, self._step_state)
        elif self.kv_mode == "paged":
            # What a row brings to the step: a token, or its block.
            width = self._block or 1
            for s in active:
                self._ensure_writable(s, int(pos[s]), width)
            if blocks is not None:
                # Rows whose block came in all clean commit it; the
                # others denoise theirs.
                commits = int((act & blocks["final"]).sum())
                span_args.update(denoise_rows=len(active) - commits,
                                 commit_rows=commits)
                self.block_steps += 1
                self.denoise_forwards += len(active) - commits
                self.commit_forwards += commits
                self.blocks_committed += commits
            if self._tp_mesh is None:
                # The decode step's attention walks each row's table to
                # its length (ops/paged_attention.py); the gathered
                # view would have read every column of every row.
                live = int((np.where(act, pos + width - 1, 0)
                            // self.kv_block + 1).sum())
                span_args["live_blocks"] = live
                self.paged_decode_steps += 1
                self.paged_live_blocks += live
                self.paged_view_blocks += self._tables["full"].size
                seen = np.where(act, pos + width, 0)
                self.paged_live_rows += len(active)
                self.paged_live_positions["full"] += int(seen.sum())
                self.kv_block_steps["full"] += self._kv.blocks_in_use()
                if self._ring is not None:
                    self.paged_live_positions["window"] += int(
                        np.minimum(seen, self._window).sum())
                    self.kv_block_steps["window"] += \
                        self._ring.blocks_in_use()
            sent = self.table_uploads
            table = self._device_table()
            uploads += self.table_uploads - sent
            self._pools, advanced = self._dispatch_decode(
                self._params, self._pools, table, self._step_state)
        else:
            self._caches, advanced = self._dispatch_decode(
                self._params, self._caches, self._step_state)
        # What the expert layers were sent rides back with the tokens
        # and is no part of the state the next step takes.
        experts_sent = advanced.pop("experts_sent", None)
        # ... nor is a block step's report, which holds what it sampled.
        report = advanced.pop("report", None)
        self._step_state = dict(self._step_state, **advanced)
        # The fence: the state's tokens are what the step sampled.
        fenced = advanced["tokens"] if report is None else report
        if report is not None:
            # What the host reads of a block step is asked for now, so
            # that it leaves the device as the program ends and not
            # when the host gets to it (the wait between the two is the
            # runtime's: a millisecond or two of a step, and more on
            # one host than on the next); and while the device
            # computes, the table the next step needs.
            report.copy_to_host_async()
            if experts_sent is not None:
                experts_sent.copy_to_host_async()
            self._stage_next_table(active, snap, span_args)
        self.decode_steps += 1
        # Whether the program's sampling branch ran, from the mirror.
        sampling = int((act & (temps > 0)).any())
        self.sampling_steps += sampling
        span_args["sampling"] = sampling
        span_args["uploads"] = uploads
        at, returned = self._dispatched
        dispatch_us = (returned - at) / 1e3
        dispatch_usual = self._follow("dispatch", dispatch_us)
        self._wake_runtime(span_args, dispatch_us, dispatch_usual)
        if traced:
            with trace_mod.annotate("hvd_tpu_decode_fence"):
                t = time.monotonic_ns()
                nxt = np.asarray(fenced)
                fence_us = (time.monotonic_ns() - t) / 1e3
            self._note_phases(
                span_args, {"prepare": (at - began) / 1e3,
                            "dispatch": dispatch_us, "fence": fence_us},
                dispatch_usual)
        else:
            nxt = np.asarray(fenced)
        if experts_sent is not None:
            pairs, touched = np.asarray(experts_sent)
            self.expert_pairs += int(pairs)
            self.experts_touched += int(touched)
        held = self._device_slots
        if report is not None:
            return self._blocks_out(active, nxt, span_args)
        self._device_slots = dict(
            held, tokens=nxt, positions=held["positions"] + held["active"])
        out = {}
        for s in active:
            toks = [int(nxt[s])]
            out[s] = toks
            self._advance_slot(s, toks)
        return out

    def _stage_next_table(self, active: List[int], snap: tuple,
                          span_args: dict) -> None:
        """Between a block step's dispatch and its fence: the blocks
        and the table the *next* step needs, sent while the device
        computes.  Which rows commit in this step the snapshot knows
        (``final``), so where every row's block lies in the next step
        is known before this one's report: a committing row opens its
        next block ``B`` positions on.  The next step compares again
        (``_device_table``) and as a rule finds nothing to send; a bind
        or a release until then changes the table like any other.  No
        row is taken past the cache's end, so the pool's floor (every
        slot servable whole) holds what is allotted here."""
        pos, blocks = snap[1], snap[7]
        B = self._block
        for s in active:
            if blocks["final"][s] and pos[s] + 2 * B <= self.max_seq_len:
                self._ensure_writable(s, int(pos[s]) + B, B)
        sent = self.table_uploads
        self._device_table()
        if self.table_uploads != sent:
            self.table_uploads_ahead += self.table_uploads - sent
            span_args["table_ahead"] = self.table_uploads - sent

    def _blocks_out(self, active: List[int], report,
                    span_args: dict) -> Dict[int, List[int]]:
        """What a block step hands back, from its fenced report
        (``_transfer``): the device's rows as they now are, the mirror
        of every active row, and for each the tokens that became final
        — its block's generated positions, in order, when the block's
        last mask fell; none otherwise."""
        now = _read_report(report, self._block)
        self._device_slots = dict(
            self._device_slots,
            **{k: v for k, v in now.items() if k != "final"})
        self._advance_blocks(active, now)
        out = {s: FinalTokens() for s in active}
        tokens, chosen = now["tokens"].tolist(), now["chosen"].tolist()
        for s in np.nonzero(now["final"])[0].tolist():
            if s in out:
                made = [i for i, at in enumerate(chosen[s]) if at >= 0]
                out[s] = FinalTokens([tokens[s][i] for i in made],
                                     [chosen[s][i] for i in made])
        final = sum(len(toks) for toks in out.values())
        self.tokens_final += final
        span_args["tokens_final"] = final
        return out

    def _dispatch_decode(self, *args):
        """The decode program's dispatch, and when the call was made
        and returned on the span clock (``_dispatched``)."""
        with trace_mod.annotate("hvd_tpu_decode_dispatch"):
            at = time.monotonic_ns()
            out = self._decode_fn(*args)
            self._dispatched = (at, time.monotonic_ns())
        return out

    def _follow(self, phase, took_us: float) -> Optional[float]:
        """Moves ``phase``'s usual length towards this reading — down
        at once, up by a twentieth and by no more than if the reading
        were twice the usual, so one long step hardly moves it and a
        host that has become slower for good is followed within some
        tens — and returns what it was before (None the first time)."""
        usual = self._usual.get(phase)
        if usual is None or took_us < usual:
            self._usual[phase] = took_us
        else:
            self._usual[phase] = usual + 0.05 * (
                min(took_us, 2 * usual) - usual)
        return usual

    def _note_phases(self, span_args: dict, took: Dict[str, float],
                     dispatch_usual: Optional[float]) -> None:
        """A traced step's phases (microseconds; the dispatch's usual
        length as it was before this step) onto its span, and the step
        held to the stall rule."""
        for phase, us in took.items():
            span_args[phase + "_us"] = us
        usual = {"prepare": self._follow("prepare", took["prepare"]),
                 "dispatch": dispatch_usual,
                 "fence": self._follow("fence", took["fence"])}
        self._note_stall(span_args, took, usual, self.stalled_steps,
                         "slow_decode_step", active=span_args["active"],
                         uploads=span_args["uploads"])

    def _note_prefill(self, span_args: dict) -> None:
        """A traced prefill's dispatch and fence held to the stall rule
        of a decode step's, each bucket against its own usual lengths:
        the fence holds the device's prefill of that many positions.
        (A pause of 110 ms is named where the phase usually takes
        under 37.)"""
        took = {phase: span_args[phase + "_us"]
                for phase in ("dispatch", "fence")}
        usual = {phase: self._follow((phase, span_args["bucket"]), us)
                 for phase, us in took.items()}
        self._note_stall(span_args, took, usual, self.stalled_prefills,
                         "slow_prefill", bucket=span_args["bucket"],
                         prompt_len=span_args["prompt_len"])

    @staticmethod
    def _note_stall(span_args: dict, took: Dict[str, float],
                    usual: Dict[str, Optional[float]],
                    count: collections.Counter, event: str,
                    **what) -> None:
        """The stall rule (``_STALL_TIMES``, ``_STALL_US``): the first
        phase that took that much longer than its usual length names
        the span a stalled one — ``args.stalled``, ``count`` and one
        ``event`` in the flight ring."""
        for phase, us in took.items():
            was = usual[phase]
            if (was is not None and us > _STALL_TIMES * was
                    and us - was >= _STALL_US):
                span_args["stalled"] = phase
                count[phase] += 1
                flight_mod.record(event, phase=phase, us=round(us, 1),
                                  usual_us=round(was, 1), **what)
                break

    def _wake_runtime(self, span_args: dict, took_us: float,
                      usual_us: Optional[float]) -> None:
        """After a dispatch that took far longer than they do.  On some
        hosts the runtime, while little goes through it, answers every
        call about a millisecond late — the dispatch, a transfer, the
        fence — and a step that uploads nothing can stay there for a
        whole request, where a step that uploaded seven arrays before
        each dispatch left within three (PERF.md section 6, PR 30).  So
        send it a few small transfers now, between the dispatch and
        the fence: the device computes, the host would only wait, and
        the step is no longer for them.  The dispatch's usual length
        follows the call's down at once and up slowly (``_follow``), so
        a host that has become slower for good stops being poked."""
        if usual_us is not None and took_us > 1.8 * usual_us:
            self._pokes = [self._to_device(_POKE) for _ in range(_POKES)]
            self.runtime_pokes += 1
            span_args["poked"] = True

    def _step_spec(self, active: List[int],
                   snap: tuple) -> Dict[int, List[int]]:
        """Draft-then-verify step: the drafter proposes ``spec_k``
        tokens per slot, the target verifies the whole draft in one
        batched forward, and each slot emits its accepted prefix plus
        the target's next token (1..K+1 tokens, token-identical to
        plain greedy decode).  ``snap`` is step()'s slot snapshot —
        re-snapshotting here could disagree with ``active`` (a
        concurrent cancel between the two reads) and write into a
        just-released slot's chain."""
        K = self.spec_k
        act, pos, temps, topks, last_tokens, spec = snap[:6]
        positions = np.where(act, pos, 0).astype(np.int32)
        for s in active:
            p = int(positions[s])
            self._kv.ensure_writable(s, p, min(K + 1, self.max_seq_len - p))
        spec_ok = act & spec & (temps <= 0)
        draft, self._drafter_caches = self._spec_draft_fn(
            self._drafter_params, self._drafter_caches,
            jnp.asarray(last_tokens), jnp.asarray(positions))
        if self._tp_mesh is not None:
            # The drafter runs single-device (it is the small model by
            # construction); re-home its committed draft onto the TP
            # mesh so the verify program sees one device set.
            draft = jax.device_put(
                np.asarray(draft),
                NamedSharding(self._tp_mesh, PartitionSpec()))
        out, accepted, self._pools, self._rng = self._spec_verify_fn(
            self._params, self._pools, self._device_table(),
            jnp.asarray(last_tokens), draft,
            jnp.asarray(positions), jnp.asarray(temps),
            jnp.asarray(topks), jnp.asarray(spec_ok), self._rng)
        # Rows advance here by what was accepted, on the host alone:
        # the next plain step uploads them.
        self._slots_sent = None
        out = np.asarray(out)
        accepted = np.asarray(accepted)
        result: Dict[int, List[int]] = {}
        spec_emitted = spec_steps = 0
        for s in active:
            toks = [int(t) for t in out[s, :int(accepted[s]) + 1]]
            result[s] = toks
            self._advance_slot(s, toks)
            if spec_ok[s]:
                # Only opted-in greedy slots measure drafter quality —
                # plain/temperature batchmates always emit exactly one
                # token and would dilute the ratio toward 1.0.
                spec_steps += 1
                spec_emitted += len(toks)
        self.spec_verify_steps += spec_steps
        self.spec_accepted_tokens += spec_emitted
        from ..obs import instrument as _obs

        _obs.on_spec_accept_ratio(
            self.spec_accepted_tokens / max(1, self.spec_verify_steps))
        return result

    def release(self, slot: int) -> None:
        """Return ``slot`` to the free pool.  Dense tier: cache rows
        are reused (stale keys invisible behind the position mask);
        paged tier: the chain's references drop and unreferenced
        prompt blocks stay resident for future prefix hits until
        evicted; state tier: the slot's state stays where it is, out of
        every program's sight (decode leaves rows without a request as
        they are), and the prefill that next binds the slot begins from
        zeros — so a release from another thread touches no device
        array."""
        if self._kv is not None:
            self._kv.release(slot)
        if self._ring is not None:
            self._ring.release(slot)
        self._clear_slot(slot)

    # --- deadline-aware preemption (serve/qos/; docs/qos.md) ----------------
    # Preempt/resume run on the batcher thread only (they drive the
    # same donated pools prefill/decode do); the QoS scheduler owns the
    # decision, this is the KV mechanics.

    def preempt_slot(self, slot: int, prompt: Sequence[int],
                     emitted: Sequence[int]):
        """Evict ``slot``'s generation for later resumption: index the
        full computed sequence (prompt + all emitted tokens whose K/V
        exists) into the prefix cache, release the slot, and return the
        engine's RNG snapshot.  The blocks drop to the LRU but stay
        reachable through the prefix index, so :meth:`resume_slot`
        re-admits with a prefix hit and recomputes only the tail —
        eviction costs a slot swap, not the generation's compute.

        The RNG snapshot is taken BEFORE release so a resume restores
        the exact stream the uninterrupted run would be on — the
        temperature half of the token-identity oracle (same
        sole-active-slot contract as KV migration's rng carry)."""
        self._refuse_blocks("preempt_slot")
        rng = np.asarray(self._rng)
        emitted = [int(t) for t in emitted]
        if self._kv is not None and emitted:
            # K/V coverage at preempt time is [0, n + k - 1): the last
            # emitted token is pending consumption, its K/V not yet
            # written — index exactly what is resident.
            seq = [int(t) for t in prompt] + emitted[:-1]
            if seq:
                self._kv.index_prompt(slot, seq)
        self.release(slot)
        return rng

    def can_resume(self, n_prompt: int, n_emitted: int) -> bool:
        """Whether a generation of this shape survives a
        preempt/resume cycle here: the paged and state tiers rebuild
        arbitrarily long sequences in bucket-sized chunks, but a
        drafter's dense cache
        has no chunked rebuild — its prefill writes one whole bucket —
        so on drafter engines only sequences fitting the largest
        bucket are preemptible (the scheduler skips other victims)."""
        n = n_prompt + max(0, n_emitted - 1)
        if self._block:
            return False    # ``_refuse_blocks``
        if self._drafter is not None or self._window:
            # ... and a window layer's ring takes a prefill from
            # position 0 only, in one chunk.
            return n <= self.prefill_buckets[-1]
        return 0 < n < self.max_seq_len

    def resume_slot(self, slot: int, prompt: Sequence[int],
                    emitted: Sequence[int], sampling: SamplingParams,
                    rng=None) -> int:
        """Re-admit a preempted generation into ``slot``: rebuild K/V
        for ``prompt + emitted[:-1]`` (prefix hit covers whatever
        survived in the cache, a prefill forward recomputes the rest —
        its sampled token is discarded, nothing already emitted is ever
        re-sampled), then bind the slot so the next ``step()`` consumes
        ``emitted[-1]`` at the position the preemption interrupted.
        Returns the prefix-hit token count.

        ``rng`` (the snapshot :meth:`preempt_slot` returned) is
        restored AFTER the recompute forward — the recompute's own
        discarded draw must not perturb the stream — and only while no
        other slot is active, mirroring ``import_slot_kv``'s contract:
        temperature resumption is then bit-identical to the
        uninterrupted run; with concurrent traffic it stays
        distributionally correct (greedy is deterministic either
        way).  One ``hvd_tpu_engine_prefill`` span (``resumed``)."""
        self._refuse_blocks("resume_slot")
        span_args = {"slot": int(slot), "resumed": True,
                     "cache": self.kv_mode}
        with trace_mod.span("hvd_tpu_engine_prefill", args=span_args):
            return self._resume_slot(slot, prompt, emitted, sampling, rng,
                                     span_args)

    def _resume_slot(self, slot: int, prompt: Sequence[int],
                     emitted: Sequence[int], sampling: SamplingParams,
                     rng, span_args: dict) -> int:
        with self._slot_lock:
            if self._active[slot]:
                raise RuntimeError(f"slot {slot} is already active")
        prompt = [int(t) for t in prompt]
        emitted = [int(t) for t in emitted]
        if not emitted:
            raise ValueError("resume_slot needs at least one emitted "
                             "token (preemption happens post-prefill)")
        self.check_prompt_tokens(prompt)
        seq = prompt + emitted[:-1]
        n = len(seq)
        span_args["prompt_len"] = n
        if self.kv_mode == "state":
            # Nothing survived the preemption: the whole sequence is
            # recomputed, the state carried from chunk to chunk, and
            # the token sampled after it is discarded.
            hit = 0
            span_args["prefix_hit"] = 0
            self._state_prefill(slot, seq, sampling, span_args)
        elif self.kv_mode == "paged":
            hit = self._begin_request(slot, seq)
            span_args["prefix_hit"] = hit
            # Recompute the non-resident tail in bucket-sized chunks:
            # the paged prefill program takes a start offset, so a
            # resumed sequence longer than the largest bucket (a long
            # generation whose cache was evicted under pressure) still
            # rebuilds — an ordinary prompt never needs this, a resume
            # must not die on it.
            top = self.prefill_buckets[-1]
            if self._window and n > top:
                raise RuntimeError(
                    f"a sequence of {n} tokens does not resume over window "
                    f"layers: their ring is prefilled from position 0 in "
                    f"one chunk, of at most {top}")
            pos = hit
            while pos < n:
                ns = min(n - pos, top)
                L = self.bucket_for(ns)
                self._ensure_writable(slot, pos, ns)
                padded = np.zeros((1, L), np.int32)
                padded[0, :ns] = np.asarray(seq[pos:pos + ns], np.int32)
                fn = self._prefill_fns[L]
                span_args["bucket"] = L     # the last chunk's
                _, self._pools, self._rng = fn(
                    self._params, self._pools,
                    self._table_rows(slot),
                    jnp.asarray(padded), jnp.int32(pos),
                    jnp.int32(ns), self._rng,
                    jnp.float32(sampling.temperature),
                    jnp.int32(sampling.top_k))
                pos += ns
            self._kv.index_prompt(slot, seq)
        else:
            hit = 0
            L = self.bucket_for(n)
            padded = np.zeros((1, L), np.int32)
            padded[0, :n] = np.asarray(seq, np.int32)
            fn = self._prefill_fns[L]
            span_args["bucket"] = L
            _, self._caches, self._rng = fn(
                self._params, self._caches, jnp.asarray(padded),
                jnp.int32(n), jnp.int32(slot), self._rng,
                jnp.float32(sampling.temperature),
                jnp.int32(sampling.top_k))
        if rng is not None and not self.active_slots():
            self._rng = self._to_device(np.asarray(rng, np.uint32))
        if self._drafter is not None:
            # Mirror start(): the drafter recomputes the sequence (its
            # dense cache shares nothing) so speculative decode can
            # draft from the resumed position immediately.
            Lf = self.bucket_for(n)
            dp = np.zeros((1, Lf), np.int32)
            dp[0, :n] = np.asarray(seq, np.int32)
            self._drafter_caches = self._draft_prefill_fns[Lf](
                self._drafter_params, self._drafter_caches,
                jnp.asarray(dp), jnp.int32(slot))
        self._bind_slot(slot, n, emitted[-1], sampling, hit)
        return hit

    # --- zero-downtime weight hot-swap (serve/swap.py; docs/hot_swap.md) ----
    # Staging runs on the subscriber thread; the COMMIT runs on the
    # batcher thread only, at the swap barrier, with no active slots —
    # so the param reference the compiled programs read never changes
    # under an in-flight generation, and a request runs start to finish
    # on exactly one version.

    @property
    def params(self):
        """The live param tree (the swap subscriber seeds its leaf
        cache from it; treat as read-only)."""
        return self._params

    @property
    def weights_version(self) -> int:
        with self._slot_lock:
            return self._weights_version

    def stage_params(self, tree, version: int) -> None:
        """Stage ``tree`` (host arrays) as version ``version`` alongside
        the live params: leaves land on the device now, so the later
        flip is one reference assignment, not a transfer.  Replaces any
        previously staged version (last writer wins — the newest intact
        step is the one worth flipping to)."""
        if self._tp_mesh is not None:
            device = self._tp_place_params(tree)
        else:
            device = jax.tree_util.tree_map(jnp.asarray, tree)
        with self._slot_lock:
            self._staged_params = device
            self._staged_version = int(version)

    def staged_version(self) -> Optional[int]:
        with self._slot_lock:
            return self._staged_version

    def discard_staged(self) -> None:
        """Drop a staged version (digest rejection / abandoned pull /
        dead flip): the live params were never touched."""
        with self._slot_lock:
            self._staged_params = None
            self._staged_version = None

    def commit_staged(self) -> int:
        """THE flip: atomically re-point the engine at the staged
        params and flush the prefix cache (resident KV was computed
        under the old weights — serving it against the new ones would
        be silently wrong).  Batcher thread only, at the swap barrier,
        with no active slots.  Returns the new version."""
        with self._slot_lock:
            if self._staged_params is None:
                raise RuntimeError("no staged params to commit")
            if np.count_nonzero(self._active):
                raise RuntimeError(
                    "commit_staged with active slots — the barrier "
                    "must drain in-flight generations first")
            params = self._staged_params
            version = int(self._staged_version)
            self._staged_params = None
            self._staged_version = None
            self._weights_version = version
        self._params = params
        if self._kv is not None:
            self._kv.flush_cache()
        from ..obs import instrument as _obs

        _obs.on_weights_version(version)
        return version

    # --- live KV migration (serve/fleet/; docs/serving.md) ------------------
    # Export/import run on the batcher thread only (they read/reassign
    # the device pools the compiled programs donate), exactly like
    # start()/step() — the fleet layer routes both through the batcher.

    def _refuse_blocks(self, what: str) -> None:
        """Preemption-resume and migration frames carry a token a row."""
        if self._block:
            raise RuntimeError(
                f"a model that generates by blocks is not preempted, "
                f"resumed or migrated yet: {what} carries a request as its "
                f"tokens so far, not a block in the middle of its denoising")

    def _refuse_frame(self, what: str) -> None:
        self._refuse_blocks(what)
        rows = {row for k in self._declared if k is not None
                for row in (k.k_row, k.v_row)}
        if self._window or len(rows) > 1 or None in self._declared:
            raise RuntimeError(
                f"a model whose layers keep different rows (window layers, "
                f"KV heads or widths by layer) has no migration frame yet: "
                f"{what} ships one K/V shape for every layer")

    def export_slot_kv(self, slot: int):
        """Export ``slot``'s resident KV as ``(chain_len, k, v)`` numpy
        arrays of shape ``[n_layer, n_blocks, block, H, D]`` — the
        slot's block table is the transfer manifest: only its live,
        non-trash chain blocks move.  Called at the prefill→decode
        boundary, when the chain covers exactly the prompt's positions
        ``[0, n_prompt)``."""
        if self.kv_mode == "state":
            raise RuntimeError(
                "a retention state has no migration frame yet: "
                "export_slot_kv ships K/V blocks")
        if self.kv_mode != "paged":
            raise RuntimeError("KV export requires the paged cache "
                               "(HVD_TPU_SERVE_KV=paged)")
        self._refuse_frame("export_slot_kv")
        chain = self._kv.chain_blocks(slot)
        if not chain:
            raise RuntimeError(f"slot {slot} has no KV chain to export")
        idx = jnp.asarray(chain, jnp.int32)
        # The wire keeps heads apart (migration ships head shards); the
        # pool stores a token's heads as one row.
        wire = (len(self._pools), len(chain), self.kv_block,
                self._model.config.kv_heads, -1)
        heads = self._kv_row        # the rest of a pool row is padding
        k = np.stack([np.asarray(p["k"][idx][..., :heads])
                      for p in self._pools]).reshape(wire)
        v = np.stack([np.asarray(p["v"][idx][..., :heads])
                      for p in self._pools]).reshape(wire)
        return len(chain), k, v

    def import_slot_kv(self, slot: int, prompt: Sequence[int],
                       k_blocks, v_blocks, first_token: int,
                       sampling: SamplingParams,
                       rng=None) -> None:
        """Bind wire-received KV blocks into this engine's pool and
        activate ``slot`` exactly as if prefill had run here: the next
        ``step()`` consumes ``first_token`` at position ``n_prompt``
        and generation continues token-identically.  ``rng`` (the
        sender's post-prefill PRNG key) is adopted only while no other
        slot is active — temperature sampling is then bit-identical to
        the single-replica run; with concurrent traffic it stays
        distributionally correct (greedy/speculative requests are
        deterministic either way).  Digest verification happens in the
        migration layer BEFORE this call — corrupt payloads never reach
        the pool.  One ``hvd_tpu_engine_prefill`` span (``imported``):
        the binding stands where a prefill would."""
        with trace_mod.span("hvd_tpu_engine_prefill",
                            args={"slot": int(slot), "imported": True,
                                  "prompt_len": len(prompt),
                                  "cache": self.kv_mode}):
            self._import_slot_kv(slot, prompt, k_blocks, v_blocks,
                                 first_token, sampling, rng)

    def _import_slot_kv(self, slot: int, prompt: Sequence[int],
                        k_blocks, v_blocks, first_token: int,
                        sampling: SamplingParams, rng) -> None:
        if self.kv_mode == "state":
            raise RuntimeError(
                "a retention state has no migration frame yet: "
                "import_slot_kv binds K/V blocks")
        if self.kv_mode != "paged":
            raise RuntimeError("KV import requires the paged cache "
                               "(HVD_TPU_SERVE_KV=paged)")
        self._refuse_frame("import_slot_kv")
        with self._slot_lock:
            if self._active[slot]:
                raise RuntimeError(f"slot {slot} is already active")
        prompt = [int(t) for t in prompt]
        n = len(prompt)
        self.check_prompt_tokens(prompt)
        nb = int(k_blocks.shape[1])
        expected = -(-n // self.kv_block)
        if nb != expected:
            raise ValueError(
                f"imported chain of {nb} block(s) does not cover the "
                f"{n}-token prompt ({expected} expected at block size "
                f"{self.kv_block})")
        chain = self._kv.bind_imported(slot, nb)
        rows = (len(self._pools), self.kv_block, -1)   # heads merged
        for j, blk in enumerate(chain):
            self._pools = self._import_fn(
                self._pools, jnp.int32(blk),
                jnp.asarray(k_blocks[:, j]).reshape(rows),
                jnp.asarray(v_blocks[:, j]).reshape(rows))
        # The imported prefix is resident here now: index it so later
        # admissions (and the global prefix directory) hit it — the
        # "prefix-directory hit landing on a decode replica" path.
        self._kv.index_prompt(slot, prompt)
        if rng is not None and not self.active_slots():
            self._rng = self._to_device(np.asarray(rng, np.uint32))
        if self._drafter is not None:
            # Mirror start(): the drafter recomputes the prompt (its
            # dense cache shares nothing) so speculative decode can
            # draft from position n_prompt immediately.
            Lf = self.bucket_for(n)
            dp = np.zeros((1, Lf), np.int32)
            dp[0, :n] = np.asarray(prompt, np.int32)
            self._drafter_caches = self._draft_prefill_fns[Lf](
                self._drafter_params, self._drafter_caches,
                jnp.asarray(dp), jnp.int32(slot))
        self._bind_slot(slot, n, int(first_token), sampling, 0)

    def export_rng(self):
        """This engine's current PRNG key as numpy (migrated with the
        KV so an idle importer can reproduce the sender's sampling
        stream bit-exactly)."""
        return np.asarray(self._rng)

    def drain_evicted_prefixes(self) -> List[tuple]:
        """Leading-block keys evicted since the last drain (piggybacked
        on response frames → global prefix directory invalidation);
        empty on the dense tier."""
        if self._kv is None:
            return []
        return self._kv.drain_evicted_keys()

    # --- observability ------------------------------------------------------

    def kv_stats(self) -> Dict:
        """JSON-ready counters of the cache this engine holds, and the
        speculative ones (merged into the batcher's snapshot and the
        serving bench artifact).  For every cache how often a step had
        to upload: ``decode_steps`` (plain decode steps),
        ``sampling_steps`` (those that held a request with a
        temperature, so that the program ranked or drew at all),
        ``step_state_uploads`` (those that sent some of the slots'
        arrays again: a bind or a clear since the last step),
        ``staged_uploads`` (arrays a prefill sent ahead of its bind,
        while the device computed), ``runtime_pokes`` (steps whose
        dispatch was slow enough to send the runtime small transfers
        behind the device's work) and, where there is a block table,
        ``table_uploads`` (times it was sent: it had changed; of them
        ``table_uploads_ahead`` behind a block step's dispatch, for the
        step after it).  Where a
        step's host time went, with tracing on: ``dispatch_ms_p50`` /
        ``_p99`` and ``fence_ms_p50`` / ``_p99`` (the decode program's
        dispatch call; the wait for its tokens, which holds the
        device's own step), read here from the ``hvd_tpu_engine_decode``
        spans that the process's span ring holds of this engine's last
        1,024 steps, and ``stalled_steps`` with ``stalled_prepare``,
        ``stalled_dispatch`` and ``stalled_fence``: steps in which that
        phase took over four times its usual length and 50 ms more
        than it (each is also a ``slow_decode_step`` event in the
        flight ring); ``stalled_prefills``: prefills whose dispatch or
        fence did, against the usual of their bucket
        (``slow_prefill``).  The
        paged pool's blocks, hits and
        evictions, and how far its decode steps walked the block table:
        ``paged_decode_steps`` (decode steps whose attention walked
        each row's table to its length; none under tensor parallelism
        or while every step is a speculative verify, which read the
        gathered view), ``paged_live_blocks`` (blocks walked, summed
        over those steps and their rows; a row without a request walks
        one) and ``paged_view_blocks`` (rows x table columns, what the
        view would have read); nothing for dense rows; for a retention
        state ``state_bytes`` (all slots and layers, whatever the context),
        ``state_slots_touched`` (slots whose state a decode step read
        and wrote, the mean over the engine's decode steps: those that
        held a request — the step's kernel copies no other row's
        state in or out; 0.0 before the first step),
        ``state_slots_skipped`` (the slots a step left alone,
        the same mean: ``max_slots`` less the former) and
        ``state_resets`` (prefills that began a slot's state from
        zeros).  For a model that generates by blocks: ``block_steps``,
        ``denoise_forwards`` and ``commit_forwards`` (a row's forwards
        of a block with masks left, and of one all clean, whose K/V
        the cache keeps), ``blocks_committed`` and ``tokens_final``
        (tokens whose block's last mask fell); ``paged_live_positions_
        full`` then counts every position up to each row's block end."""
        out: Dict = {"decode_steps": self.decode_steps,
                     "sampling_steps": self.sampling_steps,
                     "step_state_uploads": self.step_state_uploads,
                     "staged_uploads": self.staged_uploads,
                     "runtime_pokes": self.runtime_pokes,
                     "stalled_steps": sum(self.stalled_steps.values()),
                     "stalled_prefills": sum(
                         self.stalled_prefills.values())}
        for phase in _DECODE_PHASES:
            out["stalled_" + phase] = self.stalled_steps[phase]
        if self._block:
            out.update(block_steps=self.block_steps,
                       denoise_forwards=self.denoise_forwards,
                       commit_forwards=self.commit_forwards,
                       blocks_committed=self.blocks_committed,
                       tokens_final=self.tokens_final)
        recent = [s["args"] for s in trace_mod.recent(
            "hvd_tpu_engine_decode", _RECENT_STEPS, self._born_us)
                  if "dispatch_us" in s["args"]]
        for phase in ("dispatch", "fence"):
            ms = [a[phase + "_us"] / 1e3 for a in recent]
            out[phase + "_ms_p50"] = percentile(ms, 50)
            out[phase + "_ms_p99"] = percentile(ms, 99)
        if self._kv is not None:
            out.update(self._kv.stats())
            out["table_uploads"] = self.table_uploads
            if self._block:
                out["table_uploads_ahead"] = self.table_uploads_ahead
            out["paged_decode_steps"] = self.paged_decode_steps
            out["paged_live_blocks"] = self.paged_live_blocks
            out["paged_view_blocks"] = self.paged_view_blocks
            out["paged_live_rows"] = self.paged_live_rows
            # By kind of layer: positions a step's attention read and
            # blocks it found in use, each summed over the steps, and
            # bytes in use now and as a step's mean.
            pools = {"full": self._kv}
            if self._ring is not None:
                out.update(self._ring.stats())
                pools["window"] = self._ring
            steps = max(1, self.paged_decode_steps)
            for kind, pool in pools.items():
                out[f"paged_live_positions_{kind}"] = \
                    self.paged_live_positions[kind]
                out[f"kv_{kind}_block_steps"] = self.kv_block_steps[kind]
                out[f"kv_{kind}_layers"] = sum(
                    k is not None and bool(k.window) == (kind == "window")
                    for k in self._declared)
                out[f"kv_{kind}_bytes_in_use"] = (
                    pool.blocks_in_use() * pool.bytes_per_block)
                out[f"kv_{kind}_bytes_in_use_a_step"] = (
                    self.kv_block_steps[kind] / steps * pool.bytes_per_block)
        if self.expert_layers:
            out["expert_layers"] = self.expert_layers
            out["experts_held"] = (self._model.config.expert_held
                                   or (0, self._model.config.expert_count))[1]
            out["expert_pairs_held"] = self.expert_pairs
            out["experts_touched"] = self.experts_touched
        if self._states is not None:
            out["state_bytes"] = int(sum(
                x.nbytes for x in jax.tree.leaves(self._states)))
            # Every decode step of a state engine is a plain one.
            steps = self.decode_steps
            touched = self.state_rows_visited / steps if steps else 0.0
            out["state_slots_touched"] = touched
            out["state_slots_skipped"] = (
                self.max_slots - touched if steps else 0.0)
            out["state_resets"] = self.state_resets
        if self._drafter is not None:
            steps = self.spec_verify_steps
            out["spec_verify_steps"] = steps
            out["spec_accepted_tokens"] = self.spec_accepted_tokens
            out["spec_accept_per_verify"] = (
                round(self.spec_accepted_tokens / steps, 4) if steps
                else None)
        return out
