"""Flagship decoder-only transformer with pluggable parallel attention.

The reference's transformer coverage is the "BERT-Large fine-tune with
tensor fusion + fp16 Compression" baseline config (SURVEY.md §6) — a
data-parallel-only workload.  This model is designed for the full TPU
parallelism stack instead:

* ``dp``  — batch sharding (GSPMD; gradient psum implicit)
* ``tp``  — Megatron-style column/row-parallel projections via the rule
  table in ``parallel/sharding.py`` (XLA inserts the activation psums)
* ``sp``  — sequence sharding with exact ring attention or Ulysses
  all-to-all attention (``attention='ring' | 'ulysses' | 'full'``)

bfloat16 activations by default: the MXU-native dtype.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..parallel.ring_attention import full_attention, ring_self_attention
from ..parallel.ulysses import ulysses_attention


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32000
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 2048
    causal: bool = True
    attention: str = "full"            # 'full' | 'flash' | 'ring' | 'ulysses'
    attention_engine: str = "xla"      # ring per-block engine: 'xla' | 'flash'
    moe_experts: int = 0               # 0 = dense FFN; >0 = MoE with ep axis
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_every: int = 2                 # every Nth block is MoE (rest dense)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # The block's vocabulary.  The defaults are GPT-2's block and give
    # the parameter tree and the programs this model always had.
    norm: str = "layernorm"            # 'layernorm' | 'rmsnorm'
    norm_eps: float = 1e-6
    positions: str = "learned"         # 'learned': a table of max_seq_len rows | 'rope': no table | 'none': no positional signal
    rope_theta: float = 10000.0
    n_kv_head: int = 0                 # 0 = n_head: one KV head per query head
    head_dim: int = 0                  # 0 = d_model // n_head
    qk_norm: bool = False              # RMS norm of q and k per head, before positions
    # Attention layers of two kinds in one model.  ``attn`` says which a
    # layer is, one word for every layer or one per layer: 'full' (a
    # query sees every position before it) or 'window' (the last
    # ``window`` of them, itself among them).  A window layer may have
    # KV heads and a rotary base of its own (0 = the full layers') and a
    # learned sink: one float32 logit a head that joins the softmax's
    # denominator and nothing else.  Keys and queries are ``head_dim``
    # wide, values ``v_head_dim`` (0 = the same); rotary positions turn
    # the first ``rope_dim`` numbers of a head (0 = all of them) and
    # the rest pass; values are scaled by ``value_scale``.
    attn: Any = "full"
    window: int = 0
    window_kv_head: int = 0
    window_rope_theta: float = 0.0
    window_sinks: bool = False
    v_head_dim: int = 0
    rope_dim: int = 0
    value_scale: float = 1.0
    mlp: str = "gelu"                  # 'gelu' | 'swiglu' (gated SiLU) | 'relu2' (squared ReLU, not gated)
    # What mixes tokens in a layer: 'attention' (softmax over a K/V
    # cache) or 'retention' (ops/retention.py: a fixed-size state).
    # One word for every layer, or one per layer.
    mixer: Any = "attention"
    # What a layer is: 'block' (the two-part block: the mixer above,
    # then a feed-forward, on one residual) or ONE sub-layer on a
    # residual of its own, ``x + f(norm(x))``: a mixer alone
    # ('attention'; 'ssm', ops/ssm.py) or a feed-forward alone
    # ('experts', parallel/moe.py::DroplessExperts).  One word for every
    # layer, or one per layer.
    layers: Any = "block"
    # The feed-forward of a 'block' layer: 'mlp' (``mlp`` above, ``d_ff``
    # wide) or 'experts' (the dropless expert layer in the block's
    # second half).  One word for every layer, or one per layer.
    ffn: Any = "mlp"
    # 'ssm' layers (Mamba-2): heads of ssm_head_dim, B and C shared by
    # groups of heads, a state of ssm_state numbers per head channel, a
    # causal depth-wise convolution of ssm_conv taps in front.
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # 'experts' layers: the router scores expert_count experts and
    # takes expert_top_k; this chip holds the contiguous range
    # expert_held = (offset, count) of them (None = all).
    expert_count: int = 0
    expert_top_k: int = 1
    expert_d_ff: int = 0
    expert_shared_d_ff: int = 0        # 0 = no shared expert
    expert_scale: float = 1.0
    expert_held: Optional[Tuple[int, int]] = None
    expert_mlp: str = "relu2"          # an expert: 'relu2' (squared ReLU, not gated) | 'swiglu' (gated SiLU)
    expert_scoring: str = "sigmoid"    # the router's scores: 'sigmoid' of each logit | 'softmax' over all experts
    # A model that generates by diffusion over blocks: attention is
    # causal over blocks of ``block_length`` positions and full inside
    # one (a query at ``i`` sees the key at ``j`` iff ``j //
    # block_length <= i // block_length``; 0 = causal by position), in
    # the no-cache forward, a prefill chunk and a decode step alike; a
    # position still to be generated holds ``mask_token``, and the
    # logit at a position predicts that position's token.  The serving
    # engine decodes such a model a block a row (docs/serving.md "A
    # model that generates by blocks").
    block_length: int = 0
    mask_token: int = 0
    # The kinds of layer (of ``layers``) that are recomputed in the
    # backward pass instead of keeping their activations (a
    # configuration states them where a step would not fit).
    remat_layers: Tuple[str, ...] = ()
    # Tensor-parallel serving (docs/tp_serving.md): a 1-D ``tensor``
    # mesh makes one decode replica span ``tp`` chips.  Placement is
    # column-parallel only (qkv/up kernels sharded on the output dim,
    # heads sharded through attention) with an explicit all-gather
    # before every contraction (out/down/lm_head stay replicated), so
    # the sharded forward is bitwise identical to tp=1 — the property
    # the serving token-identity oracle enforces.  ``Mesh`` is hashable,
    # so the config stays a valid flax static argument.
    tp_mesh: Optional[Mesh] = None
    tp_axis: str = "tensor"

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def head_size(self) -> int:
        return self.head_dim or self.d_model // self.n_head

    @property
    def v_head_size(self) -> int:
        return self.v_head_dim or self.head_size

    def _per_layer(self, field: str, allowed) -> Tuple[str, ...]:
        value = getattr(self, field)
        kinds = ((value,) * self.n_layer if isinstance(value, str)
                 else tuple(value))
        if len(kinds) != self.n_layer or set(kinds) - set(allowed):
            raise ValueError(
                f"{field} must be one of {allowed} or {self.n_layer} of "
                f"them, got {value!r}")
        return kinds

    @property
    def attn_kinds(self) -> Tuple[str, ...]:
        """Which attention every layer has (read where it has one)."""
        kinds = self._per_layer("attn", ATTENTIONS)
        if "window" in kinds and self.window < 1:
            raise ValueError("a 'window' attention layer needs window >= 1")
        if "window" in kinds and self.block_length:
            raise ValueError(
                "a block mask (block_length) over 'window' layers is not "
                "built: a window counts positions, a block mask blocks")
        return kinds

    @property
    def ffns(self) -> Tuple[str, ...]:
        """The feed-forward of every layer (read where it is a block)."""
        return self._per_layer("ffn", FFNS)

    def kv_heads_of(self, kind: str) -> int:
        """KV heads of an attention layer of ``kind``."""
        return (self.window_kv_head if kind == "window" else 0) \
            or self.kv_heads

    @property
    def mixers(self) -> Tuple[str, ...]:
        """The mixer of every layer."""
        kinds = ((self.mixer,) * self.n_layer if isinstance(self.mixer, str)
                 else tuple(self.mixer))
        if len(kinds) != self.n_layer or set(kinds) - set(MIXERS):
            raise ValueError(
                f"mixer must be one of {MIXERS} or {self.n_layer} of them, "
                f"got {self.mixer!r}")
        return kinds

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """What every layer is."""
        kinds = ((self.layers,) * self.n_layer
                 if isinstance(self.layers, str) else tuple(self.layers))
        if len(kinds) != self.n_layer or set(kinds) - set(LAYERS):
            raise ValueError(
                f"layers must be one of {LAYERS} or {self.n_layer} of "
                f"them, got {self.layers!r}")
        return kinds


MIXERS = ("attention", "retention")
LAYERS = ("block", "attention", "ssm", "experts")
ATTENTIONS = ("full", "window")
FFNS = ("mlp", "experts")


def _refuse_serving(config: GPTConfig) -> None:
    """A model with a state-space layer is trained, not served yet."""
    if "ssm" in config.layer_kinds:
        raise NotImplementedError(
            "serving a model with 'ssm' layers is not built: a state-space "
            "layer needs a state cache beside the K/V cache in one cache "
            "manager (serve/engine.py)")


@dataclasses.dataclass(frozen=True)
class KVKind:
    """What an attention layer keeps of a token, and of how many: the
    widths of its key row and its value row (``KV heads x head width``
    each), and its causal window (0: every position so far; ``w``: the
    last ``w``, so that what the layer keeps does not grow with the
    context).  The serving engine allocates one set of pools and one
    block table for each distinct declaration."""

    k_row: int
    v_row: int
    window: int = 0


def cache_kinds(config: GPTConfig) -> Tuple[Any, ...]:
    """What each layer keeps between the tokens of a request, as the
    model declares it: a :class:`KVKind` (keys and values:
    :func:`init_kv_cache`, or the engine's paged pools — of every
    position so far, or of a window's), ``'state'`` (a fixed-size
    retention state: :func:`init_state_cache`) or None (a layer that is
    a feed-forward alone keeps nothing).  The serving engine builds its
    cache from this."""
    _refuse_serving(config)
    out = []
    for kind, mixer, attn in zip(config.layer_kinds, config.mixers,
                                 config.attn_kinds):
        if kind == "experts":
            out.append(None)
        elif "attention" in (kind, mixer):
            heads = config.kv_heads_of(attn)
            out.append(KVKind(
                heads * config.head_size, heads * config.v_head_size,
                config.window if attn == "window" else 0))
        else:
            out.append("state")
    return tuple(out)


def init_state_cache(config: GPTConfig, batch_size: int):
    """The retention state of ``batch_size`` rows, zeros: per layer one
    float32 ``{"s": [B, K, d/2 + 1, d, d], "z": [B, K, d/2 + 1, d]}``
    (``ops/retention.py`` has the layout), whatever the context length.
    In a model's ``kv_caches`` such an entry may also carry ``"valid"``
    (``[B, T]`` bool): tokens that are not valid — padding of a prefill
    bucket, rows of a decode step that hold no request — leave the state
    as it is."""
    from ..ops import retention

    s_shape, z_shape = retention.state_shapes(
        batch_size, config.kv_heads, config.head_size)
    return [{"s": jnp.zeros(s_shape, jnp.float32),
             "z": jnp.zeros(z_shape, jnp.float32)}
            for _ in range(config.n_layer)]


def init_kv_cache(config: GPTConfig, batch_size: int, max_len: int):
    """Preallocated per-layer KV cache for autoregressive decode
    (serve/engine.py): per layer one ``{"k": [B, max_len, K, D_k], "v":
    [B, max_len, K, D_v]}`` pair — ``K`` KV heads, keys ``D_k`` and
    values ``D_v`` wide, each as the configuration states it *for that
    layer* (:func:`cache_kinds`: a window layer may have other KV heads
    than a full one; the dense rows keep every position of either, and
    the window is a mask).  Allocated once per serving slot-batch so the
    decode hot path never reallocates; the engine's length buckets keep
    the set of compiled shapes small.

    The paged alternative (``horovod_tpu/serve/kv``) replaces the dense
    per-slot rows with a ``[num_blocks, block, K * D_k]`` and a
    ``[num_blocks, block, K * D_v]`` pool per layer plus a per-slot
    block table for each kind of layer; :class:`Attention` accepts
    either layout (``{"k", "v"}`` vs ``{"k_pool", "v_pool", "table"}``)."""
    out = []
    for attn in config.attn_kinds:
        heads = config.kv_heads_of(attn)
        out.append({
            "k": jnp.zeros((batch_size, max_len, heads, config.head_size),
                           config.dtype),
            "v": jnp.zeros((batch_size, max_len, heads, config.v_head_size),
                           config.dtype)})
    return out


def _tp_shard(cfg: GPTConfig, x, *spec):
    """Anchor ``x`` on the serving TP mesh (identity when unsharded).
    A bare ``_tp_shard(cfg, x)`` — empty spec — forces the all-gather
    that keeps the next contraction's input complete: the
    gather-before-contract discipline that trades wire bytes for
    bitwise identity with the tp=1 forward (docs/tp_serving.md)."""
    if cfg.tp_mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(cfg.tp_mesh, PartitionSpec(*spec)))


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` over the last axis, computed in float32."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           cfg.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + cfg.norm_eps)
        return (y * scale.astype(jnp.float32)).astype(cfg.dtype)


def _norm(cfg: GPTConfig, name: str):
    """The block's norm, under the one name either kind answers to."""
    if cfg.norm == "rmsnorm":
        return RMSNorm(cfg, name=name)
    if cfg.norm != "layernorm":
        raise ValueError(f"Unknown norm {cfg.norm!r}")
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)


def _rope(x, positions, theta: float, dim: int = 0):
    """Rotary positions on ``x [B, T, N, D]`` at absolute ``positions
    [B, T]``: the half-split convention (``x1, x2`` = the two halves of
    a head; ``x1 cos - x2 sin, x2 cos + x1 sin``), angles in float32
    from the position itself, so there is no table and no longest
    sequence.  With ``dim`` the first ``dim`` numbers of a head are
    turned (their two halves) and the rest pass."""
    if dim and dim < x.shape[-1]:
        return jnp.concatenate(
            [_rope(x[..., :dim], positions, theta), x[..., dim:]], axis=-1)
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _dense(cfg: GPTConfig, features: int, name: str):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


def _positioned(cfg: GPTConfig, q, k, positions, theta: float = 0.0):
    """``q`` and ``k`` as the mixers see them: the per-head RMS norm
    (``qk_norm``; inside a mixer's ``@nn.compact`` call, so the two
    scales are that mixer's ``q_norm`` / ``k_norm``), then rotary
    positions (``positions='rope'``) at the base ``theta`` (0: the
    configuration's ``rope_theta``)."""
    if cfg.qk_norm:
        q = RMSNorm(cfg, name="q_norm")(q)
        k = RMSNorm(cfg, name="k_norm")(k)
    if cfg.positions == "rope":
        if positions is None:
            positions = jnp.arange(q.shape[1], dtype=jnp.int32)[None]
        theta = theta or cfg.rope_theta
        q = _rope(q, positions, theta, cfg.rope_dim)
        k = _rope(k, positions, theta, cfg.rope_dim)
    return q, k


class Attention(nn.Module):
    """Self-attention.  ``attn(x)`` is the training forward.  With a KV
    cache, ``attn(x, cache=..., positions=...)`` writes the chunk's K/V
    into the cache, attends over the cache it has written and returns
    ``(out, {"k", "v"})``, the updated cache: the dense rows, or, for a
    paged cache (``{"k_pool", "v_pool", "table"}``), the pools, written
    through the block table.  ``kind`` is the layer's word of
    ``GPTConfig.attn``: a 'window' layer masks what lies ``window`` or
    more positions back, may have KV heads and a rotary base of its
    own, and a sink."""

    config: GPTConfig
    mesh: Optional[Mesh] = None
    kind: str = "full"

    @nn.compact
    def __call__(self, x, cache=None, positions=None):
        from ..ops import paged_attention

        cfg = self.config
        B, T, _ = x.shape
        windowed = self.kind == "window"
        H, K = cfg.n_head, cfg.kv_heads_of(self.kind)
        D, Dv = cfg.head_size, cfg.v_head_size
        window = cfg.window if windowed else 0
        C = H * Dv
        qkv = _dense(cfg, H * D + K * D + K * Dv, "qkv")(x)
        q, k, v = jnp.split(qkv, [H * D, (H + K) * D], axis=-1)
        # Under TP the qkv kernel is column-sharded, so q/k/v arrive
        # head-sharded; pin the layout explicitly so the paged pool
        # writes and the attention einsums stay head-local (each shard
        # computes its own H/tp heads completely — bitwise).
        q = _tp_shard(cfg, q.reshape(B, T, H, D),
                      None, None, cfg.tp_axis, None)
        k = _tp_shard(cfg, k.reshape(B, T, K, D),
                      None, None, cfg.tp_axis, None)
        v = _tp_shard(cfg, v.reshape(B, T, K, Dv),
                      None, None, cfg.tp_axis, None)
        q, k = _positioned(cfg, q, k, positions,
                           cfg.window_rope_theta if windowed else 0.0)
        if cfg.value_scale != 1.0:
            v = (v.astype(jnp.float32) * cfg.value_scale).astype(v.dtype)
        sink = (self.param("sink", nn.initializers.zeros, (H,), jnp.float32)
                if windowed and cfg.window_sinks else None)
        # What the defaults never had: a window, a sink, values of
        # another width than the keys.  Those layers have one
        # arithmetic, ``ops/paged_attention.py::view_attention``.
        special = bool(window) or sink is not None or Dv != D
        # A block mask (``block_length``) is that arithmetic's too.
        blocks = cfg.block_length
        if blocks and (cfg.attention != "full" or not cfg.causal):
            raise ValueError(
                f"a block mask (block_length={blocks}) is causal "
                f"attention='full', not {cfg.attention!r}: the flash, ring "
                f"and Ulysses paths mask by position")
        special = special or bool(blocks)
        proj = _dense(cfg, cfg.d_model, "out")

        if cache is not None:
            # KV-cache path (serving prefill chunks and single-token
            # decode steps): write this chunk's K/V at its absolute
            # ``positions`` (``[B, T]``; continuous batching puts every
            # slot at a different depth), attend over the cache just
            # written, and return it.  Keys beyond a row's position are
            # stale or padding and the ``<= position`` mask excludes
            # them: padding and intra-chunk causality need no other.
            #
            # Dense ``{"k", "v"}``: per-slot ``[B, S, K, D]`` rows.
            # Paged ``{"k_pool", "v_pool", "table"}``: per layer a pool
            # of key rows and one of value rows, ``[num_blocks, block,
            # row]``, a token's ``K * D_k`` (``K * D_v``) numbers first
            # in its row (the engine pads the row to whole vectors of
            # 128 lanes), written through the per-row block table of
            # the layer's kind: the block of positions ``[i * block, (i
            # + 1) * block)`` is column ``i mod ring`` of the row,
            # ``ring`` the table's width less its last column — ``i``
            # itself in a full layer's table, which is as wide as the
            # longest request; a window layer's is a ring of ``window /
            # block + 1`` blocks.  Invalid positions (the engine hands
            # out the last of a full table's view; a ring's cache names
            # the ``limit`` from which they are so) reach the trash
            # block by the table's last column.  Write before read, and heads in one row:
            # each keeps a donated pool updated in place
            # (docs/serving.md).  What reads the pool is chosen by the
            # chunk's shape.  One token a row (a decode step): the
            # table is walked from each row's start to its length, in
            # one kernel on the TPU
            # (``ops/paged_attention.py::paged_decode``); under tensor
            # parallelism the step stays on the view
            # (docs/tp_serving.md).  A chunk of several tokens
            # (prefill buckets, a prefix hit's suffix, speculative
            # verify): the gathered view — view row ``i`` is the token
            # at position ``i`` of the row's chain — which for them is
            # compute-shaped; where the cache says the chunk begins
            # its rows (``"fresh"``: nothing of them is in the cache
            # yet, which is all a ring is asked for), the chunk's own
            # keys and values.
            # A model that generates by blocks brings a block a row to
            # a decode step (the cache says so: ``"block_step"``): its
            # ``block_length`` queries see one range, everything up to
            # their block's end, so once the block's K/V is written
            # they ride the same kernel as ``block_length x H`` query
            # heads over the ``K`` KV heads, the row's length at the
            # block's end (``ops/paged_attention.py::fold_block``).
            paged = "k_pool" in cache
            block_step = paged and cache.get("block_step", False)
            fresh = (paged and cache.get("fresh", False) and T > 1
                     and not block_step)
            if paged:
                table = cache["table"]           # [B, n_cols] block ids
                k_pool, v_pool = cache["k_pool"], cache["v_pool"]
                block = k_pool.shape[1]
                index = positions // block
                if window:
                    # A ring.  What the engine hands out as invalid (at
                    # or past ``limit``) goes to the trash column, and
                    # so does what a chunk longer than the ring would
                    # overwrite again: only the newest ``ring`` blocks
                    # of a row reach the pool.
                    ring = table.shape[1] - 1
                    invalid = positions >= cache["limit"]
                    newest = jnp.max(jnp.where(invalid, -1, index), axis=1,
                                     keepdims=True)
                    invalid |= index <= newest - ring
                    index = jnp.where(invalid, ring, index % ring)
                blk = jnp.take_along_axis(table, index, axis=1)
                off = positions % block

                def rows(x, pool):
                    x = x.reshape(B, T, -1).astype(pool.dtype)
                    pad = pool.shape[2] - x.shape[2]
                    return x if not pad else jnp.pad(
                        x, ((0, 0), (0, 0), (0, pad)))

                k_new = k_pool.at[blk, off].set(rows(k, k_pool))
                v_new = v_pool.at[blk, off].set(rows(v, v_pool))
            else:
                at = jnp.arange(B)[:, None]
                k_new = cache["k"].at[at, positions].set(
                    k.astype(cache["k"].dtype))
                v_new = cache["v"].at[at, positions].set(
                    v.astype(cache["v"].dtype))
            if paged and (T == 1 or block_step) and cfg.tp_mesh is None:
                # The scope holds the attention alone; the projections
                # are the block's, outside it.  A model of one kind of
                # layer keeps the one name; one of two says which.
                scope = "hvd_tpu_paged_attention" + (
                    "" if len(set(cfg.attn_kinds)) == 1 else "_" + self.kind)
                with jax.named_scope(scope):
                    # One token a row goes in as it always did; only a
                    # block step folds its queries.
                    out = paged_attention.paged_decode(
                        paged_attention.fold_block(q, K) if block_step
                        else q[:, 0], k_new, v_new, table, positions[:, -1],
                        K, **(dict(v_head_dim=Dv, window=window, sink=sink)
                              if window or sink is not None or Dv != D
                              else {}))
                    out = (paged_attention.unfold_block(out, T, K)
                           if block_step else out[:, None])
            elif fresh:
                out = paged_attention.view_attention(
                    q, k.astype(k_pool.dtype), v.astype(v_pool.dtype),
                    positions, key_positions=positions, window=window,
                    sink=sink, block=blocks)
            else:
                if paged and window:
                    raise NotImplementedError(
                        "a chunk of several tokens that continues a row "
                        "of a window layer's ring is not built: the "
                        "engine prefills such a model from position 0")
                k_all, v_all = ((paged_attention.gathered_view(
                    x, table, K, d) for x, d in ((k_new, D), (v_new, Dv)))
                    if paged else (k_new, v_new))
                out = paged_attention.view_attention(
                    q, k_all, v_all, positions, window=window, sink=sink,
                    block=blocks)
            # Gather-before-contract: the ``out`` kernel is replicated
            # under TP, so the head outputs all-gather here and every
            # shard computes the full projection — bitwise identical.
            merged = _tp_shard(cfg, out.reshape(B, T, C))
            return proj(merged), {"k": k_new, "v": v_new}
        if special:
            if cfg.attention != "full" or not cfg.causal:
                raise ValueError(
                    f"a layer with a window, a sink or values of another "
                    f"width than its keys is causal attention='full', not "
                    f"{cfg.attention!r}")
            at = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
            out = paged_attention.view_attention(q, k, v, at, window=window,
                                                 sink=sink, block=blocks)
            return proj(_tp_shard(cfg, out.reshape(B, T, C)))
        if K != H:
            # Grouped KV heads: each is read by H / K query heads.
            k = jnp.repeat(k, H // K, axis=2)
            v = jnp.repeat(v, H // K, axis=2)
        if cfg.attention == "ring":
            if self.mesh is None:
                raise ValueError("attention='ring' requires a mesh")
            out = ring_self_attention(q, k, v, mesh=self.mesh,
                                      causal=cfg.causal,
                                      engine=cfg.attention_engine)
        elif cfg.attention == "ulysses":
            if self.mesh is None:
                raise ValueError("attention='ulysses' requires a mesh")
            out = ulysses_attention(q, k, v, mesh=self.mesh,
                                    causal=cfg.causal)
        elif cfg.attention == "flash":
            from ..ops import pallas_attention

            if cfg.causal:
                # Handles any T by padding up to a multiple of 128.
                out = pallas_attention.flash_attention_padded(q, k, v)
            else:
                if T % min(128, T):
                    # T < 128 runs as a single clamped block; larger T
                    # must be a multiple of 128.  Non-causal padding
                    # would need key masking in the kernel, so fail with
                    # guidance instead of a shape error deep inside the
                    # wrapper.
                    raise ValueError(
                        f"attention='flash' with causal=False requires the "
                        f"sequence length ({T}) to be a multiple of 128; "
                        f"pad the batch or use attention='full'")
                out = pallas_attention.flash_attention(q, k, v, causal=False)
        elif cfg.attention == "full":
            out = full_attention(q, k, v, causal=cfg.causal)
        else:
            raise ValueError(f"Unknown attention {cfg.attention!r}")
        return proj(_tp_shard(cfg, out.reshape(B, T, C)))


class Retention(nn.Module):
    """Power retention in the place of attention (``ops/retention.py``
    has the operator and its three forms).  Per layer: ``q`` (H heads),
    ``k``, ``v`` (K heads, each read by H / K query heads), a gate
    ``log g = log_sigmoid(gate(x))`` per KV head and token, in float32;
    q and k go through the configuration's per-head norm and positions
    as attention's do.  ``retn(x)`` is the training-shaped forward (the
    chunked form from a zero state).  With a state,
    ``retn(x, cache={"s", "z"[, "valid"]}, positions=...)`` continues
    from it — a chunk of a prompt (chunked form) or one token a row
    (recurrent form) — and returns ``(out, {"s", "z"})``: the state
    after the chunk, the same size whatever came before."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x, cache=None, positions=None):
        from ..ops import retention

        cfg = self.config
        B, T, _ = x.shape
        H, K, D = cfg.n_head, cfg.kv_heads, cfg.head_size
        q = _dense(cfg, H * D, "q")(x).reshape(B, T, H, D)
        k = _dense(cfg, K * D, "k")(x).reshape(B, T, K, D)
        v = _dense(cfg, K * D, "v")(x).reshape(B, T, K, D)
        log_g = jax.nn.log_sigmoid(nn.Dense(
            K, dtype=jnp.float32, param_dtype=cfg.param_dtype,
            bias_init=_gate_bias, name="gate")(x))
        q, k = _positioned(cfg, q, k, positions)
        proj = _dense(cfg, cfg.d_model, "out")
        if cache is None:
            out, _ = retention.retention_chunked(q, k, v, log_g)
            return proj(out.astype(cfg.dtype).reshape(B, T, H * D))
        state, valid = (cache["s"], cache["z"]), cache.get("valid")
        # The scopes hold the operator alone; the projections are the
        # block's, outside them.
        if T == 1:
            with jax.named_scope("hvd_tpu_retention_decode"):
                out, state = retention.retention_step(
                    q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], state,
                    None if valid is None else valid[:, 0])
        else:
            with jax.named_scope("hvd_tpu_retention_prefill"):
                out, state = retention.retention_chunked(
                    q, k, v, log_g, state, valid)
        out = proj(out.astype(cfg.dtype).reshape(B, T, H * D))
        return out, {"s": state[0], "z": state[1]}


def _gate_bias(key, shape, dtype=jnp.float32):
    """The gate's bias at initialisation: memories from about eight
    tokens (sigmoid(2)) to about four hundred (sigmoid(6)), one per KV
    head."""
    del key
    return jnp.linspace(2.0, 6.0, shape[0]).astype(dtype)


def _dt_bias(key, shape, dtype=jnp.float32):
    """Mamba-2's own start for the step size: ``softplus(bias)`` is
    log-uniform over [0.001, 0.1]."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape) * (hi - lo)
                             + lo), 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log(key, shape, dtype=jnp.float32):
    """``A = -exp(A_log)`` starts uniform over [-16, -1]."""
    return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                      maxval=16.0)).astype(dtype)


def _conv_taps(key, shape, dtype=jnp.float32):
    """Uniform over [-1/sqrt(taps), 1/sqrt(taps)], a depth-wise
    convolution's usual start."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Mamba2(nn.Module):
    """A Mamba-2 mixer (``ops/ssm.py`` has the scan and its two forms).
    ``in_proj`` gives a gate ``z``, the scan's inputs ``x, B, C`` in one
    stretch and a step size ``dt`` a head; the stretch goes through a
    causal depth-wise convolution of ``ssm_conv`` taps (with a bias) and
    SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
    scan's result times ``silu(z)`` goes through an RMS norm over each
    of the ``ssm_groups`` groups of channels (one learned scale) and
    ``out_proj``.  ``mamba(x)`` is the training forward, from a zero
    state."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        from ..ops import ssm

        cfg = self.config
        B, T, _ = x.shape
        H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
        inner, taps = H * P, cfg.ssm_conv
        z, xbc, dt = jnp.split(
            _dense(cfg, 2 * inner + 2 * G * N + H, "in_proj")(x),
            [inner, 2 * inner + 2 * G * N], axis=-1)
        # Tap k of the convolution reads the token taps - 1 - k back.
        conv_w = self.param(
            "conv_kernel", _conv_taps,
            (taps, xbc.shape[-1]), cfg.param_dtype).astype(jnp.float32)
        conv_b = self.param("conv_bias", nn.initializers.zeros,
                            (xbc.shape[-1],), cfg.param_dtype)
        padded = jnp.pad(xbc.astype(jnp.float32),
                         ((0, 0), (taps - 1, 0), (0, 0)))
        xbc = nn.silu(sum(padded[:, k:k + T] * conv_w[k]
                          for k in range(taps))
                      + conv_b.astype(jnp.float32)).astype(cfg.dtype)
        xs, Bm, Cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        dt = jax.nn.softplus(
            dt.astype(jnp.float32)
            + self.param("dt_bias", _dt_bias, (H,), jnp.float32))
        A = -jnp.exp(self.param("A_log", _a_log, (H,), jnp.float32))
        D = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        # The scope holds the scan alone, forward and transpose; the
        # projections, the convolution and the norm are outside it.
        with jax.named_scope("hvd_tpu_ssm_scan"):
            y, _ = ssm.ssm_chunked(
                xs.reshape(B, T, H, P), dt, A, Bm.reshape(B, T, G, N),
                Cm.reshape(B, T, G, N), D, chunk=cfg.ssm_chunk)
        y = (y.reshape(B, T, inner) * nn.silu(z.astype(jnp.float32))
             ).reshape(B, T, G, inner // G)
        y = y * jax.lax.rsqrt(
            jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.norm_eps)
        scale = self.param("norm_scale", nn.initializers.ones, (inner,),
                           cfg.param_dtype)
        y = (y.reshape(B, T, inner) * scale.astype(jnp.float32)
             ).astype(cfg.dtype)
        return _dense(cfg, cfg.d_model, "out_proj")(y)


class MlpBlock(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if cfg.mlp == "swiglu":
            # Gated SiLU: down(silu(gate(x)) * up(x)).  Column-parallel
            # placement is the plain MLP's; this branch is not TP-placed
            # yet (plan.tp_param_spec knows no ``gate``).
            h = nn.silu(_dense(cfg, cfg.d_ff, "gate")(x)) \
                * _dense(cfg, cfg.d_ff, "up")(x)
            return _dense(cfg, cfg.d_model, "down")(h)
        if cfg.mlp == "relu2":
            # Squared ReLU, not gated: down(relu(up(x)) ** 2).
            h = jnp.square(nn.relu(_dense(cfg, cfg.d_ff, "up")(x)))
            return _dense(cfg, cfg.d_model, "down")(h)
        if cfg.mlp != "gelu":
            raise ValueError(f"Unknown mlp {cfg.mlp!r}")
        x = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="up")(x)
        # Column-parallel ``up`` leaves the d_ff activation sharded;
        # gelu is elementwise so the shard survives it, then the
        # all-gather lands before the replicated ``down`` contraction
        # (gather-before-contract: bitwise identical to tp=1).
        x = _tp_shard(cfg, nn.gelu(x), None, None, cfg.tp_axis)
        x = _tp_shard(cfg, x)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="down")(x)


def _experts(cfg: GPTConfig):
    from ..parallel.moe import DroplessExperts

    return DroplessExperts(
        d_model=cfg.d_model, d_ff=cfg.expert_d_ff,
        n_experts=cfg.expert_count, top_k=cfg.expert_top_k,
        shared_d_ff=cfg.expert_shared_d_ff, scale=cfg.expert_scale,
        held=cfg.expert_held, gated=cfg.expert_mlp == "swiglu",
        scoring=cfg.expert_scoring,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="experts")


class Block(nn.Module):
    config: GPTConfig
    mesh: Optional[Mesh] = None
    use_moe: bool = False
    mixer: str = "attention"
    kind: str = "block"
    attn: str = "full"
    ffn: str = "mlp"

    @nn.compact
    def __call__(self, x, cache=None, positions=None):
        cfg = self.config
        if self.kind in ("ssm", "experts"):
            # One sub-layer on its own residual, and nothing to cache.
            part = (Mamba2(cfg, name="ssm") if self.kind == "ssm"
                    else _experts(cfg))
            x = x + part(_norm(cfg, "ln")(x))
            return x if cache is None else (x, None)
        alone = self.kind == "attention"
        attn_in = _norm(cfg, "ln" if alone else "ln1")(x)
        if self.mixer == "retention" and not alone:
            attn = Retention(cfg, name="retn")
        else:
            attn = Attention(cfg, self.mesh, self.attn, name="attn")
        new_cache = None
        if cache is not None:
            a, new_cache = attn(attn_in, cache=cache, positions=positions)
        else:
            a = attn(attn_in, positions=positions)
        x = x + a
        if alone:
            return x if cache is None else (x, new_cache)
        if self.use_moe:
            from ..parallel.moe import MoEMlp

            ffn = MoEMlp(d_model=cfg.d_model, d_ff=cfg.d_ff,
                         n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                         capacity_factor=cfg.moe_capacity_factor,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="moe")
        elif self.ffn == "experts":
            ffn = _experts(cfg)
        else:
            ffn = MlpBlock(cfg, name="mlp")
        x = x + ffn(_norm(cfg, "ln2")(x))
        if cache is not None:
            return x, new_cache
        return x


class GPT(nn.Module):
    """Decoder-only LM.  ``apply(params, tokens)`` → logits ``[B, T, V]``.

    Serving mode: ``apply(params, tokens, kv_caches=caches,
    positions=pos)`` (caches from :func:`init_kv_cache` or the engine's
    paged pools, ``pos`` the ``[B, T]`` absolute positions of the
    chunk) returns ``(logits, new_caches)`` — the jitted prefill/decode
    primitive behind ``horovod_tpu.serve.engine``.  Either layout,
    ``new_caches`` is the cache the model updated: one ``{"k", "v"}``
    per layer, dense rows or whole pools — or, for a retention layer
    (:func:`cache_kinds`), its ``{"s", "z"}`` state.  ``logit_rows``
    (``[B]``) asks for the logits of one position a row, ``[B, 1, V]``:
    a prefill needs the last real token's, not a bucket's worth of a
    large vocabulary."""

    config: GPTConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False,
                 kv_caches=None, positions=None, logit_rows=None):
        cfg = self.config
        B, T = tokens.shape
        mixers, kinds = cfg.mixers, cfg.layer_kinds
        attns, ffns = cfg.attn_kinds, cfg.ffns
        if kv_caches is not None:
            _refuse_serving(cfg)
            if cfg.attention in ("ring", "ulysses"):
                # Sequence-sharded training layouts have no KV-cache
                # analogue; decode is a per-replica workload.
                raise ValueError(
                    f"KV-cache decode requires attention='full' or "
                    f"'flash', not {cfg.attention!r}")
            if positions is None:
                raise ValueError("kv_caches requires positions ([B, T] "
                                 "absolute token positions)")
        tok_emb = nn.Embed(cfg.vocab_size, cfg.d_model,
                           param_dtype=cfg.param_dtype,
                           dtype=cfg.dtype, name="embed")(tokens)
        if cfg.positions == "learned":
            pos_emb = self.param(
                "pos_embed", nn.initializers.normal(0.02),
                (cfg.max_seq_len, cfg.d_model), cfg.param_dtype,
            )
            if kv_caches is not None:
                x = tok_emb + pos_emb[positions].astype(cfg.dtype)
            else:
                x = tok_emb + pos_emb[None, :T].astype(cfg.dtype)
        elif cfg.positions in ("rope", "none"):
            # 'rope': the mixers rotate q and k; 'none': the order of the
            # tokens reaches the model through its causal layers alone.
            # Neither has a table.
            x = tok_emb
        else:
            raise ValueError(f"Unknown positions {cfg.positions!r}")
        new_caches = []
        for i in range(cfg.n_layer):
            use_moe = (cfg.moe_experts > 0
                       and (i + 1) % max(1, cfg.moe_every) == 0)
            block = (nn.remat(Block) if kinds[i] in cfg.remat_layers
                     else Block)(
                cfg, self.mesh, use_moe=use_moe, mixer=mixers[i],
                kind=kinds[i], attn=attns[i], ffn=ffns[i],
                name=f"block_{i}")
            if kv_caches is not None:
                x, c = block(x, cache=kv_caches[i], positions=positions)
                new_caches.append(c)
            else:
                x = block(x)
        if logit_rows is not None:
            x = jnp.take_along_axis(x, logit_rows[:, None, None], axis=1)
        x = _norm(cfg, "ln_f")(x)
        if return_hidden:
            # Pre-head activations for the chunked-vocab loss
            # (ops/xent.py) — the lm_head matmul happens inside the
            # chunk loop there instead of materializing [B, T, V] here
            # — and, with ``kv_caches``, for a prefill that samples no
            # token and so needs no logits.
            return x if kv_caches is None else (x, new_caches)
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, name="lm_head")
        if cfg.block_length and kv_caches is not None:
            # A block step's head belongs to what turns a block's
            # logits into its next state (serve/engine.py::_transfer).
            with jax.named_scope("hvd_tpu_block_transfer"):
                logits = head(x)
        else:
            logits = head(x)
        if kv_caches is not None:
            return logits, new_caches
        return logits


def lm_loss_fn(model: GPT, *, vocab_chunk_size: int = 0):
    """Next-token cross-entropy: ``loss_fn(params, (inputs, targets))``
    with both ``[B, T]`` (pre-shifted by the data pipeline, so ``T`` stays
    divisible by the ``sp`` axis under sequence sharding).

    ``vocab_chunk_size > 0`` switches to the memory-efficient chunked
    head (``ops/xent.py``): the ``[B, T, V]`` logits tensor is never
    materialized — the head matmul + softmax run per token-chunk under
    remat.  Numerically equal to the dense path at float32 tolerance.
    """
    if vocab_chunk_size:
        from ..ops.xent import chunked_lm_xent

        def loss_fn(params, batch):
            inputs, targets = batch
            hidden = model.apply({"params": params}, inputs,
                                 return_hidden=True)
            return chunked_lm_xent(hidden, params["lm_head"]["kernel"],
                                   targets, chunk_size=vocab_chunk_size)

        return loss_fn

    def loss_fn(params, batch):
        inputs, targets = batch
        logits = model.apply({"params": params}, inputs)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll)

    return loss_fn
