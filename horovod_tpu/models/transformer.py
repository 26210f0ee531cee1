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
from typing import Any, Optional

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


def init_kv_cache(config: GPTConfig, batch_size: int, max_len: int):
    """Preallocated per-layer KV cache for autoregressive decode
    (serve/engine.py): one ``{"k", "v"}`` pair of ``[B, max_len, H, D]``
    arrays per block.  Allocated once per serving slot-batch so the
    decode hot path never reallocates; the engine's length buckets keep
    the set of compiled shapes small.

    The paged alternative (``horovod_tpu/serve/kv``) replaces the dense
    per-slot rows with one ``[num_blocks, block, H * D]`` pool per
    layer plus a per-slot block table; :class:`Attention` accepts either
    layout (``{"k", "v"}`` vs ``{"k_pool", "v_pool", "table"}``)."""
    head_dim = config.d_model // config.n_head
    shape = (batch_size, max_len, config.n_head, head_dim)
    return [{"k": jnp.zeros(shape, config.dtype),
             "v": jnp.zeros(shape, config.dtype)}
            for _ in range(config.n_layer)]


_NEG_INF = -1e30  # additive mask value (matches parallel/ring_attention)


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


class Attention(nn.Module):
    """Self-attention.  ``attn(x)`` is the training forward.  With a KV
    cache, ``attn(x, cache=..., positions=...)`` writes the chunk's K/V
    into the cache, attends over the cache it has written and returns
    ``(out, {"k", "v"})``, the updated cache: the dense rows, or, for a
    paged cache (``{"k_pool", "v_pool", "table"}``), the pools, written
    through the block table."""

    config: GPTConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, cache=None, positions=None):
        cfg = self.config
        B, T, C = x.shape
        H = cfg.n_head
        D = C // H
        qkv = nn.Dense(3 * C, use_bias=False, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        # Under TP the qkv kernel is column-sharded, so q/k/v arrive
        # head-sharded; pin the layout explicitly so the paged pool
        # writes and the attention einsums stay head-local (each shard
        # computes its own H/tp heads completely — bitwise).
        q = _tp_shard(cfg, q.reshape(B, T, H, D),
                      None, None, cfg.tp_axis, None)
        k = _tp_shard(cfg, k.reshape(B, T, H, D),
                      None, None, cfg.tp_axis, None)
        v = _tp_shard(cfg, v.reshape(B, T, H, D),
                      None, None, cfg.tp_axis, None)
        proj = nn.Dense(C, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="out")
        if cache is not None:
            # KV-cache path (serving prefill chunks and single-token
            # decode steps): write this chunk's K/V at its absolute
            # ``positions`` (``[B, T]``; continuous batching puts every
            # slot at a different depth), attend over the cache just
            # written, and return it.  Keys beyond a row's position are
            # stale or padding and the ``<= position`` mask excludes
            # them: padding and intra-chunk causality need no other.
            #
            # Dense ``{"k", "v"}``: per-slot ``[B, S, H, D]`` rows.
            # Paged ``{"k_pool", "v_pool", "table"}``: one ``[num_blocks,
            # block, H * D]`` pool per layer, written and gathered
            # through the per-row block table (view row ``i`` is the
            # token at position ``i`` of the row's chain; invalid
            # positions reach the trash block by the table's last
            # column).  Write before read, and heads in one row: each
            # keeps a donated pool updated in place (docs/serving.md).
            if "k_pool" in cache:
                table = cache["table"]           # [B, n_cols] block ids
                k_pool, v_pool = cache["k_pool"], cache["v_pool"]
                block = k_pool.shape[1]
                blk = jnp.take_along_axis(table, positions // block, axis=1)
                off = positions % block
                k_new = k_pool.at[blk, off].set(
                    k.reshape(B, T, C).astype(k_pool.dtype))
                v_new = v_pool.at[blk, off].set(
                    v.reshape(B, T, C).astype(v_pool.dtype))
                k_all = k_new[table].reshape(B, -1, H, D)
                v_all = v_new[table].reshape(B, -1, H, D)
            else:
                row = jnp.arange(B)[:, None]
                k_new = k_all = cache["k"].at[row, positions].set(
                    k.astype(cache["k"].dtype))
                v_new = v_all = cache["v"].at[row, positions].set(
                    v.astype(cache["v"].dtype))
            S = k_all.shape[1]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_all)
            scores = scores.astype(jnp.float32) * (D ** -0.5)
            visible = jnp.arange(S)[None, None, :] <= positions[:, :, None]
            scores = jnp.where(visible[:, None], scores, _NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)
            # Gather-before-contract: the ``out`` kernel is replicated
            # under TP, so the head outputs all-gather here and every
            # shard computes the full projection — bitwise identical.
            merged = _tp_shard(cfg, out.reshape(B, T, C))
            return proj(merged), {"k": k_new, "v": v_new}
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
                # Handles any T by padding up to the kernel block size.
                out = pallas_attention.flash_attention_padded(q, k, v)
            else:
                if T % min(128, T):
                    # T < 128 runs as a single clamped block; larger T
                    # must divide the 128 block.  Non-causal padding
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


class MlpBlock(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
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


class Block(nn.Module):
    config: GPTConfig
    mesh: Optional[Mesh] = None
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, cache=None, positions=None):
        cfg = self.config
        attn_in = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        attn = Attention(cfg, self.mesh, name="attn")
        new_cache = None
        if cache is not None:
            a, new_cache = attn(attn_in, cache=cache, positions=positions)
        else:
            a = attn(attn_in)
        x = x + a
        if self.use_moe:
            from ..parallel.moe import MoEMlp

            ffn = MoEMlp(d_model=cfg.d_model, d_ff=cfg.d_ff,
                         n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                         capacity_factor=cfg.moe_capacity_factor,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="moe")
        else:
            ffn = MlpBlock(cfg, name="mlp")
        x = x + ffn(nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x))
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
    per layer, dense rows or whole pools."""

    config: GPTConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False,
                 kv_caches=None, positions=None):
        cfg = self.config
        B, T = tokens.shape
        if kv_caches is not None:
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
        pos_emb = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (cfg.max_seq_len, cfg.d_model), cfg.param_dtype,
        )
        if kv_caches is not None:
            x = tok_emb + pos_emb[positions].astype(cfg.dtype)
        else:
            x = tok_emb + pos_emb[None, :T].astype(cfg.dtype)
        new_caches = []
        for i in range(cfg.n_layer):
            use_moe = (cfg.moe_experts > 0
                       and (i + 1) % max(1, cfg.moe_every) == 0)
            block = Block(cfg, self.mesh, use_moe=use_moe,
                          name=f"block_{i}")
            if kv_caches is not None:
                x, c = block(x, cache=kv_caches[i], positions=positions)
                new_caches.append(c)
            else:
                x = block(x)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        if return_hidden:
            # Pre-head activations for the chunked-vocab loss
            # (ops/xent.py) — the lm_head matmul happens inside the
            # chunk loop there instead of materializing [B, T, V] here.
            return x
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                          param_dtype=cfg.param_dtype, name="lm_head")(x)
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
