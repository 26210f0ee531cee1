"""BERT encoder family — the reference's transformer parity config.

The driver's BASELINE.json names "BERT-Large fine-tune with tensor
fusion + fp16 Compression" as one of the six reference configs
(SURVEY.md §6; upstream horovod exercises BERT via its synthetic
benchmark scripts and the Horovod paper's BERT rows).  The reference
treats BERT as a user model over its DP allreduce; here the model itself
is in-tree so the config is runnable end to end:
``benchmarks/bert_finetune_bench.py`` fine-tunes this model under
``hvd.DistributedOptimizer`` with tensor fusion + ``Compression.fp16``.

TPU-first notes:

* bfloat16 activations (MXU-native), float32 params/softmax/LayerNorm —
  no loss-scale dance needed, unlike the reference's fp16 AMP path.
* Post-LN residuals, learned position + segment embeddings, GELU —
  faithful BERT architecture (Devlin et al.), so checkpoints map 1:1.
* The attention core reuses ``parallel/ring_attention.full_attention``
  with a key-padding mask; with no mask, ``attention='flash'`` routes
  through the Pallas kernel.
* MLM decoder weights are tied to the token embedding (``Embed.attend``)
  as in the original — halves the largest gradient the DP allreduce
  carries.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.ring_attention import full_attention


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522            # WordPiece, uncased
    n_layer: int = 24                  # BERT-Large defaults
    n_head: int = 16
    d_model: int = 1024
    d_ff: int = 4096
    max_seq_len: int = 512
    type_vocab_size: int = 2
    attention: str = "full"            # 'full' | 'flash' (flash: no padding mask)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def large(**kw) -> "BertConfig":
        return BertConfig(**kw)

    @staticmethod
    def base(**kw) -> "BertConfig":
        kw.setdefault("n_layer", 12)
        kw.setdefault("n_head", 12)
        kw.setdefault("d_model", 768)
        kw.setdefault("d_ff", 3072)
        return BertConfig(**kw)


class BertSelfAttention(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x, key_mask):
        cfg = self.config
        B, T, C = x.shape
        H, D = cfg.n_head, C // cfg.n_head
        qkv = nn.Dense(3 * C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.reshape(B, T, H, D) for t in (q, k, v))
        if cfg.attention == "flash" and key_mask is None:
            from ..ops import pallas_attention

            # Kernel rule (see ops/pallas_attention): T < 128 runs as a
            # single clamped block; larger T must be a multiple of 128.
            out = pallas_attention.flash_attention(q, k, v, causal=False) \
                if T % min(128, T) == 0 else \
                full_attention(q, k, v, causal=False)
        else:
            out = full_attention(q, k, v, causal=False, key_mask=key_mask)
        out = out.reshape(B, T, C)
        return nn.Dense(C, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        name="out")(out)


class BertBlock(nn.Module):
    """Post-LN encoder block (original BERT residual order)."""

    config: BertConfig

    @nn.compact
    def __call__(self, x, key_mask):
        cfg = self.config
        attn = BertSelfAttention(cfg, name="attn")(x, key_mask)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x + attn)
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="ffn_up")(x)
        h = nn.gelu(h)
        h = nn.Dense(cfg.d_model, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="ffn_down")(h)
        return nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x + h)


class BertEncoder(nn.Module):
    """Embeddings + N post-LN blocks.  Returns ``(sequence, pooled)``.

    ``attention_mask`` is ``[B, T]`` with 1 for real tokens (HuggingFace
    convention); ``None`` = all real.  setup-style so heads can reach
    ``self.tok_embed`` for weight tying.
    """

    config: BertConfig

    def setup(self):
        cfg = self.config
        self.tok_embed = nn.Embed(cfg.vocab_size, cfg.d_model,
                                  param_dtype=cfg.param_dtype,
                                  dtype=cfg.dtype, name="tok_embed")
        self.seg_embed = nn.Embed(cfg.type_vocab_size, cfg.d_model,
                                  param_dtype=cfg.param_dtype,
                                  dtype=cfg.dtype, name="seg_embed")
        self.pos_embed = self.param("pos_embed",
                                    nn.initializers.normal(0.02),
                                    (cfg.max_seq_len, cfg.d_model),
                                    cfg.param_dtype)
        self.ln_embed = nn.LayerNorm(dtype=cfg.dtype, name="ln_embed")
        self.blocks = [BertBlock(cfg, name=f"block_{i}")
                       for i in range(cfg.n_layer)]
        self.pooler = nn.Dense(cfg.d_model, dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype, name="pooler")

    def __call__(self, input_ids, token_type_ids=None, attention_mask=None):
        cfg = self.config
        T = input_ids.shape[1]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = (self.tok_embed(input_ids)
             + self.pos_embed[None, :T].astype(cfg.dtype)
             + self.seg_embed(token_type_ids))
        x = self.ln_embed(x)
        key_mask = None if attention_mask is None else attention_mask > 0
        for block in self.blocks:
            x = block(x, key_mask)
        pooled = nn.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForSequenceClassification(nn.Module):
    """The fine-tune head of the baseline config (GLUE-style)."""

    config: BertConfig
    num_classes: int = 2

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = BertEncoder(self.config, name="bert")(
            input_ids, token_type_ids, attention_mask)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        param_dtype=self.config.param_dtype,
                        name="classifier")(pooled)


class BertForMaskedLM(nn.Module):
    """Pre-training head; decoder tied to the token embedding."""

    config: BertConfig

    def setup(self):
        cfg = self.config
        self.bert = BertEncoder(cfg, name="bert")
        self.mlm_transform = nn.Dense(cfg.d_model, dtype=cfg.dtype,
                                      param_dtype=cfg.param_dtype,
                                      name="mlm_transform")
        self.mlm_ln = nn.LayerNorm(dtype=cfg.dtype, name="mlm_ln")
        self.mlm_bias = self.param("mlm_bias", nn.initializers.zeros,
                                   (cfg.vocab_size,), jnp.float32)

    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 return_hidden: bool = False):
        seq, _ = self.bert(input_ids, token_type_ids, attention_mask)
        h = self.mlm_ln(nn.gelu(self.mlm_transform(seq)))
        if return_hidden:
            # Pre-decoder activations for the chunked-vocab loss — the
            # tied-decoder matmul happens inside ops/xent.py's chunk
            # loop instead of materializing [B, T, V] here.
            return h
        logits = self.bert.tok_embed.attend(h).astype(jnp.float32)
        return logits + self.mlm_bias


def masked_lm_loss_fn(model: BertForMaskedLM, *, vocab_chunk_size: int = 0):
    """MLM pre-training loss.

    Batch is ``(input_ids, labels, label_mask)`` or — for padded
    batches — ``(input_ids, attention_mask, labels, label_mask)``
    (attention_mask per the HuggingFace convention, like
    :func:`classification_loss_fn`).  Cross-entropy over positions with
    ``label_mask=1`` (the 15% masked tokens), mean over masked
    positions.

    ``vocab_chunk_size > 0`` routes through the chunked-vocab head
    (``ops/xent.py``): the tied decoder is the token embedding, so the
    ``[B, T, V]`` MLM logits — the largest tensor of BERT pre-training —
    are never materialized.
    """

    def unpack(batch):
        if len(batch) == 4:
            input_ids, attention_mask, labels, label_mask = batch
        else:
            input_ids, labels, label_mask = batch
            attention_mask = None
        return input_ids, attention_mask, labels, label_mask

    def dense_loss(params, batch):
        input_ids, attention_mask, labels, label_mask = unpack(batch)
        logits = model.apply({"params": params}, input_ids, None,
                             attention_mask)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        m = label_mask.astype(jnp.float32)
        return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)

    if not vocab_chunk_size:
        return dense_loss

    from ..ops.xent import chunked_lm_xent

    def chunked_loss(params, batch):
        input_ids, attention_mask, labels, label_mask = unpack(batch)
        h = model.apply({"params": params}, input_ids, None,
                        attention_mask, return_hidden=True)
        kernel = params["bert"]["tok_embed"]["embedding"].T  # tied [D, V]
        return chunked_lm_xent(h, kernel, labels,
                               chunk_size=vocab_chunk_size,
                               bias=params["mlm_bias"], mask=label_mask)

    return chunked_loss


def classification_loss_fn(model: BertForSequenceClassification):
    """Softmax cross-entropy for ``make_train_step``.

    Batch is ``(input_ids, labels)`` or — for real padded data —
    ``(input_ids, attention_mask, labels)`` (mask per the HuggingFace
    convention, 1 = real token).
    """

    def loss_fn(params, batch):
        if len(batch) == 3:
            input_ids, attention_mask, labels = batch
        else:
            input_ids, labels = batch
            attention_mask = None
        logits = model.apply({"params": params}, input_ids, None,
                             attention_mask)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                             axis=-1))

    return loss_fn
