"""BERT encoder in flax — the framework's flagship language benchmark model.

The rebuild targets "≥90% scaling efficiency for ResNet-50 and BERT-base"
(BASELINE.md); the reference itself has no BERT code (2019, CNN-centric), so
this is specified by the target, not ported. TPU-first choices: bfloat16
activations / fp32 params, einsum-formulated attention (MXU-friendly, and the
seam where the Pallas flash-attention kernel and ring-attention sequence
parallelism plug in — see ``horovod_tpu.ops.attention`` /
``horovod_tpu.parallel.sequence``), static shapes throughout.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from .losses import token_nll


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16
    # mlm_head compute dtype; None = model dtype (see
    # LlamaConfig.head_dtype — set jnp.float32 for full-precision raw
    # logits).
    head_dtype: Any = None
    # jax.checkpoint each transformer block in the backward pass (see
    # LlamaConfig.remat).
    remat: bool = False


BERT_BASE = BertConfig()
BERT_LARGE = BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                        intermediate_size=4096)
BERT_TINY = BertConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                       num_heads=2, intermediate_size=128,
                       max_position_embeddings=128)


class SelfAttention(nn.Module):
    """Multi-head attention via einsum. ``attention_fn`` lets callers swap
    the core softmax(QK^T)V for a Pallas flash kernel or a ring-attention
    sequence-parallel variant without touching the module."""

    config: BertConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_heads
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            features=(cfg.num_heads, head_dim), axis=-1, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        q = dense("query")(x)
        k = dense("key")(x)
        v = dense("value")(x)

        if self.attention_fn is not None:
            ctx = self.attention_fn(q, k, v, mask)
        else:
            scale = 1.0 / np.sqrt(head_dim)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            if mask is not None:
                big_neg = jnp.finfo(jnp.float32).min
                logits = jnp.where(mask[:, None, None, :], logits, big_neg)
            probs = nn.softmax(logits.astype(jnp.float32)).astype(cfg.dtype)
            probs = nn.Dropout(cfg.dropout_rate)(
                probs, deterministic=deterministic)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)

        out = nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1),
                              dtype=cfg.dtype, param_dtype=jnp.float32,
                              name="out")(ctx)
        return out


class TransformerBlock(nn.Module):
    config: BertConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask, deterministic: bool = True):
        cfg = self.config
        attn = SelfAttention(cfg, attention_fn=self.attention_fn)(
            x, mask, deterministic)
        attn = nn.Dropout(cfg.dropout_rate)(attn, deterministic=deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=jnp.float32)(x + attn)
        h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32)(x)
        h = nn.gelu(h)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32)(h)
        h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        return nn.LayerNorm(dtype=cfg.dtype, param_dtype=jnp.float32)(x + h)


class BertEncoder(nn.Module):
    """Embeddings + transformer stack + MLM head (tied-free simple head)."""

    config: BertConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, positions=None):
        """``positions``: global token positions of the local rows, shape
        (S,) — required under sequence parallelism (each shard passes its
        global offsets so the learned position embedding indexes
        correctly); defaults to 0..S-1. The GLOBAL sequence length must
        stay within ``cfg.max_position_embeddings``: a learned position
        table cannot extrapolate, and out-of-range indices would be
        silently clamped by ``nn.Embed`` — unlike RoPE models
        (``LlamaLM``), BERT's SP context is capped by its table size."""
        cfg = self.config
        b, s = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((b, s), dtype=bool)
        else:
            attention_mask = attention_mask.astype(bool)
        if token_type_ids is None:
            token_type_ids = jnp.zeros((b, s), dtype=jnp.int32)
        if positions is None:
            positions = jnp.arange(s)

        tok = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                       param_dtype=jnp.float32, name="token_embeddings")(
                           input_ids)
        pos = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                       param_dtype=jnp.float32, name="position_embeddings")(
                           positions[None, :])
        typ = nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                       param_dtype=jnp.float32, name="type_embeddings")(
                           token_type_ids)
        x = (tok + pos + typ).astype(cfg.dtype)
        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=jnp.float32,
                         name="embed_norm")(x)
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=deterministic)

        block_cls = (nn.remat(TransformerBlock, static_argnums=(3,))
                     if cfg.remat else TransformerBlock)
        for i in range(cfg.num_layers):
            x = block_cls(cfg, attention_fn=self.attention_fn,
                          name=f"layer_{i}")(
                              x, attention_mask, deterministic)

        # Head matmul in head_dtype (default: model compute dtype; MXU
        # accumulates f32 internally); mlm_loss upcasts to f32 before the
        # softmax.
        logits = nn.Dense(cfg.vocab_size,
                          dtype=cfg.head_dtype or cfg.dtype,
                          param_dtype=jnp.float32, name="mlm_head")(x)
        return logits


def mlm_loss(logits, labels, label_mask):
    """Masked-LM cross entropy over positions where label_mask is 1.

    Uses the lse formulation (``lse(logits) - logits[label]``) so no
    (B, S, V) f32 array is materialized — see
    ``horovod_tpu.models.losses.token_nll``."""
    nll = token_nll(logits, labels)
    label_mask = label_mask.astype(jnp.float32)
    return (nll * label_mask).sum() / jnp.maximum(label_mask.sum(), 1.0)
