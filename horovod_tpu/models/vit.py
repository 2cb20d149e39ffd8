"""Vision Transformer in flax — the vision counterpart of the BERT/Llama
transformer stack.

The reference's vision benchmarks are CNNs (ResNet/Inception/VGG,
``docs/benchmarks.md``); ViT extends the model zoo with the architecture
modern vision training actually scales — and it is a pure win on TPU: the
patch embedding is one strided conv (a single MXU matmul per patch grid) and
everything after is the same MXU-friendly einsum attention the language
models use, so the flash-attention kernel seam (``attention_fn``), remat,
and the DP/TP/FSDP shardings all apply unchanged.

TPU-first choices mirror ``bert.py``: bfloat16 activations / fp32 params,
static shapes, pre-LN blocks (ViT convention), ``jax.checkpoint`` per block
under ``remat``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

from .bert import SelfAttention
from .losses import token_nll


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    # Classification-head compute dtype; None = model dtype (see
    # LlamaConfig.head_dtype).
    head_dtype: Any = None
    # jax.checkpoint each block in the backward pass (see LlamaConfig.remat).
    remat: bool = False

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2


VIT_B16 = ViTConfig()
VIT_S16 = ViTConfig(hidden_size=384, num_heads=6, intermediate_size=1536)
VIT_TINY = ViTConfig(image_size=32, patch_size=8, num_classes=10,
                     hidden_size=64, num_layers=2, num_heads=2,
                     intermediate_size=128)


class ViTBlock(nn.Module):
    """Pre-LN transformer block (the ViT/GPT convention; BERT's blocks are
    post-LN, so this is its own module while the attention core is shared)."""

    config: Any  # ViTConfig; SelfAttention reads the shared field subset
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        h = nn.LayerNorm(dtype=cfg.dtype, param_dtype=jnp.float32)(x)
        h = SelfAttention(cfg, attention_fn=self.attention_fn)(
            h, mask=None, deterministic=deterministic)
        h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        x = x + h
        h = nn.LayerNorm(dtype=cfg.dtype, param_dtype=jnp.float32)(x)
        h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32)(h)
        h = nn.gelu(h)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32)(h)
        h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        return x + h


class VisionTransformer(nn.Module):
    """Patch embed + CLS token + pre-LN encoder + classification head."""

    config: ViTConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, images, deterministic: bool = True):
        """``images``: (B, H, W, C) floats (NHWC, the TPU conv layout)."""
        cfg = self.config
        b = images.shape[0]
        # Patch embedding as ONE strided conv: XLA lowers it to a single
        # (B*patches, p*p*C) x (p*p*C, hidden) MXU matmul.
        x = nn.Conv(cfg.hidden_size,
                    kernel_size=(cfg.patch_size, cfg.patch_size),
                    strides=(cfg.patch_size, cfg.patch_size),
                    dtype=cfg.dtype, param_dtype=jnp.float32,
                    name="patch_embed")(images.astype(cfg.dtype))
        x = x.reshape(b, -1, cfg.hidden_size)  # (B, patches, hidden)

        cls = self.param("cls_token", nn.initializers.zeros,
                         (1, 1, cfg.hidden_size), jnp.float32)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls, (b, 1, cfg.hidden_size)).astype(cfg.dtype),
             x], axis=1)
        pos = self.param("position_embeddings",
                         nn.initializers.normal(stddev=0.02),
                         (1, cfg.num_patches + 1, cfg.hidden_size),
                         jnp.float32)
        x = x + pos.astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=deterministic)

        block_cls = (nn.remat(ViTBlock, static_argnums=(2,))
                     if cfg.remat else ViTBlock)
        for i in range(cfg.num_layers):
            x = block_cls(cfg, attention_fn=self.attention_fn,
                          name=f"layer_{i}")(x, deterministic)

        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=jnp.float32,
                         name="final_norm")(x)
        logits = nn.Dense(cfg.num_classes,
                          dtype=cfg.head_dtype or cfg.dtype,
                          param_dtype=jnp.float32, name="head")(x[:, 0])
        return logits


def classification_loss(logits, labels):
    """Mean cross entropy over the batch, lse-formulated (no (B, C) f32
    log-softmax materialization — ``losses.token_nll``)."""
    return token_nll(logits, labels).mean()
