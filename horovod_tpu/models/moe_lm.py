"""Mixture-of-experts transformer LM — the expert-parallel model family.

No reference counterpart (the reference is 2019 CNN-era data parallelism,
SURVEY.md §2.3); this extends the Llama-style LM (``models/llama.py``) with
a Switch/GShard MoE feed-forward on every other layer, wired to the
expert-parallel substrate (``parallel/moe.py``):

- ``expert_axis=None`` (default): every expert is resident and dispatch
  runs densely under ``vmap`` (``moe_apply_dense``) — single-chip runs,
  tests, eval.
- ``expert_axis="expert"`` inside ``shard_map``: expert parameters are
  sharded one-per-device along that mesh axis and token dispatch rides
  ``all_to_all`` over ICI (``moe_apply``). The routing (and therefore the
  numerics) is identical in both modes.

One expert per device is this model's sharded mode, not the package's
only one: ``parallel.moe.moe_apply_held`` is a dropless layer that is told
which experts (several, any subset) a device holds, and
``models/smallthinker.py`` is built on it. The capacity path here keeps
its fixed ``[E, C, D]`` buffers because ``all_to_all`` needs them.

The non-MoE machinery is shared with the Llama family (the one decoder
file that imports another: this one extends ``llama.py``); the losses are
``models/losses.py``'s. Aux (load-balancing) losses from every MoE layer
are summed into the ``"aux_loss"`` collection — fold ``sum(aux) *
aux_weight`` into the objective.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.moe import moe_apply, moe_apply_dense
from .decoder import RMSNorm, linear, rematerialised, token_embedding
from .llama import LlamaBlock, LlamaConfig, attention_sublayer


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    vocab_size: int = 32000
    dim: int = 2048
    num_layers: int = 16
    num_heads: int = 32
    num_kv_heads: int = 8
    ffn_hidden: int = 5632
    num_experts: int = 8
    expert_hidden: int = 5632
    num_selected: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 2           # every moe_every-th layer gets an MoE FFN
    norm_eps: float = 1e-5
    rope_theta: float = 1e4
    dtype: Any = jnp.bfloat16
    # lm_head compute dtype; None = model dtype (see
    # LlamaConfig.head_dtype).
    head_dtype: Any = None
    # jax.checkpoint each block in the backward pass (see
    # LlamaConfig.remat).
    remat: bool = False

    def llama(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            num_layers=self.num_layers, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, ffn_hidden=self.ffn_hidden,
            norm_eps=self.norm_eps, rope_theta=self.rope_theta,
            dtype=self.dtype, head_dtype=self.head_dtype)


MOE_TINY = MoeConfig(vocab_size=512, dim=64, num_layers=2, num_heads=4,
                     num_kv_heads=2, ffn_hidden=128, num_experts=4,
                     expert_hidden=128, moe_every=2)
# 249.7M params (151M routed across 8 experts + 98.7M dense, counted from
# the init tree): a single-chip MoE benchmark config (top-2 of 8 experts,
# every other layer routed).
MOE_SMALL = MoeConfig(vocab_size=32000, dim=768, num_layers=12,
                      num_heads=12, num_kv_heads=6, ffn_hidden=2048,
                      num_experts=8, expert_hidden=2048, moe_every=2)


class MoeFFN(nn.Module):
    """Top-k routed feed-forward: gate -> dispatch -> per-expert gated MLP
    -> combine. Expert weights carry a leading expert axis: the GLOBAL
    expert count in dense mode, the LOCAL count (one per device) under
    ``shard_map`` — flax validates declared param shapes at apply time, so
    the sharded mode must declare the slice it will actually receive."""

    config: MoeConfig
    expert_axis: Optional[str] = None
    local_experts: Optional[int] = None
    # Decode mode: capacity covers the all-tokens-to-one-expert worst case
    # (cf = E/k) so no assignment is ever dropped — see MoeBlock.
    no_drop: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)

        gate_w = self.param("gate", nn.initializers.normal(0.02),
                            (d, cfg.num_experts), jnp.float32)
        # Router in float32 (Switch: routing is precision-sensitive).
        logits = tokens.astype(jnp.float32) @ gate_w

        n_param = (self.local_experts
                   if self.expert_axis is not None and self.local_experts
                   else cfg.num_experts)
        experts = {
            "wi": self.param(
                "wi", nn.initializers.lecun_normal(),
                (n_param, d, cfg.expert_hidden), jnp.float32),
            "wo": self.param(
                "wo", nn.initializers.lecun_normal(),
                (n_param, cfg.expert_hidden, d), jnp.float32),
        }

        def expert_fn(p, t):
            h = nn.silu(t @ p["wi"].astype(cfg.dtype))
            return h @ p["wo"].astype(cfg.dtype)

        capacity_factor = (cfg.num_experts / cfg.num_selected
                           if self.no_drop else cfg.capacity_factor)
        kwargs = dict(capacity_factor=capacity_factor,
                      num_selected=cfg.num_selected)
        if self.expert_axis is None:
            y, aux = moe_apply_dense(expert_fn, experts,
                                     tokens.astype(cfg.dtype),
                                     logits, **kwargs)
        else:
            y, aux = moe_apply(expert_fn, experts,
                               tokens.astype(cfg.dtype), logits,
                               axis_name=self.expert_axis, **kwargs)
        self.sow("aux_loss", "moe", aux)
        return y.reshape(b, s, d)


class MoeBlock(nn.Module):
    """Transformer block with a routed FFN (dense layers reuse
    ``LlamaBlock`` directly — see ``MoeLM``)."""

    config: MoeConfig
    expert_axis: Optional[str] = None
    local_experts: Optional[int] = None
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, positions=None, cache=None, cache_index=None):
        cfg = self.config
        x, new_cache = attention_sublayer(cfg.llama(), self.attention_fn, x,
                                          positions, cache, cache_index)
        h = RMSNorm(cfg.norm_eps, cfg.dtype, name="ffn_norm")(x)
        # Decode runs the experts at NO-DROP capacity (cf = E/k covers the
        # all-tokens-to-one-expert worst case): training-time capacity
        # drops are a throughput/regularization tradeoff computed from the
        # per-CALL token pool, and a single-token decode step's tiny pool
        # would bind capacity differently from the training forward —
        # dropping tokens at inference is never the right trade.
        out = x + MoeFFN(cfg, expert_axis=self.expert_axis,
                         local_experts=self.local_experts,
                         no_drop=cache is not None,
                         name="moe_ffn")(h)
        return out if cache is None else (out, new_cache)


class MoeLM(nn.Module):
    """Causal MoE LM. Apply with ``{"params": params}`` (not the full init
    variables — a stale ``aux_loss`` collection would double-count) and
    ``mutable=["aux_loss"]`` to collect the per-layer balancing losses:

        logits, col = model.apply({"params": p}, ids, mutable=["aux_loss"])
        aux = sum(jax.tree.leaves(col["aux_loss"]))

    For expert parallelism set ``expert_axis`` to the mesh axis and
    ``local_experts=1`` (the one-expert-per-device contract), shard the
    ``wi``/``wo`` leaves over that axis, and apply inside ``shard_map``.
    """

    config: MoeConfig
    expert_axis: Optional[str] = None
    local_experts: Optional[int] = None
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False,
                 cache=None, cache_index=None):
        """``positions``: global token positions of the local rows (see
        ``LlamaLM.__call__``) — required under sequence parallelism.
        ``return_hidden``: skip the lm_head and return the final-norm
        hidden states — pair with ``models.chunked_causal_lm_loss``
        (same contract as ``LlamaLM``).
        ``cache``/``cache_index``: autoregressive decoding, same contract
        as ``LlamaLM`` (``models.llama.generate`` works on this model too;
        aux-loss sow is a no-op outside a mutable collection). Decode runs
        the experts at NO-DROP capacity (see ``MoeBlock``): capacity is
        otherwise computed from the per-call token pool, so a single-token
        step would drop different assignments than a full forward. Decode
        therefore matches a full forward exactly WHEN the full forward's
        own capacity doesn't bind; under training-config capacity drops
        the two can legitimately diverge (the drop is a training
        artifact)."""
        cfg = self.config
        if cache is not None and positions is None:
            positions = cache_index + jnp.arange(input_ids.shape[1])
        x = token_embedding(cfg)(input_ids).astype(cfg.dtype)
        new_cache = {}
        moe_cls = rematerialised(cfg, MoeBlock)
        dense_cls = rematerialised(cfg, LlamaBlock)
        for i in range(cfg.num_layers):
            # Every moe_every-th layer is routed (moe_every=1: all layers);
            # the rest are plain LlamaBlocks (shared implementation).
            routed = i % cfg.moe_every == cfg.moe_every - 1
            if cache is not None:
                # Decoding never needs remat (no backward pass).
                cls = MoeBlock if routed else LlamaBlock
                kwargs = (dict(expert_axis=self.expert_axis,
                               local_experts=self.local_experts)
                          if routed else {})
                x, new_cache[f"layer_{i}"] = cls(
                    cfg if routed else cfg.llama(),
                    attention_fn=self.attention_fn, name=f"layer_{i}",
                    **kwargs)(x, positions, cache[f"layer_{i}"], cache_index)
            elif routed:
                x = moe_cls(cfg, expert_axis=self.expert_axis,
                            local_experts=self.local_experts,
                            attention_fn=self.attention_fn,
                            name=f"layer_{i}")(x, positions)
            else:
                x = dense_cls(cfg.llama(), attention_fn=self.attention_fn,
                              name=f"layer_{i}")(x, positions)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        if return_hidden:
            return x
        # Head matmul in head_dtype (default: model compute dtype),
        # matching LlamaLM — see LlamaConfig.head_dtype.
        logits = linear(cfg.vocab_size, cfg.head_dtype or cfg.dtype,
                        "lm_head")(x)
        return logits if cache is None else (logits, new_cache)
