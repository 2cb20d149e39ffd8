"""LFM2: a causal expert LM whose mixers are mostly short convolutions.

The architecture of ``LiquidAI/LFM2-24B-A2B`` (``model_type``
``lfm2_moe``; 24B parameters, 2B active; widths from its public
``config.json``), built from ``models/decoder.py``'s parts,
``ops.short_conv``'s two kernels and ``ops.linear_attention.causal_conv``.
The block is
``h = x + Mixer(norm(x))``, ``y = h + FFN(norm(h))``. What sets it apart:

* **Three mixers in four are gated short convolutions** (``layer_types``,
  period [conv, conv, attention, conv]): ``[B, C, u] = split3(z W_in)``,
  ``v = conv3(B * u)`` (causal, depthwise, 3 taps, no bias and no
  activation), ``out = (C * v) W_out``: a gate on either side of a
  convolution that sees two tokens back. Where the width is a whole
  number of 128-lane tiles and the sequence of row blocks, ``C * conv3(B *
  u)`` is one Pallas kernel forward and one backward over the projection's
  packed result (``ops.short_conv``); elsewhere XLA's plain form.
* **The fourth is grouped-query attention** with an RMSNorm over each q
  and k head (one scale of the head's width, shared by the heads) BEFORE
  the rotary embedding, which rotates the whole head; 32 query heads over
  8, width ``dim / heads`` = 64.
* **The first ``num_dense_layers`` FFNs are dense** (SiLU-gated); the
  others route: scores ``s = sigmoid(z W_r)`` in float32, the chosen set
  is the 4 largest of ``s + b`` with ``b`` one float an expert that
  enters THE CHOICE ONLY (``use_expert_bias``: what an
  auxiliary-loss-free balancing rule moves; here a leaf that takes no
  gradient, see below), the weights are the chosen experts' own scores
  over their sum plus 1e-6 (``norm_topk_prob``; the published
  ``routed_scaling_factor`` is 1); no shared expert.
* **The head is the embedding**: ``logits = x E^T``; with
  ``return_hidden`` the caller hands
  ``chunked_causal_lm_loss`` the embedding transposed, and the
  embedding's gradient is the sum of the lookup's and the head's.

**The bias** is the leaf ``layer_i/expert_bias/kernel`` ``[num_experts]``
in ``params``: the routing rule reads it under ``stop_gradient``, so its
gradient is exactly zero, and a training step must leave it out of weight
decay (``decay_mask``). The update that trains it by the experts' loads
is not in this file.

**The experts held.** ``experts_held`` names the routed experts whose
weights this device has (``SmallThinkerConfig.experts_held``): a sparse
block routes over all ``num_experts`` and adds the part the held experts
give; mixers, router, bias and the dense layers are whole on every
device. The routed parts of disjoint shares, with everything else counted
once, add up to the whole layer (``tests/test_lfm2.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import profiler
from ..ops.linear_attention import causal_conv
from ..ops.short_conv import block_rows, gated_short_conv_packed
from ..parallel.moe import grouped_gated_mlp, sigmoid_top_k
from .decoder import (GatedMLP, Leaf, RMSNorm, decoder_layers, held_experts,
                      linear, one_entry_a_layer, project_heads, project_out,
                      rotary_embedding, router_logits, stack_loads,
                      token_embedding, xla_attention)

CONV, FULL = "conv", "full_attention"
_PERIOD = (CONV, CONV, FULL, CONV)


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    dim: int = 2048
    num_layers: int = 40
    # One entry a layer: its mixer's kind.
    layer_types: Tuple[str, ...] = _PERIOD * 10
    # The first ``num_dense_layers`` FFNs are dense, the others route.
    num_dense_layers: int = 2
    num_heads: int = 32              # the head's width is dim / num_heads
    num_kv_heads: int = 8
    rope_theta: float = 1e6
    conv_taps: int = 3
    mlp_hidden: int = 11776          # the dense layers'
    num_experts: int = 64            # the router's width
    num_selected: int = 4
    expert_hidden: int = 1536
    # Routed expert ids whose weights this device holds; None = all.
    experts_held: Optional[Tuple[int, ...]] = None
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # jax.checkpoint each block in the backward pass (LlamaConfig.remat).
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.num_heads

    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.experts_held is None
                else tuple(self.experts_held))


LFM2_24B_A2B = Lfm2Config()
# A leading dense layer and one period after it, 2 of 8 experts a token.
LFM2_TINY = Lfm2Config(
    vocab_size=512, dim=64, num_layers=5,
    layer_types=(CONV, FULL, CONV, CONV, CONV), num_dense_layers=1,
    num_heads=2, num_kv_heads=1, mlp_hidden=160, num_experts=8,
    num_selected=2, expert_hidden=48)


def _taps_init(key, shape, dtype=jnp.float32):
    """Uniform within 1/sqrt(taps) of zero: a depthwise convolution's
    default in the framework the model was published in."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def gated_short_conv(b_gate, c_gate, u, taps):
    """``C * conv(B * u)``: the pointwise part of the short-convolution
    mixer in its plain form, (B, S, dim) arrays and ``taps`` (K, dim). The
    convolution is ``ops.linear_attention.causal_conv`` with no
    activation. What the mixer runs where
    ``ops.short_conv.block_rows`` names no block for its shapes (a width
    off the 128-lane tile, a sequence the block does not divide); the
    others go through ``ops.short_conv.gated_short_conv_packed``."""
    return c_gate * causal_conv(b_gate * u, taps)


def decay_mask(params):
    """``params``-shaped tree of bools for an optimizer's weight decay:
    False on every ``expert_bias`` leaf, which no step trains."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: not any(
            getattr(k, "key", None) == "expert_bias" for k in path), params)


class ShortConvMixer(nn.Module):
    """``[B, C, u] = split3(z W_in)``; ``out = (C * conv(B * u)) W_out``,
    no bias anywhere. All of it under ``hvd.shortconv``, the part between
    the projections under ``hvd.shortconv.pointwise``.

    Which form the pointwise part takes is read from the shapes: where
    ``ops.short_conv.block_rows`` names a block (the width a whole number
    of 128-lane tiles, the sequence a whole number of blocks: 256 rows at
    width 2048), one Pallas kernel forward and one backward read the
    in-projection's result where it lies and write ``y``, and the three
    gates' gradients as one array; anything else (the tiny models at
    width 64, a ragged sequence) splits it and runs
    :func:`gated_short_conv`."""
    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with jax.named_scope(profiler.SCOPE_SHORTCONV):
            gates = linear(3 * cfg.dim, cfg.dtype, "in_proj")(x)
            taps = Leaf("kernel", (cfg.conv_taps, cfg.dim), _taps_init,
                        name="taps")()
            fused = block_rows(x.shape[1], cfg.dim, cfg.conv_taps)
            if not fused:
                b_gate, c_gate, u = jnp.split(gates, 3, axis=-1)
            with jax.named_scope(profiler.SCOPE_SHORTCONV_POINTWISE):
                y = (gated_short_conv_packed(gates, taps) if fused
                     else gated_short_conv(b_gate, c_gate, u, taps))
            return linear(cfg.dim, cfg.dtype, "out_proj")(y)


class Lfm2Attention(nn.Module):
    """Causal grouped-query attention: RMSNorm over each q and k head,
    then the rotary embedding over the whole head. ``attention_fn(q, k,
    v, None)`` carries the band and runs under ``hvd.attn.full``."""
    config: Lfm2Config
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        q = RMSNorm(cfg.norm_eps, cfg.dtype, name="q_norm")(
            project_heads(cfg.num_heads, cfg.head_dim, cfg.dtype, "wq")(x))
        k = RMSNorm(cfg.norm_eps, cfg.dtype, name="k_norm")(
            project_heads(cfg.num_kv_heads, cfg.head_dim, cfg.dtype, "wk")(x))
        v = project_heads(cfg.num_kv_heads, cfg.head_dim, cfg.dtype, "wv")(x)
        q = rotary_embedding(q, cfg.rope_theta, positions)
        k = rotary_embedding(k, cfg.rope_theta, positions)
        with jax.named_scope(profiler.SCOPE_ATTN_FULL):
            ctx = self.attention_fn(q, k, v, None)
        return project_out(cfg.dim, cfg.dtype)(ctx)


class Lfm2Block(nn.Module):
    """``h = x + Mixer(norm(x))``; ``out = h + F(norm(h))``, ``F`` the
    dense MLP or the chosen routed experts held here. Returns ``(out,
    load)``, ``load`` the assignments each held expert received (``None``
    from a dense layer)."""
    config: Lfm2Config
    kind: str           # the mixer's: CONV or FULL
    sparse: bool
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        b, s, d = x.shape
        z = RMSNorm(cfg.norm_eps, cfg.dtype, name="operator_norm")(x)
        if self.kind == CONV:
            mixed = ShortConvMixer(cfg, name="conv")(z)
        elif self.kind == FULL:
            mixed = Lfm2Attention(cfg, self.attention_fn,
                                  name="attention")(z, positions)
        else:
            raise ValueError(f"Lfm2Block: layer type {self.kind!r} is "
                             f"neither {CONV!r} nor {FULL!r}")
        h = x + mixed
        z = RMSNorm(cfg.norm_eps, cfg.dtype, name="ffn_norm")(h)
        if not self.sparse:
            return h + GatedMLP(cfg.mlp_hidden, cfg.dtype, name="mlp")(z), \
                None
        rows = z.reshape(b * s, d)
        logits = router_logits(rows, cfg.num_experts)
        bias = Leaf("kernel", (cfg.num_experts,),
                    nn.initializers.normal(0.01), name="expert_bias")()
        routed, load = held_experts(
            functools.partial(grouped_gated_mlp, activation=jax.nn.silu),
            rows, logits, cfg.held(), cfg.expert_hidden, cfg.num_selected,
            route=sigmoid_top_k(bias))
        return h + routed.reshape(b, s, d), load


class Lfm2LM(nn.Module):
    """Token embedding, the blocks, a final RMSNorm and the head, which
    is the embedding again.

    ``attention_fn(q, k, v, mask)`` serves the attention layers; the
    default is the plain XLA softmax. On the chip pass
    ``make_attention_fn(causal=True)``, whose own shape rule picks the
    kernels.

    Returns ``(logits, load)``, or with ``return_hidden`` ``(hidden,
    load)`` for ``chunked_causal_lm_loss``, whose ``head_kernel`` is then
    ``params["tok_embeddings"]["embedding"].T``; ``load[sparse layer,
    held expert]`` counts the assignments each held expert received (a
    dense layer has no row)."""
    config: Lfm2Config
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False):
        cfg = self.config
        one_entry_a_layer("Lfm2LM", cfg, "layer_types")
        attention_fn = self.attention_fn or xla_attention()
        layers = [dict(kind=kind, sparse=i >= cfg.num_dense_layers,
                       attention_fn=attention_fn if kind == FULL else None)
                  for i, kind in enumerate(cfg.layer_types[:cfg.num_layers])]
        embed = token_embedding(cfg)
        x, loads = decoder_layers(cfg, Lfm2Block, layers, embed(input_ids),
                                  positions)
        load = stack_loads(loads, cfg.held())
        if return_hidden:
            return x, load
        return jnp.einsum("bsd,vd->bsv", x, embed.embedding.astype(
            cfg.dtype)), load
