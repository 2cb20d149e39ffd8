"""Laguna: a causal expert LM whose layers differ in kind.

The architecture of ``poolside/Laguna-XS.2`` (33.4B parameters, 3B active;
widths from its public ``config.json``), built from
``models/decoder.py``'s parts. What sets its block apart:

* **Attention takes its shape from the layer's type** (``layer_types``,
  period [full, sliding, sliding, sliding]). A full layer has 48 query
  heads, sees every earlier key and rotates the first HALF of each q and
  k head by YaRN's blended frequencies with cos and sin scaled; a sliding
  layer has 64 query heads, sees the keys ``i - window < j <= i`` and
  rotates the whole head by plain RoPE at a theta of its own. Both over 8
  key/value heads of width 128, no bias, no q/k norm.
* **A gate a head on the attention's output**: ``g = sigmoid(h W_g)``,
  one number a head and token, multiplies that head's context before
  ``W_o``.
* **The first layer's MLP is dense** (SiLU-gated, 8192 wide); every
  other layer is sparse (``mlp_layer_types``): the router reads the
  NORMED input in float32, a token's 8 largest of 256 logits are weighed
  by a softmax over them, the routed part is scaled by
  ``routed_scale`` 2.5 and added to a **shared expert** every token
  passes with weight 1: ``F(h) = S(h) + 2.5 sum_e w_e E_e(h)``, ``S``
  and ``E_e`` SiLU-gated MLPs 512 wide.

**The experts held.** ``experts_held`` names the routed experts whose
weights this device has (``SmallThinkerConfig.experts_held``): the block
routes over all ``num_experts`` and adds the part the held experts give;
attention, router, shared expert and the dense layer are whole on every
device. The routed parts of disjoint shares, with everything else counted
once, add up to the whole layer (``tests/test_laguna.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import profiler
from ..parallel.moe import grouped_gated_mlp, softmax_top_k
from .decoder import (GatedMLP, RMSNorm, decoder_layers, held_experts,
                      linear, lm_head, one_entry_a_layer, project_heads,
                      project_out, rotary_embedding, router_logits,
                      stack_loads, token_embedding, xla_attention)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
_PERIOD = (FULL, SLIDING, SLIDING, SLIDING)


@dataclasses.dataclass(frozen=True)
class RotarySpec:
    """One layer type's rotary embedding: rotate-half over the first
    ``fraction`` of the head width at ``theta``; with ``yarn_factor`` the
    frequencies are YaRN's blend and cos and sin are multiplied by
    ``attention_factor``."""
    theta: float
    fraction: float = 1.0
    yarn_factor: Optional[float] = None
    original_positions: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


def yarn_inv_freq(theta: float, rotary_dim: int, factor: float,
                  original_positions: int, beta_fast: float,
                  beta_slow: float):
    """YaRN's blended frequencies (Peng et al., arXiv:2309.00071, as the
    public ``transformers`` ``rope_type: "yarn"`` computes them), float64
    numpy, ``rotary_dim // 2`` of them. ``f_i = theta ** (-2i /
    rotary_dim)``; a frequency that turns more than ``beta_fast`` times
    within the original context keeps ``f_i`` (extrapolated), one that
    turns fewer than ``beta_slow`` times becomes ``f_i / factor``
    (interpolated), and between the two indices ``low`` and ``high`` a
    linear ramp blends them."""
    half = rotary_dim // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) * 2 / rotary_dim)

    def index_turning(rotations):
        # The (fractional) index of the frequency that turns ``rotations``
        # times over the original context.
        return rotary_dim * math.log(
            original_positions / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(index_turning(beta_fast)), 0)
    high = min(math.ceil(index_turning(beta_slow)), rotary_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def rotary_arguments(spec: RotarySpec, head_dim: int) -> dict:
    """``spec`` as ``rotary_embedding``'s keyword arguments."""
    rotary_dim = int(head_dim * spec.fraction)
    inv_freq = None
    if spec.yarn_factor is not None:
        inv_freq = yarn_inv_freq(
            spec.theta, rotary_dim, spec.yarn_factor,
            spec.original_positions, spec.beta_fast, spec.beta_slow)
    return dict(theta=spec.theta, rotary_dim=rotary_dim, inv_freq=inv_freq,
                scale=spec.attention_factor)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    dim: int = 2048
    num_layers: int = 40
    # One entry a layer: its attention's kind, its query heads, its MLP's
    # kind.
    layer_types: Tuple[str, ...] = _PERIOD * 10
    heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    full_rotary: RotarySpec = RotarySpec(
        theta=500000.0, fraction=0.5, yarn_factor=64.0,
        original_positions=4096, beta_fast=64.0, beta_slow=1.0,
        attention_factor=1.4158883083359672)
    sliding_rotary: RotarySpec = RotarySpec(theta=10000.0)
    mlp_hidden: int = 8192           # the dense layer's
    num_experts: int = 256           # the router's width
    num_selected: int = 8
    expert_hidden: int = 512
    shared_hidden: int = 512
    routed_scale: float = 2.5
    # Routed expert ids whose weights this device holds; None = all.
    experts_held: Optional[Tuple[int, ...]] = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # jax.checkpoint each block in the backward pass (LlamaConfig.remat).
    remat: bool = False

    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.experts_held is None
                else tuple(self.experts_held))


LAGUNA_XS2 = LagunaConfig()
# The dense layer and one period after it, a window shorter than any test
# sequence, YaRN's original context shorter too, 2 of 8 experts a token.
LAGUNA_TINY = LagunaConfig(
    vocab_size=512, dim=64, num_layers=5,
    layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
    heads_per_layer=(6, 8, 8, 8, 6),
    mlp_layer_types=(DENSE,) + (SPARSE,) * 4,
    num_kv_heads=2, head_dim=32, sliding_window=48,
    full_rotary=dataclasses.replace(LagunaConfig.full_rotary,
                                    original_positions=32, yarn_factor=8.0,
                                    beta_fast=8.0),
    mlp_hidden=160, num_experts=8, num_selected=2, expert_hidden=48,
    shared_hidden=48)


def head_gate(ctx, gate_logits):
    """The attention's output gate: context (B, S, H, D) times the
    sigmoid of one logit a head and token (B, S, H)."""
    return ctx * nn.sigmoid(gate_logits)[..., None]


class LagunaAttention(nn.Module):
    """Causal grouped-query attention with ``heads`` query heads, the
    rotary embedding ``rotary`` on q and k and a sigmoid gate a head on
    the context. ``attention_fn(q, k, v, None)`` carries the band (causal,
    windowed or not) and runs under ``band_scope``."""
    config: LagunaConfig
    heads: int
    rotary: RotarySpec
    band_scope: str
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        q = project_heads(self.heads, cfg.head_dim, cfg.dtype, "wq")(x)
        k = project_heads(cfg.num_kv_heads, cfg.head_dim, cfg.dtype, "wk")(x)
        v = project_heads(cfg.num_kv_heads, cfg.head_dim, cfg.dtype, "wv")(x)
        gate = linear(self.heads, cfg.dtype, "wg")(x)
        rotate = functools.partial(
            rotary_embedding, positions=positions,
            **rotary_arguments(self.rotary, cfg.head_dim))
        with jax.named_scope(profiler.SCOPE_ATTN_POINTWISE):
            q, k = rotate(q), rotate(k)
        with jax.named_scope(self.band_scope):
            ctx = self.attention_fn(q, k, v, None)
        with jax.named_scope(profiler.SCOPE_ATTN_POINTWISE):
            ctx = head_gate(ctx, gate)
        return project_out(cfg.dim, cfg.dtype)(ctx)


class LagunaBlock(nn.Module):
    """``a = x + Attn(norm(x))``; ``out = a + F(norm(a))``, ``F`` the
    dense MLP or the shared expert plus ``routed_scale`` times the chosen
    routed experts held here. Returns ``(out, load)``, ``load`` the
    assignments each held expert received (``None`` from a dense layer)."""
    config: LagunaConfig
    kind: str           # the attention's: FULL or SLIDING
    heads: int
    mlp_kind: str       # DENSE or SPARSE
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        b, s, d = x.shape
        full = self.kind == FULL
        a = x + LagunaAttention(
            cfg, self.heads,
            cfg.full_rotary if full else cfg.sliding_rotary,
            profiler.SCOPE_ATTN_FULL if full else profiler.SCOPE_ATTN_WINDOW,
            self.attention_fn, name="attention")(
            RMSNorm(cfg.norm_eps, cfg.dtype, name="attention_norm")(x),
            positions)
        h = RMSNorm(cfg.norm_eps, cfg.dtype, name="ffn_norm")(a)
        if self.mlp_kind == DENSE:
            return a + GatedMLP(cfg.mlp_hidden, cfg.dtype,
                                name="mlp")(h), None
        rows = h.reshape(b * s, d)
        logits = router_logits(rows, cfg.num_experts)
        with jax.named_scope(profiler.SCOPE_MOE_SHARED):
            shared = GatedMLP(cfg.shared_hidden, cfg.dtype, name="shared")(h)
        routed, load = held_experts(
            functools.partial(grouped_gated_mlp, activation=jax.nn.silu),
            rows, logits, cfg.held(), cfg.expert_hidden, cfg.num_selected,
            route=softmax_top_k)
        return a + shared + cfg.routed_scale * routed.reshape(b, s, d), load


class LagunaLM(nn.Module):
    """Token embedding, the blocks, a final RMSNorm and an untied head.

    ``attention_fn`` serves the full layers and ``window_attention_fn``
    the sliding ones, both with the signature ``(q, k, v, mask)``; the
    defaults are the plain XLA softmax. On the chip pass
    ``make_attention_fn(causal=True)`` and ``make_attention_fn(causal=True,
    window=cfg.sliding_window)``, whose own shape rule picks the kernels.

    Returns ``(logits, load)``, or with ``return_hidden`` ``(hidden,
    load)`` for ``chunked_causal_lm_loss``; ``load[sparse layer, held
    expert]`` counts the assignments each held expert received (a dense
    layer has no row)."""
    config: LagunaConfig
    attention_fn: Optional[Callable] = None
    window_attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False):
        cfg = self.config
        one_entry_a_layer("LagunaLM", cfg, "layer_types", "heads_per_layer",
                          "mlp_layer_types")
        plain = self.attention_fn or xla_attention()
        windowed = self.window_attention_fn or xla_attention(
            cfg.sliding_window)
        layers = [dict(kind=kind, heads=cfg.heads_per_layer[i],
                       mlp_kind=cfg.mlp_layer_types[i],
                       attention_fn=plain if kind == FULL else windowed)
                  for i, kind in enumerate(cfg.layer_types[:cfg.num_layers])]
        x, loads = decoder_layers(cfg, LagunaBlock, layers,
                                  token_embedding(cfg)(input_ids), positions)
        load = stack_loads(loads, cfg.held())
        if return_hidden:
            return x, load
        return lm_head(cfg)(x), load
