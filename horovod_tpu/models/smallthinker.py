"""SmallThinker: a causal expert LM whose router reads ahead of attention.

The architecture of ``PowerInfer/SmallThinker-21BA3B-Instruct`` (the
SmallThinker report, arXiv:2507.20984; widths from its public
``config.json``), built from ``models/decoder.py``'s parts. What sets
its block apart:

* **Every layer is an expert layer**, 6 of 64 experts a token, no shared
  expert, no capacity and no dropped assignment; an expert is a
  three-matrix ReLU-gated MLP, ``w_down(relu(w_gate h) * (w_up h))``; the
  weights are a softmax over the six chosen logits.
* **The router reads the layer's input**, before the attention's norm:
  ``r = x W_r`` in float32, so the routing of a layer does not wait for
  its attention.
* **Attention alternates by layer** (``window_layout`` / ``rope_layout``,
  period [global, window, window, window]): a global layer sees every
  earlier key and has NO rotary embedding; a window layer sees the keys
  ``i - window < j <= i`` and rotates q and k over the whole head width.
  Grouped-query heads of a width of their own (28 over 4, width 128 at a
  hidden width of 2560, so q is 3584 wide).

**The experts held.** ``experts_held`` names the expert ids whose weights
this device has; the block hands them to ``decoder.held_experts``
(``parallel.moe.moe_apply_held``), which routes over all ``num_experts``
and returns the part of the layer's result that the held experts give.
``None`` holds all of them and is the published layer. With a share (16
of 64: one chip of four that share each layer) the block's output is ``a
+ (that part)``, and that partial result goes on to the next layer:
nothing stands in for the other devices or their exchange. The parts of
disjoint shares, with attention and the residual counted once, add up to
the whole layer (``tests/test_smallthinker.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.moe import grouped_gated_mlp, softmax_top_k
from .decoder import (RMSNorm, decoder_layers, held_experts, lm_head,
                      one_entry_a_layer, project_heads, project_out,
                      rotary_embedding, router_logits, stack_loads,
                      token_embedding, xla_attention)

_PERIOD = (0, 1, 1, 1)      # global without RoPE, then three window layers


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    dim: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64            # the router's width
    num_selected: int = 6
    expert_hidden: int = 768
    # Expert ids whose weights this device holds; None = all of them.
    experts_held: Optional[Tuple[int, ...]] = None
    sliding_window: int = 4096
    # One entry a layer: 1 = that layer is windowed / rotated.
    window_layout: Tuple[int, ...] = _PERIOD * 13
    rope_layout: Tuple[int, ...] = _PERIOD * 13
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # jax.checkpoint each block in the backward pass (LlamaConfig.remat).
    remat: bool = False

    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.experts_held is None
                else tuple(self.experts_held))


SMALLTHINKER_21B = SmallThinkerConfig()
# Two periods, a window shorter than any test sequence, 2 of 8 experts.
SMALLTHINKER_TINY = SmallThinkerConfig(
    vocab_size=512, dim=64, num_layers=8, num_heads=4, num_kv_heads=2,
    head_dim=32, num_experts=8, num_selected=2, expert_hidden=48,
    sliding_window=48, window_layout=_PERIOD * 2, rope_layout=_PERIOD * 2)


class SmallThinkerAttention(nn.Module):
    """Causal grouped-query attention at an explicit head width, rotary
    embedding on or off. ``attention_fn(q, k, v, None)`` carries the band
    (causal, windowed or not): ``make_attention_fn(causal=True,
    window=...)``."""
    config: SmallThinkerConfig
    rope: bool
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        q = project_heads(cfg.num_heads, cfg.head_dim, cfg.dtype, "wq")(x)
        k = project_heads(cfg.num_kv_heads, cfg.head_dim, cfg.dtype, "wk")(x)
        v = project_heads(cfg.num_kv_heads, cfg.head_dim, cfg.dtype, "wv")(x)
        if self.rope:
            q = rotary_embedding(q, cfg.rope_theta, positions)
            k = rotary_embedding(k, cfg.rope_theta, positions)
        ctx = self.attention_fn(q, k, v, None)
        return project_out(cfg.dim, cfg.dtype)(ctx)


class SmallThinkerBlock(nn.Module):
    """``r = x W_r``; ``a = x + Attn(norm(x))``; ``out = a + sum over the
    chosen experts held here of w_e y_e(norm(a))``. Returns ``(out,
    load)``, ``load`` the assignments each held expert received."""
    config: SmallThinkerConfig
    rope: bool
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        b, s, d = x.shape
        # The router reads the un-normed input, ahead of attention.
        logits = router_logits(x.reshape(b * s, d), cfg.num_experts)
        a = x + SmallThinkerAttention(
            cfg, self.rope, self.attention_fn, name="attention")(
            RMSNorm(cfg.norm_eps, cfg.dtype, name="attention_norm")(x),
            positions)
        h = RMSNorm(cfg.norm_eps, cfg.dtype, name="ffn_norm")(a)
        y, load = held_experts(grouped_gated_mlp, h.reshape(b * s, d),
                               logits, cfg.held(), cfg.expert_hidden,
                               cfg.num_selected, route=softmax_top_k)
        return a + y.reshape(b, s, d), load


class SmallThinkerLM(nn.Module):
    """Token embedding, the blocks, a final RMSNorm and an untied head.

    ``attention_fn`` serves the global layers and
    ``window_attention_fn`` the windowed ones, both with the signature
    ``(q, k, v, mask)``; the defaults are the plain XLA softmax. On the
    chip pass ``make_attention_fn(causal=True)`` and
    ``make_attention_fn(causal=True, window=cfg.sliding_window)``, whose
    own shape rule picks the kernels.

    Returns ``(logits, load)``, or with ``return_hidden`` ``(hidden,
    load)`` for ``chunked_causal_lm_loss``; ``load[layer, held expert]``
    counts the assignments each held expert received."""
    config: SmallThinkerConfig
    attention_fn: Optional[Callable] = None
    window_attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False):
        cfg = self.config
        one_entry_a_layer("SmallThinkerLM", cfg, "window_layout",
                          "rope_layout")
        plain = self.attention_fn or xla_attention()
        windowed = self.window_attention_fn or xla_attention(
            cfg.sliding_window)
        layers = [dict(rope=bool(cfg.rope_layout[i]),
                       attention_fn=windowed if cfg.window_layout[i]
                       else plain) for i in range(cfg.num_layers)]
        x, loads = decoder_layers(cfg, SmallThinkerBlock, layers,
                                  token_embedding(cfg)(input_ids), positions)
        load = stack_loads(loads, cfg.held())
        if return_hidden:
            return x, load
        return lm_head(cfg)(x), load
