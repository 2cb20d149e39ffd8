"""SmallThinker: a causal expert LM whose router reads ahead of attention.

The architecture of ``PowerInfer/SmallThinker-21BA3B-Instruct`` (the
SmallThinker report, arXiv:2507.20984; widths from its public
``config.json``), beside ``LlamaLM`` and ``MoeLM`` and built from their
parts (``RMSNorm``, ``rotary_embedding``). What sets its block apart:

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
this device has; the block hands them to
``parallel.moe.moe_apply_held``, which routes over all
``num_experts`` and returns the part of the layer's result that the held
experts give. ``None`` holds all of them and is the published layer. With
a share (16 of 64: one chip of four that share each layer) the block's
output is ``a + (that part)``, and that partial result goes on to the next
layer: nothing stands in for the other devices or their exchange. The
parts of disjoint shares, with attention and the residual counted once,
add up to the whole layer (``tests/test_smallthinker.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from ..ops.attention import make_attention_fn
from ..parallel.moe import (grouped_gated_mlp, moe_apply_held,
                            softmax_top_k)
from .llama import RMSNorm, rotary_embedding

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


class _Kernel(nn.Module):
    """One float32 matrix under the leaf name ``kernel``, as ``nn.Dense``
    names its own."""
    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(0.02), self.shape,
                          jnp.float32)


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
        dense = lambda heads, name: nn.DenseGeneral(  # noqa: E731
            features=(heads, cfg.head_dim), axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        q = dense(cfg.num_heads, "wq")(x)
        k = dense(cfg.num_kv_heads, "wk")(x)
        v = dense(cfg.num_kv_heads, "wv")(x)
        if self.rope:
            q = rotary_embedding(q, cfg.rope_theta, positions)
            k = rotary_embedding(k, cfg.rope_theta, positions)
        ctx = self.attention_fn(q, k, v, None)
        return nn.DenseGeneral(features=cfg.dim, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name="wo")(ctx)


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
        held = cfg.held()
        # The router, ahead of attention and on the un-normed input, in
        # float32: which experts a token gets is decided on small
        # differences between logits.
        logits = x.reshape(b * s, d).astype(jnp.float32) @ _Kernel(
            (d, cfg.num_experts), name="router")()
        a = x + SmallThinkerAttention(
            cfg, self.rope, self.attention_fn, name="attention")(
            RMSNorm(cfg.norm_eps, cfg.dtype, name="attention_norm")(x),
            positions)
        h = RMSNorm(cfg.norm_eps, cfg.dtype, name="ffn_norm")(a)
        experts = {
            "w_gate": _Kernel((len(held), d, cfg.expert_hidden),
                              name="w_gate")(),
            "w_up": _Kernel((len(held), d, cfg.expert_hidden),
                            name="w_up")(),
            "w_down": _Kernel((len(held), cfg.expert_hidden, d),
                              name="w_down")(),
        }
        y, load = moe_apply_held(grouped_gated_mlp, experts,
                                 h.reshape(b * s, d), logits, held,
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
        if len(cfg.window_layout) < cfg.num_layers or len(
                cfg.rope_layout) < cfg.num_layers:
            raise ValueError("SmallThinkerLM: window_layout and rope_layout "
                             f"need an entry for each of {cfg.num_layers} "
                             "layers")
        plain = self.attention_fn or make_attention_fn(
            causal=True, use_flash=False)
        windowed = self.window_attention_fn or make_attention_fn(
            causal=True, use_flash=False, window=cfg.sliding_window)
        x = nn.Embed(cfg.vocab_size, cfg.dim, param_dtype=jnp.float32,
                     name="tok_embeddings")(input_ids).astype(cfg.dtype)
        block_cls = nn.remat(SmallThinkerBlock) if cfg.remat \
            else SmallThinkerBlock
        loads = []
        for i in range(cfg.num_layers):
            x, load = block_cls(
                cfg, rope=bool(cfg.rope_layout[i]),
                attention_fn=windowed if cfg.window_layout[i] else plain,
                name=f"layer_{i}")(x, positions)
            loads.append(load)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        load = jnp.stack(loads)
        if return_hidden:
            return x, load
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="lm_head")(x)
        return logits, load
