"""Olmo-Hybrid: a causal LM whose token mixer is chosen layer by layer,
three gated delta-rule layers to every full-attention layer.

The architecture of ``allenai/Olmo-Hybrid-7B`` (widths from its public
``config.json``; the linear layers' keys are those of
flash-linear-attention's ``GatedDeltaNet``), built from
``models/decoder.py``'s parts. What sets it apart:

* **The block norms its sublayers' outputs**, the OLMo 2/3 family's
  convention: ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(MLP(h))``,
  ``MLP(h) = W_down(silu(W_gate h) * (W_up h))``. The mixer and the MLP
  read the residual stream as it is. No bias anywhere, the head untied.
* **``layer_types[i]`` picks the mixer.** ``"full_attention"``: ``q, k, v
  = W_q x, W_k x, W_v x``; q and k each pass an RMSNorm with one learned
  scale over the *whole* projection (all heads' columns together); heads
  of width ``head_dim``, causal softmax attention, **no rotary embedding**
  (the recurrent layers carry position); ``W_o``.
* ``"linear_attention"``, head h of H, widths ``d_k`` and ``d_v``::

      q~, k~, v~ = W_q x, W_k x, W_v x
      q, k, v    = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                   conv: per channel, ``conv_kernel`` taps ending at the
                   token's own, zero before the sequence, no bias
      q^ = q/||q||_2 * d_k^-1/2,  k^ = k/||k||_2     per head and token
      beta_t = 2 * sigmoid(W_b x)_h    in (0, 2): ``linear_allow_neg_eigval``
      g_t    = -exp(A_log_h) * softplus((W_a x)_h + dt_bias_h)
      S_t    = e^{g_t} S_{t-1} + beta_t (v_t - e^{g_t} S_{t-1} k^_t) k^_t^T
      o_t    = S_t q^_t                    (``ops.linear_attention``)
      y      = W_o [ RMSNorm_{d_v}(o_t) * silu(W_g x) ]

  the norm per head with one learned ``d_v`` scale.

**The heads held.** ``heads_held`` names the head ids this device has in
every mixer (``None``: all of them, the published layer). A block that
holds a share owns the columns of ``W_q``, ``W_k``, ``W_v``, ``W_g``,
``W_a``, ``W_b``, the convolutions' channels, ``A_log``, ``dt_bias`` and
the rows of ``W_o`` of its heads, and computes the part of the mixer's
result those heads give; the MLP, the norms and the residual are whole on
every device. With ``heads_axis`` the shares meet in two ``lax.psum``s over
that axis: the output projection's partial sums, and on the full layers
the sum of squares under the q/k norm. With ``heads_axis=None``, as on one
chip, neither runs and nothing stands in for the absent devices: the
partial result goes on into the norm, and the q/k norm is over the held
columns. The shares of a layer, joined over ``heads_axis``, are the whole
layer (``tests/test_olmo_hybrid.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..common import profiler
from ..ops import linear_attention
from .decoder import (Leaf, RMSNorm, decoder_layers, gated_mlp, linear,
                      lm_head, one_entry_a_layer, token_embedding,
                      xla_attention)

LINEAR, FULL = "linear_attention", "full_attention"
_PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    dim: int = 3840
    num_layers: int = 32
    layer_types: Tuple[str, ...] = _PERIOD * 8
    mlp_hidden: int = 11008
    num_heads: int = 30              # of every mixer, full and linear
    head_dim: int = 128              # the full layers' heads
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    # Head ids this device holds in every mixer; None = all of them.
    heads_held: Optional[Tuple[int, ...]] = None
    # Mesh (or vmap) axis over which the shares of a layer meet.
    heads_axis: Optional[str] = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # jax.checkpoint each block in the backward pass (LlamaConfig.remat).
    remat: bool = False

    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_heads)) if self.heads_held is None
                else tuple(self.heads_held))


# One period and a half, so that both kinds of layer follow both kinds.
OLMO_HYBRID_TINY = OlmoHybridConfig(
    vocab_size=512, dim=64, num_layers=6, layer_types=_PERIOD * 2,
    mlp_hidden=96, num_heads=4, head_dim=16, linear_key_dim=16,
    linear_value_dim=32)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log`` of a decay rate uniform in [1, 16], flash-linear-attention's
    default."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniform in [0.001, 0.1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(0.001),
                                    jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _taps_init(key, shape, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


class _WholeNorm(nn.Module):
    """RMSNorm over every head's columns together, with one learned scale
    a column held; the mean of squares is taken over ``heads_axis`` too
    where the shares of a layer meet there."""
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        squares = jnp.sum(x32 * x32, axis=-1, keepdims=True)
        columns = x.shape[-1]
        if cfg.heads_axis is not None:
            squares = lax.psum(squares, cfg.heads_axis)
            columns = lax.psum(columns, cfg.heads_axis)
        return (x32 * lax.rsqrt(squares / columns + cfg.norm_eps)
                * scale).astype(cfg.dtype)


class FullAttentionMixer(nn.Module):
    """Causal softmax attention over the heads held: a q/k norm over the
    whole projection, no rotary embedding. ``attention_fn(q, k, v, None)``
    carries the band: ``make_attention_fn(causal=True)``."""
    config: OlmoHybridConfig
    attention_fn: Callable

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, _ = x.shape
        heads = len(cfg.held())
        width = heads * cfg.head_dim
        q = _WholeNorm(cfg, name="q_norm")(linear(width, cfg.dtype, "wq")(x))
        k = _WholeNorm(cfg, name="k_norm")(linear(width, cfg.dtype, "wk")(x))
        v = linear(width, cfg.dtype, "wv")(x)
        split = lambda a: a.reshape(b, s, heads, cfg.head_dim)  # noqa: E731
        ctx = self.attention_fn(split(q), split(k), split(v), None)
        return linear(cfg.dim, cfg.dtype, "wo")(ctx.reshape(b, s, width))


class LinearAttentionMixer(nn.Module):
    """The gated delta-rule layer over the heads held (the module's
    equations). Returns ``(y, stats)``: ``stats`` is the smallest and the
    mean log decay ``g`` over the tokens and the held heads (logs: the
    smallest decay of a hundred thousand tokens underflows float32), and
    the largest Frobenius norm among the heads' states after the last
    token."""
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, _ = x.shape
        heads = len(cfg.held())
        d_k, d_v = cfg.linear_key_dim, cfg.linear_value_dim

        def conv(a, name):
            return linear_attention.causal_conv_silu(a, Leaf(
                "kernel", (cfg.conv_kernel, a.shape[-1]), _taps_init,
                name=name)())

        q = linear(heads * d_k, cfg.dtype, "wq")(x)
        k = linear(heads * d_k, cfg.dtype, "wk")(x)
        v = linear(heads * d_v, cfg.dtype, "wv")(x)
        gate = linear(heads * d_v, cfg.dtype, "wg")(x)
        with jax.named_scope(profiler.SCOPE_LINATTN_CONV):
            q = linear_attention.l2_normalize(
                conv(q, "conv_q").reshape(b, s, heads, d_k)) * d_k ** -0.5
            k = linear_attention.l2_normalize(
                conv(k, "conv_k").reshape(b, s, heads, d_k))
            v = conv(v, "conv_v").reshape(b, s, heads, d_v)
        x32 = x.astype(jnp.float32)
        beta = 2.0 * jax.nn.sigmoid(linear(heads, jnp.float32, "wb")(x32))
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        g = -jnp.exp(a_log) * jax.nn.softplus(
            linear(heads, jnp.float32, "wa")(x32) + dt_bias)
        with jax.named_scope(profiler.SCOPE_LINATTN_SCAN):
            o, state = linear_attention.gated_delta_rule(
                q, k, v, g, beta, output_final_state=True)
        with jax.named_scope(profiler.SCOPE_LINATTN_GATE):
            o = linear_attention.gated_head_norm(
                o, gate.reshape(b, s, heads, d_v),
                Leaf("scale", (d_v,), nn.initializers.ones,
                      name="o_norm")(), cfg.norm_eps)
        stats = lax.stop_gradient(jnp.stack([
            jnp.min(g), jnp.mean(g),
            jnp.sqrt(jnp.max(jnp.sum(state * state, axis=(-2, -1))))]))
        return linear(cfg.dim, cfg.dtype, "wo")(
            o.reshape(b, s, heads * d_v)), stats


class OlmoHybridBlock(nn.Module):
    """``h = x + norm(Mixer(x))``; ``y = h + norm(MLP(h))``. Returns ``(y,
    stats)``, ``stats`` the linear mixer's three numbers (zeros on a
    full-attention layer)."""
    config: OlmoHybridConfig
    layer_type: str
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if self.layer_type == LINEAR:
            mixed, stats = LinearAttentionMixer(cfg, name="mixer")(x)
        elif self.layer_type == FULL:
            mixed = FullAttentionMixer(cfg, self.attention_fn,
                                       name="mixer")(x)
            stats = jnp.zeros((3,), jnp.float32)
        else:
            raise ValueError(f"OlmoHybridBlock: layer type "
                             f"{self.layer_type!r} is neither {LINEAR!r} "
                             f"nor {FULL!r}")
        if cfg.heads_axis is not None:
            mixed = lax.psum(mixed, cfg.heads_axis)
        h = x + RMSNorm(cfg.norm_eps, cfg.dtype, name="mixer_norm")(mixed)
        mlp = gated_mlp(h, cfg.mlp_hidden, cfg.dtype)
        return h + RMSNorm(cfg.norm_eps, cfg.dtype, name="mlp_norm")(mlp), \
            stats


class OlmoHybridLM(nn.Module):
    """Token embedding, the blocks, a final RMSNorm and an untied head.

    ``attention_fn`` serves the full-attention layers with the signature
    ``(q, k, v, mask)``; the default is the plain XLA softmax. On the chip
    pass ``make_attention_fn(causal=True)``, whose own shape rule picks
    the kernels.

    Returns ``(logits, stats)``, or with ``return_hidden`` ``(hidden,
    stats)`` for ``chunked_causal_lm_loss``; ``stats[i]`` is the i-th
    linear layer's ``(smallest log decay, mean log decay, largest state
    norm)``."""
    config: OlmoHybridConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, return_hidden=False):
        cfg = self.config
        one_entry_a_layer("OlmoHybridLM", cfg, "layer_types")
        attention_fn = self.attention_fn or xla_attention()
        kinds = cfg.layer_types[:cfg.num_layers]
        layers = [dict(layer_type=kind, attention_fn=attention_fn)
                  for kind in kinds]
        x, stats = decoder_layers(cfg, OlmoHybridBlock, layers,
                                  token_embedding(cfg)(input_ids))
        stats = [row for row, kind in zip(stats, kinds) if kind == LINEAR]
        stats = jnp.stack(stats) if stats else jnp.zeros((0, 3), jnp.float32)
        if return_hidden:
            return x, stats
        return lm_head(cfg)(x), stats
