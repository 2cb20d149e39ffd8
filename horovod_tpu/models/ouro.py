"""Ouro: a looped causal LM whose one stack of layers runs several times.

The architecture of ``ByteDance/Ouro-2.6B`` (``model_type`` ``ouro``;
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741;
widths from its public ``config.json``), built from ``models/decoder.py``'s
parts and ``models/losses.py``'s weighted sweep. What sets it apart:

* **The whole stack is run ``total_ut_steps`` times on the same
  parameters** (``decoder.looped_decoder_layers``): ``h_0 = Embed(ids)``,
  ``h_t = FinalNorm(Block_L(... Block_1(h_{t-1})))``, the final norm at
  the end of EVERY pass and its output going on into the next, positions
  0..S-1 in every pass. A parameter is one leaf whatever the number of
  passes, and its gradient the sum over them.
* **The block norms before AND after each sublayer**: ``x' = x +
  N2(Attn(N1(x)))``, ``y = x' + N4(MLP(N3(x')))``, the four RMSNorms named
  as the public modelling code names them (``input_layernorm``,
  ``input_layernorm_2``, ``post_attention_layernorm``,
  ``post_attention_layernorm_2``). Attention is plain multi-head (16
  heads of 128 over as many K/V heads), rotate-half over the whole head;
  the MLP is SiLU-gated; no bias in either.
* **An exit gate after every pass**: ``lam_t = sigmoid(w_g . h_t + b_g)``,
  one ``Linear(dim -> 1)`` with bias shared by the passes
  (``early_exit_gate``), computed in float32. The exit distribution
  (:func:`exit_distribution`) is ``p_t = lam_t prod_{j<t} (1 - lam_j)``
  with the last exit taking what is left, so ``sum_t p_t = 1``.
* **The loss goes through the one head once an exit, under weights the
  gate learns** (:func:`ouro_lm_loss`): the mean over the positions that
  have a target of ``sum_t p_t nll_t - beta H(p)``, the paper's
  first-stage objective (the expected task loss under the exit
  distribution less an entropy term against a uniform prior). The exits
  are stacked on the batch axis and go through
  ``losses.weighted_chunked_causal_lm_loss`` as one sweep.

Serving a looped model (a K/V cache a pass a layer, exit by the gate's
threshold, ``early_exit_threshold``) is not in this file.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import profiler
from .decoder import (RMSNorm, gated_mlp, lm_head, looped_decoder_layers,
                      project_heads, project_out, rotary_embedding,
                      token_embedding, xla_attention)
from .losses import weighted_chunked_causal_lm_loss


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    dim: int = 2048
    num_layers: int = 48             # built once
    total_ut_steps: int = 4          # and run this many times
    num_heads: int = 16              # as many K/V heads
    head_dim: int = 128
    rope_theta: float = 1e6
    mlp_hidden: int = 5632
    norm_eps: float = 1e-6
    # The entropy term's weight in ``ouro_lm_loss``.
    exit_entropy_beta: float = 0.1
    dtype: Any = jnp.bfloat16
    # jax.checkpoint each application of a block in the backward pass.
    remat: bool = False


OURO_2_6B = OuroConfig()
# Two layers, the published four passes.
OURO_TINY = OuroConfig(vocab_size=512, dim=64, num_layers=2, num_heads=2,
                       head_dim=32, mlp_hidden=160)


def exit_log_distribution(gate_logits):
    """``log p`` of :func:`exit_distribution`, from log-sigmoids: finite
    however sure a gate is."""
    gate_logits = gate_logits.astype(jnp.float32)
    nothing = jnp.zeros_like(gate_logits[:1])
    # log(1 - lam_j) summed over the passes before t; the last exit has
    # no gate of its own.
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits[:-1]), axis=0)
    leaves = jax.nn.log_sigmoid(gate_logits[:-1])
    return jnp.concatenate([nothing, stayed], axis=0) \
        + jnp.concatenate([leaves, nothing], axis=0)


def exit_distribution(gate_logits):
    """``p[t]`` from the gates' logits ``(T, ...)``, pass-major: ``p_t =
    lam_t prod_{j<t} (1 - lam_j)`` with ``lam = sigmoid(logit)``, and the
    last exit takes what is left (``p_T = prod_{j<T} (1 - lam_j)``; the
    last pass's own logit is not read), so the ``T`` entries sum to 1.
    ``lam = 1/2`` everywhere gives ``(1/2, 1/4, 1/8, 1/8)``; one pass
    gives 1."""
    return jnp.exp(exit_log_distribution(gate_logits))


def exit_entropy(log_p):
    """``H(p) = -sum_t p_t log p_t`` from ``log p`` ``(T, ...)``: ``ln
    T`` where the exits are uniform, 0 where one carries every weight."""
    return -(jnp.exp(log_p) * log_p).sum(axis=0)


class OuroAttention(nn.Module):
    """Causal multi-head attention, the rotary embedding over the whole
    head. ``attention_fn(q, k, v, None)`` carries the band and runs under
    ``hvd.attn.full``."""
    config: OuroConfig
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        q, k, v = (project_heads(cfg.num_heads, cfg.head_dim, cfg.dtype,
                                 name)(x) for name in ("wq", "wk", "wv"))
        q = rotary_embedding(q, cfg.rope_theta, positions)
        k = rotary_embedding(k, cfg.rope_theta, positions)
        with jax.named_scope(profiler.SCOPE_ATTN_FULL):
            ctx = self.attention_fn(q, k, v, None)
        return project_out(cfg.dim, cfg.dtype)(ctx)


class OuroBlock(nn.Module):
    """``h = x + N2(Attn(N1(x)))``; ``out = h + N4(MLP(N3(h)))``. Returns
    ``(out, None)``, as ``decoder``'s stacks call a block."""
    config: OuroConfig
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.dtype, name=name)

        mixed = OuroAttention(cfg, self.attention_fn, name="attention")(
            norm("input_layernorm")(x), positions)
        h = x + norm("input_layernorm_2")(mixed)
        out = gated_mlp(norm("post_attention_layernorm")(h), cfg.mlp_hidden,
                        cfg.dtype)
        return h + norm("post_attention_layernorm_2")(out), None


class OuroLM(nn.Module):
    """Token embedding, the stack run ``total_ut_steps`` times, the exit
    gate on every pass's normed states, the untied head.

    ``attention_fn(q, k, v, mask)`` serves every layer; the default is
    the plain XLA softmax. On the chip pass
    ``make_attention_fn(causal=True)``, whose own shape rule picks the
    streamed kernels.

    With ``return_hidden`` returns ``(states, gate_logits)`` for
    :func:`ouro_lm_loss`: every pass's normed states ``(T, B, S, dim)``
    and the gate's logits ``(T, B, S)`` in float32, pass-major. Otherwise
    ``(logits, gate_logits)``, ``logits`` the expectation of the exits'
    logits under :func:`exit_distribution`."""
    config: OuroConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False):
        cfg = self.config
        layers = [dict(attention_fn=self.attention_fn or xla_attention())
                  ] * cfg.num_layers
        states = looped_decoder_layers(
            cfg, OuroBlock, layers, token_embedding(cfg)(input_ids),
            cfg.total_ut_steps, positions)
        with jax.named_scope(profiler.SCOPE_LOOP_EXIT):
            states = jnp.stack(states)
            # One gate for every pass, in float32: an exit's weight hangs
            # on small differences between its logits.
            gate_logits = nn.Dense(
                1, dtype=jnp.float32, param_dtype=jnp.float32,
                kernel_init=nn.initializers.normal(0.02),
                name="early_exit_gate")(states)[..., 0]
        if return_hidden:
            return states, gate_logits
        # The head is linear and has no bias: the expectation of the
        # exits' logits is the head of the expectation of their states.
        weights = exit_distribution(gate_logits).astype(cfg.dtype)
        return lm_head(cfg)(jnp.einsum("tbs,tbsd->bsd", weights,
                                       states)), gate_logits


def ouro_lm_loss(states, gate_logits, head_kernel, input_ids,
                 num_chunks: int = 8, beta: float = 0.1):
    """The mean over the ``B (S - 1)`` positions that have a target of
    ``sum_t p_t nll_t - beta H(p)``: ``p = exit_distribution(gate_logits)``,
    ``nll_t`` the next token's negative log-likelihood from pass ``t``'s
    states through ``head_kernel`` (``params["lm_head"]["kernel"]``),
    ``H(p) = -sum_t p_t log p_t``. ``states`` ``(T, B, S, dim)`` and
    ``gate_logits`` ``(T, B, S)`` are ``OuroLM``'s with ``return_hidden``.

    The ``T`` exits go through the head as ONE sweep, stacked on the batch
    axis with ``p`` as their weights
    (``weighted_chunked_causal_lm_loss``): the gate's gradient comes
    through the weights, the stack's through every pass's states. With
    one pass ``p`` is 1, the loss the plain next-token mean and the gate's
    gradient zero.

    Returns ``(loss, exits)``: ``exits`` is ``T + 1`` float32 numbers,
    the mean exit distribution over those positions and then their mean
    entropy (``ln T`` is uniform; near 0 one exit carries every weight)."""
    t, b, s, d = states.shape
    with jax.named_scope(profiler.SCOPE_LOOP_EXIT):
        log_p = exit_log_distribution(gate_logits)
        p = jnp.exp(log_p)
        # Over the positions that have a target, as the task loss is.
        entropy = exit_entropy(log_p)[:, :-1].mean()
        exits = jnp.concatenate([p[:, :, :-1].mean(axis=(1, 2)),
                                 entropy[None]])
        stacked, weights = states.reshape(t * b, s, d), p.reshape(t * b, s)
    task = weighted_chunked_causal_lm_loss(
        stacked, head_kernel, input_ids, weights, num_chunks=num_chunks)
    return task - beta * entropy, exits
