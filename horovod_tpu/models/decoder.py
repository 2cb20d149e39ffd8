"""The parts every decoder configuration is built from.

``LlamaLM``, ``MoeLM``, ``SmallThinkerLM``, ``OlmoHybridLM``, ``LagunaLM``,
``Lfm2LM``, ``JoyAILM`` and ``OuroLM`` import this module and ``losses``,
and no model file imports another (``moe_lm.py`` extends ``llama.py``). What
a configuration's own file holds: ``docs/decoder-configurations.md``.

Apart from the four classes, everything here is a **plain function
called inside the caller's ``@nn.compact`` method**: the flax modules it
makes live in the calling module's scope, so a function adds no level to
a parameter's path (``layer_3/router/kernel``) nor to an operation's
name (``layer_3/hvd.moe.dispatch/...``), and the callers' checkpoints,
the benchmark's readers and the compiled programs do not see it.

* leaves and layers: :class:`RMSNorm`, :func:`rotary_embedding`,
  :class:`Kernel`, :class:`Leaf`, :func:`linear`, :func:`project_heads`,
  :func:`project_out`, :func:`gated_mlp` / :class:`GatedMLP`;
* the held sparse layer: :func:`router_logits`, :func:`held_experts`;
* the LM's skeleton: :func:`one_entry_a_layer`, :func:`xla_attention`,
  :func:`token_embedding`, :func:`rematerialised`, :func:`decoder_layers`,
  :func:`looped_decoder_layers`, :func:`stack_loads`, :func:`lm_head`.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import profiler
from ..ops.attention import FLASH_RESIDUAL_NAMES, make_attention_fn
from ..parallel.moe import moe_apply_held


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        # All-f32 chain, deliberately: a bf16-application variant (f32
        # stats, bf16 multiply) measured SLOWER on v5e (56.0k vs 59.3k
        # tok/s Llama-300M — it splits the fused norm chain) and loosened
        # sp-parity tolerances. XLA fuses this form fully.
        x32 = x.astype(jnp.float32)
        norm = x32 * jnp.reciprocal(
            jnp.sqrt(jnp.mean(x32 ** 2, axis=-1, keepdims=True) + self.eps))
        return (norm * scale).astype(self.dtype)


def rotary_embedding(x, theta: float, positions=None, rotary_dim=None,
                     inv_freq=None, scale=None):
    """Apply RoPE to (B, S, H, D). ``positions`` are the GLOBAL token
    positions of the rows — defaults to 0..S-1. Shape (S,) rotates every
    batch row alike (training, whole-batch decode); shape (B, S) gives
    each sequence its own positions (the serving tier's continuous
    batches mix sequences at heterogeneous decode positions). Under
    sequence parallelism each shard must pass its own global offsets
    (e.g. ``axis_index * S_local + arange(S_local)``) or every shard
    would rotate as if it held the sequence start.

    The defaults rotate the whole head at ``theta ** (-i / half)``. A
    partial rotary embedding gives ``rotary_dim`` < D: the first
    ``rotary_dim`` entries of each head are rotated (rotate-half inside
    them), the rest pass through. ``inv_freq`` (``rotary_dim // 2``
    numbers) takes the place of the frequencies ``theta`` gives, for a
    scaled embedding whose frequencies are blended (YaRN:
    ``models/laguna.py``); ``scale`` multiplies cos and sin (YaRN's
    attention factor)."""
    b, s, h, d = x.shape
    rotated = d if rotary_dim is None else rotary_dim
    half = rotated // 2
    if inv_freq is None:
        freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    else:
        freqs = np.asarray(inv_freq, np.float32)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    # Angles/cos/sin in f32 (positional phase must not quantize: at
    # position 64k a bf16 angle would be off by whole radians), then the
    # APPLICATION runs in the activation dtype — the rotation factors are
    # in [-1, 1] where bf16 is at its densest, and the f32 elementwise
    # over (B, S, H, D) this replaces was ~8% of the Llama-300M step
    # (XProf round 3).
    angles = positions.astype(jnp.float32)[..., :, None] * freqs
    # (S, half) rotates every batch row alike, (B, S, half) each its own.
    at = (None, slice(None), None) if angles.ndim == 2 \
        else (slice(None), slice(None), None)

    def table(fn):
        values = fn(angles) if scale is None else fn(angles) * scale
        return values[at].astype(x.dtype)

    cos, sin = table(jnp.cos), table(jnp.sin)
    x1, x2 = x[..., :half], x[..., half:rotated]
    parts = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if rotated < d:
        parts.append(x[..., rotated:])
    return jnp.concatenate(parts, axis=-1)


class Kernel(nn.Module):
    """One float32 matrix under the leaf name ``kernel``, as ``nn.Dense``
    names its own."""
    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(0.02), self.shape,
                          jnp.float32)


class Leaf(nn.Module):
    """One float32 parameter under a leaf name of the usual vocabulary
    (``kernel``, ``scale``), as ``nn.Dense`` and ``RMSNorm`` name theirs."""
    leaf: str
    shape: Tuple[int, ...]
    init: Callable

    @nn.compact
    def __call__(self):
        return self.param(self.leaf, self.init, self.shape, jnp.float32)


def linear(features, dtype, name):
    """A product with a float32 ``kernel`` and no bias, computed in
    ``dtype``."""
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, name=name)


def project_heads(heads, head_dim, dtype, name):
    """An attention's ``wq`` / ``wk`` / ``wv``: :func:`linear` with the
    result split a head, ``(..., heads, head_dim)``, and the kernel
    ``(dim, heads, head_dim)``."""
    return nn.DenseGeneral(features=(heads, head_dim), axis=-1,
                           use_bias=False, dtype=dtype,
                           param_dtype=jnp.float32, name=name)


def project_out(dim, dtype, name="wo"):
    """An attention's ``wo``: the context's ``(heads, head_dim)`` axes
    contracted against a ``(heads, head_dim, dim)`` kernel."""
    return nn.DenseGeneral(features=dim, axis=(-2, -1), use_bias=False,
                           dtype=dtype, param_dtype=jnp.float32, name=name)


def gated_mlp(h, hidden, dtype):
    """``w_down(silu(w_gate h) * (w_up h))`` with the three kernels in
    the caller's scope: a block's dense MLP."""
    return linear(h.shape[-1], dtype, "w_down")(
        nn.silu(linear(hidden, dtype, "w_gate")(h))
        * linear(hidden, dtype, "w_up")(h))


class GatedMLP(nn.Module):
    """:func:`gated_mlp` under a name of its own: a sparse model's dense
    layer (``mlp``) and its shared expert (``shared``)."""
    hidden: int
    dtype: Any

    @nn.compact
    def __call__(self, h):
        return gated_mlp(h, self.hidden, self.dtype)


def router_logits(rows, num_experts):
    """``rows`` (N, D) times the ``router`` kernel, in float32: which
    experts a token gets is decided on small differences between logits.
    Apart from :func:`held_experts` because a block chooses what the
    router reads (SmallThinker: the un-normed input, ahead of attention)
    and what it traces between the two (a shared expert)."""
    return rows.astype(jnp.float32) @ Kernel(
        (rows.shape[-1], num_experts), name="router")()


def held_experts(expert_fn, rows, logits, held, hidden, num_selected, route):
    """The routed experts this device holds, applied to ``rows`` (N, D):
    ``w_gate`` / ``w_up`` ``(len(held), D, hidden)`` and ``w_down``
    ``(len(held), hidden, D)`` through
    ``parallel.moe.moe_apply_held(expert_fn, ...)``, which routes over all
    of ``logits``' experts by ``route`` and returns ``(routed, load)``:
    the part of the layer's result the held experts give, (N, D), and the
    assignments each of them received. The routing rule is built where
    the block is (``softmax_top_k``, ``sigmoid_top_k(bias)``)."""
    d = rows.shape[-1]
    experts = {
        "w_gate": Kernel((len(held), d, hidden), name="w_gate")(),
        "w_up": Kernel((len(held), d, hidden), name="w_up")(),
        "w_down": Kernel((len(held), hidden, d), name="w_down")(),
    }
    return moe_apply_held(expert_fn, experts, rows, logits, held,
                          num_selected, route=route)


def one_entry_a_layer(owner, cfg, *fields):
    """Raises unless each of ``cfg``'s per-layer ``fields`` has an entry
    for each of ``cfg.num_layers`` layers."""
    for name in fields:
        if len(getattr(cfg, name)) < cfg.num_layers:
            raise ValueError(f"{owner}: {name} needs an entry for each of "
                             f"{cfg.num_layers} layers")


def xla_attention(window=None):
    """A decoder LM's ``attention_fn`` where none is given: causal, within
    ``window`` where there is one, the plain XLA softmax. On the chip a
    caller passes ``make_attention_fn(causal=True, window=...)``, whose
    own shape rule picks the kernels."""
    return make_attention_fn(causal=True, use_flash=False, window=window)


def token_embedding(cfg):
    """``tok_embeddings``: ``cfg.vocab_size`` float32 rows ``cfg.dim``
    wide."""
    return nn.Embed(cfg.vocab_size, cfg.dim, param_dtype=jnp.float32,
                    name="tok_embeddings")


def rematerialised(cfg, block):
    """``block``, checkpointed where ``cfg.remat`` (``jax.checkpoint`` a
    block). A checkpoint keeps the block's input and, of what the block
    computes, the flash kernel's output and row log-sum-exp
    (``ops.attention.FLASH_RESIDUAL_NAMES``: (B, S, H * Dv) in the
    block's dtype and (B, H, S) float32 a call); everything else (norms,
    projections, rotary, q, k and v, gates, the expert layer, the MLP) is
    computed again in the backward pass. With both of the forward
    kernel's results kept, the recomputation does not call it: a step
    holds one forward call beside each dq / dk-dv pair."""
    if not cfg.remat:
        return block
    keep = jax.checkpoint_policies.save_only_these_names(
        *FLASH_RESIDUAL_NAMES)
    return nn.remat(block, policy=keep)


def decoder_layers(cfg, block, layers, x, *args):
    """The embedded tokens ``x`` cast to ``cfg.dtype``, through ``block``
    as ``layer_0`` .. ``layer_{n-1}`` and then ``final_norm``. ``layers``
    holds one dict a layer: what that layer's block is built with beside
    ``cfg``; each is called with ``(x, *args)`` and returns ``(x, aux)``.
    Returns the normed hidden states and the list of every layer's
    ``aux`` (an expert layer's load, ``None`` from a dense one)."""
    x = x.astype(cfg.dtype)
    block_cls = rematerialised(cfg, block)
    aux = []
    for i, built_with in enumerate(layers):
        x, layer_aux = block_cls(cfg, name=f"layer_{i}", **built_with)(
            x, *args)
        aux.append(layer_aux)
    return RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x), aux


def looped_decoder_layers(cfg, block, layers, x, passes, *args):
    """A stack that is BUILT ONCE AND RUN ``passes`` TIMES: the embedded
    tokens ``x`` cast to ``cfg.dtype``, then ``passes`` times through
    ``block`` as ``layer_0`` .. ``layer_{n-1}`` and ``final_norm``, each
    pass reading the normed states the pass before it wrote. ``layers`` and
    ``args`` as :func:`decoder_layers` has them (a block's ``aux`` is
    dropped). Returns the list of every pass's normed states.

    A parameter's path is ``layer_i/...`` or ``final_norm/scale`` whatever
    ``passes`` is: one leaf a shared parameter, whose gradient autodiff
    sums over the passes. With ``cfg.remat`` each application of a block
    is a checkpoint of its own (:func:`rematerialised`): it keeps its
    input and its flash kernel's output and row log-sum-exp, ``passes``
    times a layer, and recomputes the rest in the backward pass.

    The passes are UNROLLED IN PYTHON, not a ``lax.scan`` / ``nn.scan``:
    the device trace shows a ``while`` as one operation, so a scanned loop
    would hide the flash kernels and every scope inside it from every
    reader of the benchmark. Each pass runs under ``hvd.loop.pass``, the
    same name every pass."""
    x = x.astype(cfg.dtype)
    block_cls = rematerialised(cfg, block)
    blocks = [block_cls(cfg, name=f"layer_{i}", **built_with)
              for i, built_with in enumerate(layers)]
    final_norm = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")
    states = []
    for _ in range(passes):
        with jax.named_scope(profiler.SCOPE_LOOP_PASS):
            for layer in blocks:
                x, _ = layer(x, *args)
            x = final_norm(x)
        states.append(x)
    return states


def stack_loads(loads, held):
    """``load[sparse layer, held expert]`` from the sparse layers' loads
    (the ``None`` of a dense layer left out); no row where no layer is
    sparse."""
    loads = [load for load in loads if load is not None]
    return jnp.stack(loads) if loads else jnp.zeros((0, len(held)),
                                                    jnp.int32)


def lm_head(cfg):
    """The untied head ``lm_head``, computed in ``cfg.dtype``."""
    return linear(cfg.vocab_size, cfg.dtype, "lm_head")
