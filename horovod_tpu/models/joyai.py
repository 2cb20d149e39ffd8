"""JoyAI-LLM-Flash: a causal expert LM with multi-head latent attention
and a multi-token-prediction module.

The architecture of ``jdopensource/JoyAI-LLM-Flash`` (48B parameters,
2.7B active; widths from its public ``config.json``, whose keys are
DeepSeek-V3's), built from ``models/decoder.py``'s parts. What sets it
apart:

* **Multi-head latent attention** on every layer. Queries and keys come
  through low-rank paths, each normed in the middle: ``c_q =
  RMSNorm(z W_qa)`` (1536 wide), ``[q_n | q_r] = c_q W_qb`` a head (128 |
  64); ``[c_kv | k_r] = z W_kva`` (512 | 64), ``c_kv`` normed, ``[k_n |
  v] = c_kv W_kvb`` a head (128 | 128). ``k_r`` is ONE 64-wide vector a
  token, rotated and read by every head. A head attends with ``q = [q_n |
  RoPE(q_r)]`` and ``k = [k_n | RoPE(k_r)]``, **192 wide**, over values
  **128 wide**, at the scale ``192 ** -0.5``: ``attention_fn(q, k, v,
  None)`` takes the two widths (``ops.attention.flash_attention``: both
  kernel families read ``v``'s width from ``v``).
* **The rotation.** The published model rotates the interleaved pairs
  ``(x[2i], x[2i + 1])`` (``rope_interleave``). Here the 64 rotary
  columns are de-interleaved (evens, then odds: a fixed permutation) and
  rotated by halves through ``rotary_embedding``: q and k are permuted
  alike and only ``q . k`` is read, so the scores are the published
  ones. A departure in the arithmetic's order, not in the function
  (``tests/test_joyai.py`` holds the two against each other).
* **The FFNs.** The first ``num_dense_layers`` are dense (SiLU-gated,
  7168 wide); every other routes: ``s = sigmoid(z W_r)`` in float32, a
  token's 8 of 256 experts are the largest of ``s + b`` (``b`` the expert
  bias, in the choice only), ``w_e = s_e / (sum of the chosen s +
  1e-20)``, and ``F(z) = S(z) + 2.5 sum_e w_e E_e(z)`` with one shared
  expert ``S`` every token passes; ``S`` and ``E_e`` SiLU-gated MLPs 768
  wide. The bias is the leaf ``expert_bias/kernel``, read under
  ``stop_gradient``: leave it out of weight decay
  (``models.lfm2.decay_mask``).
* **The multi-token-prediction module** (DeepSeek-V3, arXiv:2412.19437,
  section 2.2; depth 1): ``u_i = W_eh [RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(g_i)]`` with ``g`` the main model's final-norm output, one more
  block of the sparse kind over ``u``, a norm, then THE MAIN MODEL'S head
  and embedding (the same two leaves, not copies); it predicts
  ``t_{i+2}``. :func:`joyai_lm_loss` is ``L_main + mtp_weight x L_MTP``,
  both through ``chunked_causal_lm_loss`` (``ahead`` 1 and 2). The last
  position has no ``t_{i+1}``: it is given ``t_0``, which under the
  causal band only itself reads, and it has no target.

**The experts held.** ``experts_held`` names the routed experts whose
weights this device has (``SmallThinkerConfig.experts_held``): a sparse
block routes over all ``num_experts`` and adds the part the held experts
give; attention, router, bias, shared expert, the dense layer and the
module's projection are whole on every device. The routed parts of
disjoint shares, with everything else counted once, add up to the whole
layer (``tests/test_joyai.py``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import profiler
from ..parallel.moe import grouped_gated_mlp, sigmoid_top_k
from .decoder import (GatedMLP, Leaf, RMSNorm, decoder_layers, held_experts,
                      linear, lm_head, project_heads, project_out,
                      rematerialised, rotary_embedding, router_logits,
                      stack_loads, token_embedding, xla_attention)
from .losses import chunked_causal_lm_loss


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    dim: int = 2048
    num_layers: int = 40
    # The first ``num_dense_layers`` FFNs are dense, the others route.
    num_dense_layers: int = 1
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 3.2e7
    mlp_hidden: int = 7168           # the dense layers'
    num_experts: int = 256           # the router's width
    num_selected: int = 8
    expert_hidden: int = 768
    shared_hidden: int = 768
    routed_scale: float = 2.5
    # In the normalisation of the routing weights.
    weight_sum_eps: float = 1e-20
    # Multi-token-prediction modules after the last layer: 0 or 1.
    mtp_layers: int = 1
    # Routed expert ids whose weights this device holds; None = all.
    experts_held: Optional[Tuple[int, ...]] = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # jax.checkpoint each block in the backward pass (LlamaConfig.remat).
    remat: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.experts_held is None
                else tuple(self.experts_held))


JOYAI_LLM_FLASH = JoyAIConfig()
# A leading dense layer, a sparse one and the module; q/k 48 wide over v
# 32, the published 3:2 of the two widths; 2 of 8 experts a token.
JOYAI_TINY = JoyAIConfig(
    vocab_size=512, dim=64, num_layers=2, num_heads=2, q_lora_rank=48,
    kv_lora_rank=24, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, mlp_hidden=160, num_experts=8, num_selected=2,
    expert_hidden=48, shared_hidden=48)


def deinterleave(x):
    """The last axis' even entries, then its odd ones: the fixed
    permutation after which rotating halves rotates what were the
    interleaved pairs ``(x[2i], x[2i + 1])``."""
    *lead, width = x.shape
    return x.reshape(*lead, width // 2, 2).swapaxes(-1, -2).reshape(x.shape)


class LatentAttention(nn.Module):
    """Multi-head latent attention (module docstring): the expanded q, k
    (``qk_head_dim`` wide) and v (``v_head_dim``) go to
    ``attention_fn(q, k, v, None)``, which carries the causal band, under
    ``hvd.attn.latent``; everything before it under
    ``hvd.attn.latent.proj``; ``wo`` under neither."""
    config: JoyAIConfig
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        nope, rope, heads = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                             cfg.num_heads)
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype,  # noqa: E731
                                    name=name)
        rotate = lambda r: rotary_embedding(  # noqa: E731
            deinterleave(r), cfg.rope_theta, positions)
        with jax.named_scope(profiler.SCOPE_ATTN_LATENT_PROJ):
            q = project_heads(heads, cfg.qk_head_dim, cfg.dtype, "wq_b")(
                norm("q_a_norm")(
                    linear(cfg.q_lora_rank, cfg.dtype, "wq_a")(x)))
            c_kv, k_rope = jnp.split(
                linear(cfg.kv_lora_rank + rope, cfg.dtype, "wkv_a")(x),
                [cfg.kv_lora_rank], axis=-1)
            k_nope, v = jnp.split(
                project_heads(heads, nope + cfg.v_head_dim, cfg.dtype,
                              "wkv_b")(norm("kv_a_norm")(c_kv)),
                [nope], axis=-1)
            q = jnp.concatenate(
                [q[..., :nope], rotate(q[..., nope:])], axis=-1)
            # One rotary key a token, rotated once and read by every head.
            k_rope = rotate(k_rope[:, :, None, :])
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_rope, k_nope.shape[:-1] + (rope,))], axis=-1)
        with jax.named_scope(profiler.SCOPE_ATTN_LATENT):
            ctx = self.attention_fn(q, k, v, None)
        return project_out(cfg.dim, cfg.dtype)(ctx)


class JoyAIBlock(nn.Module):
    """``h = x + MLA(norm(x))``; ``out = h + F(norm(h))``, ``F`` the dense
    MLP or the shared expert plus ``routed_scale`` times the chosen routed
    experts held here. Returns ``(out, load)``, ``load`` the assignments
    each held expert received (``None`` from a dense layer)."""
    config: JoyAIConfig
    sparse: bool
    attention_fn: Callable

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.config
        b, s, d = x.shape
        h = x + LatentAttention(cfg, self.attention_fn, name="attention")(
            RMSNorm(cfg.norm_eps, cfg.dtype, name="attention_norm")(x),
            positions)
        z = RMSNorm(cfg.norm_eps, cfg.dtype, name="ffn_norm")(h)
        if not self.sparse:
            return h + GatedMLP(cfg.mlp_hidden, cfg.dtype, name="mlp")(z), \
                None
        rows = z.reshape(b * s, d)
        logits = router_logits(rows, cfg.num_experts)
        bias = Leaf("kernel", (cfg.num_experts,),
                    nn.initializers.normal(0.01), name="expert_bias")()
        with jax.named_scope(profiler.SCOPE_MOE_SHARED):
            shared = GatedMLP(cfg.shared_hidden, cfg.dtype, name="shared")(z)
        routed, load = held_experts(
            functools.partial(grouped_gated_mlp, activation=jax.nn.silu),
            rows, logits, cfg.held(), cfg.expert_hidden, cfg.num_selected,
            route=sigmoid_top_k(bias, eps=cfg.weight_sum_eps))
        return h + shared + cfg.routed_scale * routed.reshape(b, s, d), load


class MTPModule(nn.Module):
    """One multi-token-prediction module: ``u = W_eh [norm(e) ;
    norm(g)]``, a sparse block over ``u``, a norm. ``e`` is the embedding
    of each position's NEXT token and ``g`` the main model's final-norm
    output; what comes out goes through the main model's head. Returns
    ``(hidden, load)``."""
    config: JoyAIConfig
    attention_fn: Callable

    @nn.compact
    def __call__(self, e, g, positions=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.norm_eps, cfg.dtype,  # noqa: E731
                                    name=name)
        u = linear(cfg.dim, cfg.dtype, "eh_proj")(
            jnp.concatenate([norm("enorm")(e), norm("hnorm")(g)], axis=-1))
        u, load = rematerialised(cfg, JoyAIBlock)(
            cfg, sparse=True, attention_fn=self.attention_fn, name="block")(
            u, positions)
        return norm("norm")(u), load


class JoyAILM(nn.Module):
    """Token embedding, the blocks, a final RMSNorm, an untied head, and
    after them the multi-token-prediction module.

    ``attention_fn(q, k, v, mask)`` serves every layer with q and k
    ``qk_head_dim`` wide and v ``v_head_dim``; the default is the plain
    XLA softmax. On the chip pass ``make_attention_fn(causal=True)``,
    whose own shape rule picks the kernels.

    Returns ``(logits, mtp_logits, load)``, or with ``return_hidden``
    ``(hidden, mtp_hidden, load)`` for :func:`joyai_lm_loss`;
    ``mtp_logits[:, i]`` scores ``t_{i+2}`` (``None`` where
    ``mtp_layers`` is 0). ``load[sparse layer, held expert]`` counts the
    assignments each held expert received, the module's block last (a
    dense layer has no row)."""
    config: JoyAIConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False):
        cfg = self.config
        if cfg.mtp_layers not in (0, 1):
            raise ValueError("JoyAILM: mtp_layers is 0 or 1, got "
                             f"{cfg.mtp_layers}")
        attention_fn = self.attention_fn or xla_attention()
        layers = [dict(sparse=i >= cfg.num_dense_layers,
                       attention_fn=attention_fn)
                  for i in range(cfg.num_layers)]
        embed = token_embedding(cfg)
        x, loads = decoder_layers(cfg, JoyAIBlock, layers, embed(input_ids),
                                  positions)
        mtp = None
        if cfg.mtp_layers:
            with jax.named_scope(profiler.SCOPE_MTP):
                # Position i is given t_{i+1}; the last is given t_0, which
                # no position that has a target reads.
                following = embed(jnp.roll(input_ids, -1, axis=1)).astype(
                    cfg.dtype)
                mtp, load = MTPModule(cfg, attention_fn, name="mtp")(
                    following, x, positions)
            loads.append(load)
        load = stack_loads(loads, cfg.held())
        if return_hidden:
            return x, mtp, load
        head = lm_head(cfg)
        return head(x), None if mtp is None else head(mtp), load


def joyai_lm_loss(hidden, mtp_hidden, head_kernel, input_ids,
                  num_chunks: int = 8, mtp_weight: float = 0.1):
    """``L_main + mtp_weight x L_MTP``: the next token's mean
    cross-entropy from ``hidden`` and, from ``mtp_hidden``, the mean over
    the ``S - 2`` positions a sequence that have a token two ahead, both
    through ``chunked_causal_lm_loss`` and the one ``head_kernel``
    (``params["lm_head"]["kernel"]``), whose gradient then has two
    sources. The second pass runs under ``hvd.mtp``. ``mtp_hidden`` None
    or ``mtp_weight`` 0: the main loss alone."""
    loss = chunked_causal_lm_loss(hidden, head_kernel, input_ids,
                                  num_chunks=num_chunks)
    if mtp_hidden is None or not mtp_weight:
        return loss
    with jax.named_scope(profiler.SCOPE_MTP):
        return loss + mtp_weight * chunked_causal_lm_loss(
            mtp_hidden, head_kernel, input_ids, num_chunks=num_chunks,
            ahead=2)
