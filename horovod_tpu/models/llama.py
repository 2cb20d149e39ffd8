"""Decoder-only transformer LM (Llama-style: RMSNorm, rotary embeddings,
SwiGLU, grouped-query attention).

No reference-repo equivalent (2019-era); required by the rebuild's target
workloads (BASELINE.json config "Llama-3-8B — stress fused allreduce at LLM
gradient sizes"). TPU-first: bf16 activations / fp32 params, einsum
attention with the same ``attention_fn`` seam as BERT (flash / ring
attention plug in), static shapes. GQA K/V stay at ``num_kv_heads`` through
attention fns that declare ``supports_gqa`` (the flash kernel routes query
heads to their K/V group in the grid — no repeat); others get repeated K/V.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import profiler
from .decoder import (RMSNorm, gated_mlp, linear, project_heads, project_out,
                      rematerialised, rotary_embedding, token_embedding)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    ffn_hidden: int = 14336
    rope_theta: float = 500000.0
    max_seq_len: int = 8192
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # lm_head compute dtype; None = model dtype (bf16 — measured on v5e:
    # 215.4 vs 222.0 ms/step for f32, first-step loss identical to 4
    # decimals). Set jnp.float32 if downstream consumers of RAW logits
    # (perplexity eval, logit distillation) need full precision — the
    # in-tree losses upcast inside the lse reduction either way.
    head_dtype: Any = None
    # Rematerialize each block's activations in the backward pass
    # (jax.checkpoint): live activations drop from O(layers) to O(1)
    # layers' worth at ~1/3 extra FLOPs — the knob that lets sequence
    # length scale past what HBM holds at remat=False.
    remat: bool = False


LLAMA_8B = LlamaConfig()
LLAMA_1B = LlamaConfig(dim=2048, num_layers=16, num_heads=32, num_kv_heads=8,
                       ffn_hidden=8192)
# ~320M params: fits one 16 GB chip WITH f32 Adam state — the single-chip
# benchmark config. LLAMA_1B also trains single-chip by swapping the
# memory: adafactor (factored second moments) + chunked_causal_lm_loss
# runs 12.0k tok/s on a v5e (Adam moments alone would need ~8.8 GiB);
# Adam-state sharding across chips is the ZeRO-1 wrapper's job.
LLAMA_300M = LlamaConfig(vocab_size=32000, dim=1024, num_layers=16,
                         num_heads=16, num_kv_heads=8, ffn_hidden=4096)
LLAMA_TINY = LlamaConfig(vocab_size=512, dim=64, num_layers=2, num_heads=4,
                         num_kv_heads=2, ffn_hidden=128, max_seq_len=256)


class LlamaAttention(nn.Module):
    config: LlamaConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, positions=None, cache=None, cache_index=None):
        """``cache``/``cache_index``: autoregressive-decoding mode (see
        :func:`init_kv_cache`). The new K/V rows are written into the
        static-shape cache at ``cache_index`` and attention runs against
        the whole window under an explicit positional mask; returns
        ``(out, new_cache)``. Training mode (``cache=None``) is unchanged.
        """
        cfg = self.config
        head_dim = cfg.dim // cfg.num_heads
        q = rotary_embedding(
            project_heads(cfg.num_heads, head_dim, cfg.dtype, "wq")(x),
            cfg.rope_theta, positions)
        k = rotary_embedding(
            project_heads(cfg.num_kv_heads, head_dim, cfg.dtype, "wk")(x),
            cfg.rope_theta, positions)
        v = project_heads(cfg.num_kv_heads, head_dim, cfg.dtype, "wv")(x)
        out_proj = project_out(cfg.dim, cfg.dtype)

        if cache is not None:
            ctx, new_cache = _cached_attention(q, k, v, cache, cache_index)
            return out_proj(ctx), new_cache

        # flash_attention / reference_attention / ring_attention handle
        # grouped K/V heads natively (the flash grid routes each query
        # head to its group's K/V row — no repeated K/V copy in HBM; the
        # ring rotates Hkv-head blocks, Hkv/H the ICI bytes). Repeat only
        # for attention_fns that don't declare GQA support via a
        # ``supports_gqa`` attribute.
        gqa_native = (self.attention_fn is None
                      or getattr(self.attention_fn, "supports_gqa", False))
        if not gqa_native:
            from ..ops.attention import repeat_kv

            k, v = repeat_kv(q, k, v)
        if self.attention_fn is not None:
            ctx = self.attention_fn(q, k, v, None)
        else:
            from ..ops.attention import reference_attention

            ctx = reference_attention(q, k, v, causal=True)
        return out_proj(ctx)


# Trace-time switch for the Pallas decode-attention fast path. Default on:
# the kernel consumes the cache in the default major-to-minor layout, which
# frees XLA to keep the loop-carried cache d-minor and make the per-step
# one-row cache write a true in-place update (the XLA formulation forces a
# seq-minor layout whose one-row update rewrites the whole buffer —
# artifacts/decode_ceiling_r5.json). generate() classifies the variables'
# sharding (see classify_decode_sharding): heads-sharded-on-TP meshes ride
# the kernel through shard_map (``_DECODE_TP``); exotic shardings fall back
# to the einsum path, which GSPMD shards naturally.
_DECODE_KERNEL = True
# When set, single-token cached attention runs the kernel per-shard inside
# ``jax.shard_map``: (mesh, head_axis, batch_axis).
_DECODE_TP = None


@contextlib.contextmanager
def decode_kernel_disabled():
    """Within this context, single-token cached attention uses the plain
    XLA einsum path instead of the Pallas kernel (trace-time static)."""
    global _DECODE_KERNEL
    prev = _DECODE_KERNEL
    _DECODE_KERNEL = False
    try:
        yield
    finally:
        _DECODE_KERNEL = prev


@contextlib.contextmanager
def _decode_tp_override(value):
    global _DECODE_TP
    prev = _DECODE_TP
    _DECODE_TP = value
    try:
        yield
    finally:
        _DECODE_TP = prev


def decode_kernel_sharded(mesh, head_axis: str, batch_axis=None):
    """Within this context, single-token cached attention runs the Pallas
    kernel per-shard inside ``jax.shard_map`` over ``head_axis`` (the TP
    axis sharding attention heads), with the one-row cache write kept
    in-place per shard (trace-time static; see
    ``ops.decode_attention.sharded_decode_step``)."""
    return _decode_tp_override((mesh, head_axis, batch_axis))


def decode_path_context(path: str, mesh=None, head_axis=None,
                        batch_axis=None):
    """THE path -> trace-time-context switch, shared by ``_decode`` and
    the serving engine's compiled programs — one place decides what each
    classifier verdict means. ``"kernel"`` explicitly CLEARS any ambient
    TP context: the traced program must match its jit cache key, not
    whatever context the caller happens to hold."""
    if path == "kernel_tp":
        return decode_kernel_sharded(mesh, head_axis, batch_axis)
    if path == "kernel":
        return _decode_tp_override(None)
    return decode_kernel_disabled()


def _cached_attention(q, k, v, cache, cache_index):
    """Decode-mode attention: write the s new K/V rows at ``cache_index``,
    attend every query (global position ``cache_index + i``) over the full
    static window under ``key_pos <= q_pos``. Masked logits hit
    exp(-inf) = 0 exactly, so the softmax equals the one over only the
    valid prefix. Grouped-query: queries attend their K/V group directly
    (no repeated K/V in the cache).

    Four code paths, one semantics: single-token steps ride the Pallas
    decode kernel (see ``_DECODE_KERNEL`` above — it keeps the carried
    cache in a layout where the row write is in-place), per-shard inside
    ``shard_map`` when the TP mesh shards heads (``_DECODE_TP``); prefill
    at static index 0 attends over the FRESH rows so no matmul ever
    consumes the cache buffers (a dot on them would re-pin the seq-minor
    layout the kernel path exists to avoid); the general chunked-append
    form (traced or nonzero index with s > 1) keeps the reference
    masked-window einsum. Each path is labeled with a
    ``jax.named_scope("hvd.decode.<path>")`` so the chosen path is
    attributable from HLO metadata and profiler traces
    (``utils.comm_accounting.decode_path_markers``)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    # The cache is stored ROW-FLAT, (B, L, Hkv*D): the decode kernel then
    # consumes it with no reshape anywhere near the buffers (an XLA-side
    # split of the flat axis would re-open the layout question; an
    # in-kernel split of tiled minor dims is not Mosaic-legal).
    kc = k.astype(cache["k"].dtype)
    vc = v.astype(cache["v"].dtype)
    scale = 1.0 / np.sqrt(d)
    if "tables" in cache:
        # PAGED decode (hvd.serving): the cache entry is the shared
        # block pool plus this batch's block tables, and ``cache_index``
        # is the per-sequence position VECTOR (B,) — one batch mixes
        # sequences at heterogeneous decode positions (continuous
        # batching). Prefill never lands here: it runs on a contiguous
        # scratch cache and the engine scatters whole blocks into the
        # pool (serving.engine._paged_prefill).
        if s != 1:
            raise ValueError(
                f"paged cache is single-token decode only (s={s})")
        from ..ops.decode_attention import (
            paged_cache_write,
            paged_decode_attention,
            paged_gather_attention,
            sharded_paged_decode_step,
        )

        tables = cache["tables"]
        if _DECODE_KERNEL and _DECODE_TP is not None:
            mesh, head_axis, batch_axis = _DECODE_TP
            with jax.named_scope(profiler.decode_scope("paged_tp")):
                ctx, k_pool, v_pool = sharded_paged_decode_step(
                    q, kc, vc, cache["k"], cache["v"], tables,
                    cache_index, hkv, mesh=mesh, head_axis=head_axis,
                    batch_axis=batch_axis, sm_scale=scale)
        else:
            k_pool, v_pool = paged_cache_write(
                cache["k"], cache["v"], kc, vc, tables, cache_index)
            if _DECODE_KERNEL:
                with jax.named_scope(profiler.decode_scope("paged")):
                    ctx = paged_decode_attention(
                        q, k_pool, v_pool, tables, cache_index, hkv,
                        sm_scale=scale)
            else:
                # The gather-einsum fallback shares the einsum marker:
                # it IS the einsum path, reading through the tables.
                with jax.named_scope(profiler.decode_scope("einsum")):
                    ctx = paged_gather_attention(
                        q, k_pool, v_pool, tables, cache_index, hkv,
                        sm_scale=scale)
        return ctx, {"k": k_pool, "v": v_pool, "tables": tables}
    if s == 1 and _DECODE_KERNEL and _DECODE_TP is not None:
        # TP-sharded serving: cache-row write AND kernel run per-shard
        # inside shard_map — the outer dynamic_update_slice below never
        # touches the sharded cache buffers.
        from ..ops.decode_attention import sharded_decode_step

        mesh, head_axis, batch_axis = _DECODE_TP
        with jax.named_scope(profiler.decode_scope("kernel_tp")):
            ctx, k_cache, v_cache = sharded_decode_step(
                q, kc, vc, cache["k"], cache["v"], cache_index, hkv,
                mesh=mesh, head_axis=head_axis, batch_axis=batch_axis,
                sm_scale=scale)
        return ctx, {"k": k_cache, "v": v_cache}
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], kc.reshape(b, s, hkv * d), (0, cache_index, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], vc.reshape(b, s, hkv * d), (0, cache_index, 0))
    window = k_cache.shape[1]
    if s == 1 and _DECODE_KERNEL:
        from ..ops.decode_attention import decode_attention

        with jax.named_scope(profiler.decode_scope("kernel")):
            ctx = decode_attention(q, k_cache, v_cache, cache_index, hkv,
                                   sm_scale=scale)
        return ctx, {"k": k_cache, "v": v_cache}
    if s > 1 and isinstance(cache_index, int) and cache_index == 0:
        # Prefill at index 0: the valid window IS the fresh rows — no
        # matmul consumes the cache buffers (their layout must stay
        # friendly to the decode loop's row writes). Attend over the
        # CACHE-DTYPE rows (kc/vc), so prefill sees exactly the values
        # every later decode step reads back — one semantics across
        # paths even when the cache dtype quantizes.
        with jax.named_scope(profiler.decode_scope("prefill")):
            qg = q.reshape(b, s, hkv, group, d)
            logits = jnp.einsum("bshgd,blhd->bshgl", qg, kc).astype(
                jnp.float32) * scale
            causal = (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])
            logits = jnp.where(causal[None, :, None, None, :], logits,
                               jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            ctx = jnp.einsum("bshgl,blhd->bshgd", probs, vc)
        return ctx.reshape(b, s, h, d), {"k": k_cache, "v": v_cache}
    # General path (einsum over the 4D view; also the s == 1 path under
    # exotic multi-device sharding — see _DECODE_KERNEL above).
    with jax.named_scope(profiler.decode_scope("einsum")):
        qg = q.reshape(b, s, hkv, group, d)
        k4 = k_cache.reshape(b, window, hkv, d)
        v4 = v_cache.reshape(b, window, hkv, d)
        logits = jnp.einsum("bshgd,blhd->bshgl", qg, k4).astype(
            jnp.float32) * scale
        q_pos = cache_index + jnp.arange(s)
        key_pos = jnp.arange(window)
        mask = key_pos[None, :] <= q_pos[:, None]          # (s, window)
        logits = jnp.where(mask[None, :, None, None, :], logits,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        ctx = jnp.einsum("bshgl,blhd->bshgd", probs, v4).reshape(b, s, h, d)
    return ctx, {"k": k_cache, "v": v_cache}


def attention_sublayer(cfg, attention_fn, x, positions, cache, cache_index):
    """Pre-norm attention + residual, shared by ``LlamaBlock`` and
    ``MoeBlock`` so ONE place owns the cache protocol (plain function:
    flax submodules created here live in the calling module's compact
    scope, keeping the param names ``attention_norm``/``attention``).
    Returns ``(x, new_cache_or_None)``."""
    attn_in = RMSNorm(cfg.norm_eps, cfg.dtype, name="attention_norm")(x)
    attn = LlamaAttention(cfg, attention_fn=attention_fn, name="attention")
    if cache is None:
        return x + attn(attn_in, positions), None
    a, new_cache = attn(attn_in, positions, cache, cache_index)
    return x + a, new_cache


class LlamaBlock(nn.Module):
    config: LlamaConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, positions=None, cache=None, cache_index=None):
        cfg = self.config
        x, new_cache = attention_sublayer(cfg, self.attention_fn, x,
                                          positions, cache, cache_index)
        h = RMSNorm(cfg.norm_eps, cfg.dtype, name="ffn_norm")(x)
        out = x + gated_mlp(h, cfg.ffn_hidden, cfg.dtype)
        return out if cache is None else (out, new_cache)


class LlamaLM(nn.Module):
    """Causal LM: embeddings + blocks + tied-free output head."""

    config: LlamaConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, return_hidden=False,
                 cache=None, cache_index=None):
        """``positions``: global token positions of the local rows, shape
        (S,). Required under sequence parallelism (each shard passes its
        global offsets so RoPE rotates correctly); defaults to 0..S-1.
        ``return_hidden``: skip the lm_head and return the final-norm
        hidden states (B, S, dim) — pair with
        :func:`chunked_causal_lm_loss`.
        ``cache``/``cache_index``: autoregressive decoding — the rows are
        the tokens at global positions ``cache_index..cache_index+S-1``
        (RoPE positions default accordingly), the per-layer K/V land in
        the cache, and the call returns ``(logits, new_cache)``. Use
        :func:`init_kv_cache` + :func:`generate`."""
        cfg = self.config
        if cache is not None and positions is None:
            steps = jnp.arange(input_ids.shape[1])
            if getattr(cache_index, "ndim", 0):
                # Per-sequence positions (paged/serving decode): the
                # index is a (B,) vector, each row rotates at its own
                # global position.
                positions = cache_index[:, None] + steps
            else:
                positions = cache_index + steps
        x = token_embedding(cfg)(input_ids).astype(cfg.dtype)
        new_cache = {}
        block_cls = rematerialised(cfg, LlamaBlock)
        for i in range(cfg.num_layers):
            if cache is None:
                x = block_cls(cfg, attention_fn=self.attention_fn,
                              name=f"layer_{i}")(x, positions)
            else:
                # Decoding never needs remat (no backward pass).
                x, new_cache[f"layer_{i}"] = LlamaBlock(
                    cfg, attention_fn=self.attention_fn,
                    name=f"layer_{i}")(x, positions, cache[f"layer_{i}"],
                                       cache_index)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")(x)
        if return_hidden:
            # For chunked_causal_lm_loss: the caller applies the lm_head
            # chunk-by-chunk so the (B, S, V) logits never materialize.
            return x
        # Head matmul in head_dtype (default: model compute dtype; MXU
        # accumulates f32 internally) — see LlamaConfig.head_dtype.
        logits = linear(cfg.vocab_size, cfg.head_dtype or cfg.dtype,
                        "lm_head")(x)
        return logits if cache is None else (logits, new_cache)


def init_kv_cache(cfg, batch_size: int, max_len: int, dtype=None):
    """Static-shape per-layer K/V cache for autoregressive decoding:
    ``{layer_i: {"k"/"v": (B, max_len, num_kv_heads * head_dim)}}`` —
    each position's GQA heads stored ROW-FLAT so the Pallas decode kernel
    consumes the buffers with no reshape (see ``_cached_attention``; the
    einsum paths view the flat axis as (Hkv, D)). GQA pays off directly
    here: the cache holds ``num_kv_heads`` head rows, an H/Hkv memory
    saving over repeating K/V (the reason GQA exists). ``cfg`` is any
    config with dim/num_heads/num_kv_heads/num_layers (``LlamaConfig`` or
    ``MoeConfig``)."""
    dtype = dtype or cfg.dtype
    head_dim = cfg.dim // cfg.num_heads
    # Windows past the decode kernel's single-tile VMEM budget get
    # L-tiled; round them to a 128 multiple so a decent tile DIVISOR
    # exists (<= +6% extra masked rows; small windows stay exact — no
    # read amplification where a single tile serves anyway).
    if max_len > 1024:
        max_len = (max_len + 127) // 128 * 128
    shape = (batch_size, max_len, cfg.num_kv_heads * head_dim)
    return {
        f"layer_{i}": {"k": jnp.zeros(shape, dtype),
                       "v": jnp.zeros(shape, dtype)}
        for i in range(cfg.num_layers)
    }


@dataclasses.dataclass(frozen=True)
class DecodePath:
    """Verdict of :func:`classify_decode_sharding`: which single-token
    decode path :func:`generate` traces, and why. ``generate`` records
    its last verdict in ``LAST_DECODE_PATH`` so harnesses and bench rows
    can prove which path ran (the HLO-metadata twin is
    ``utils.comm_accounting.decode_path_markers``)."""

    path: str                       # "kernel" | "kernel_tp" | "einsum"
    reason: str
    mesh: Any = None
    head_axis: Optional[str] = None
    batch_axis: Optional[str] = None


#: Last :class:`DecodePath` chosen by :func:`generate` (None before any
#: call). Read-only attribution for harnesses; not used for dispatch.
LAST_DECODE_PATH: Optional[DecodePath] = None


def _multi_device(leaf) -> bool:
    sh = getattr(leaf, "sharding", None)
    if sh is None:
        return False
    try:
        return (len(sh.device_set) > 1
                and not sh.is_fully_replicated)
    except (AttributeError, TypeError):
        return True  # unknown sharding type: take the safe path


def classify_decode_sharding(variables, prompt_ids,
                             num_kv_heads: int) -> DecodePath:
    """Pick the single-token decode path from the variables' shardings.

    Three-way dispatch (the blanket ``sharded -> einsum`` fallback this
    replaces threw away a measured ~47%-of-step win exactly on the
    multi-chip serving path):

    * nothing is sharded over a multi-device mesh → ``"kernel"`` (the
      single-device Pallas fast path, as before);
    * the Megatron TP pattern — attention projections sharded on the
      heads dim only, all on ONE mesh axis whose size divides
      ``num_kv_heads``, batch replicated or sharded on one other axis —
      → ``"kernel_tp"``: attention is per-head independent, so the
      kernel runs per-shard inside ``shard_map``
      (``ops.decode_attention.sharded_decode_step``) with in-place
      per-shard cache-row writes;
    * anything exotic (sequence-sharded prompt, uneven head splits,
      mixed meshes, non-Named shardings) → ``"einsum"``, which GSPMD
      shards naturally.
    """
    from ..parallel.mesh import common_mesh, sharding_axes

    leaves = jax.tree_util.tree_leaves((variables, prompt_ids))
    if not any(_multi_device(leaf) for leaf in leaves):
        return DecodePath("kernel", "replicated: single-device kernel")
    mesh = common_mesh((variables, prompt_ids))
    if mesh is None:
        return DecodePath(
            "einsum", "unknown sharding types or mixed meshes")

    # Megatron TP pattern: wq/wk/wv kernels (dim, heads, head_dim) may
    # shard ONLY dim 1, wo (heads, head_dim, dim) only dim 0 — all on
    # one axis.
    head_axes = set()
    clean = True

    def visit(path, leaf):
        nonlocal clean
        names = {getattr(p, "key", str(p)) for p in path}
        if "kernel" not in names:
            return
        proj = names & {"wq", "wk", "wv", "wo"}
        if not proj:
            return
        axes = sharding_axes(leaf)
        if axes is None:
            clean = _multi_device(leaf) is False and clean
            return
        head_dim = 0 if "wo" in proj else 1
        for i, dim_axes in enumerate(axes):
            if i == head_dim:
                if len(dim_axes) > 1:
                    clean = False
                head_axes.update(dim_axes)
            elif dim_axes:
                clean = False

    jax.tree_util.tree_map_with_path(visit, variables)
    if not clean:
        return DecodePath(
            "einsum", "attention params sharded off the heads dim")
    if len(head_axes) != 1:
        return DecodePath(
            "einsum",
            "attention heads not sharded on exactly one mesh axis "
            f"(axes={sorted(head_axes)})")
    (head_axis,) = head_axes
    tp = mesh.shape[head_axis]
    if num_kv_heads % tp:
        return DecodePath(
            "einsum", f"uneven head split: Hkv ({num_kv_heads}) % "
            f"tp ({tp}) != 0")

    batch_axis = None
    if _multi_device(prompt_ids):
        p_axes = sharding_axes(prompt_ids)
        if p_axes is None or any(p_axes[1:]) or len(p_axes[0]) > 1:
            return DecodePath(
                "einsum", "prompt sharded off the batch dim "
                "(sequence-sharded cache is exotic)")
        if p_axes[0]:
            (batch_axis,) = p_axes[0]
            if (batch_axis == head_axis
                    or prompt_ids.shape[0] % mesh.shape[batch_axis]):
                return DecodePath(
                    "einsum", f"batch axis {batch_axis!r} unusable "
                    "(clashes with head axis or uneven split)")
    return DecodePath(
        "kernel_tp",
        f"heads sharded on {head_axis!r} (tp={tp}): shard_mapped kernel",
        mesh, head_axis, batch_axis)


def generate(model, variables, prompt_ids, max_new_tokens: int,
             max_len: Optional[int] = None, temperature: float = 0.0,
             rng=None, unroll: int = 1):
    """Autoregressive decoding with the KV cache: prefill the prompt in one
    call, then ``lax.scan`` single-token steps — the whole loop is two
    compiled programs regardless of length (no per-token dispatch).
    ``model`` is any causal LM with the cache call contract (``LlamaLM``,
    ``MoeLM``).

    ``temperature`` 0.0 = greedy argmax (default); > 0 samples from
    ``softmax(logits / temperature)`` using ``rng``. Returns
    ``(B, prompt + max_new_tokens)`` ids (prompt included).

    ``unroll``: tokens decoded per ``lax.scan`` iteration (the loop body
    is replicated; the cache takes one in-place row write per token
    either way). >1 amortizes the fixed per-iteration while-loop cost
    that dominates small-batch decode (``examples/decode_floor_probe.py``);
    identical tokens at any value.

    This is the inference counterpart of the training path the framework
    benchmarks; for serving without this framework see ``docs/inference.md``
    (checkpoints are plain pytrees; sharding-path dispatch is described
    in ``docs/decode-serving.md``)."""
    cfg = model.config
    b, s = prompt_ids.shape
    if max_len is None:
        # MoeConfig has no max_seq_len (RoPE-only positions); cap on it
        # only where the config declares one.
        max_len = min(getattr(cfg, "max_seq_len", s + max_new_tokens),
                      s + max_new_tokens)
    if s + max_new_tokens > max_len:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"cache window max_len={max_len}")
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if rng is None:
        rng = jax.random.PRNGKey(0)  # unused on the greedy path
    if max_new_tokens <= 0:
        return prompt_ids
    # greedy is the only STATIC part of the sampling decision: temperature
    # rides in as a traced operand so a temperature sweep shares one
    # compiled program instead of recompiling the prefill+scan per value.
    #
    # Sharding classifier (see classify_decode_sharding): heads-on-TP
    # meshes keep the Pallas fast path through shard_map; only exotic
    # shardings trace the einsum form, which GSPMD shards naturally.
    global LAST_DECODE_PATH
    info = classify_decode_sharding(variables, prompt_ids,
                                    cfg.num_kv_heads)
    if not _DECODE_KERNEL:
        info = DecodePath("einsum", "decode_kernel_disabled()")
    LAST_DECODE_PATH = info
    new_tokens = _decode(model, variables, prompt_ids, rng,
                         jnp.float32(temperature), int(max_new_tokens),
                         int(max_len), temperature <= 0.0, info.path,
                         info.mesh, info.head_axis, info.batch_axis,
                         int(unroll))
    return jnp.concatenate([prompt_ids, new_tokens], axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "max_len", "greedy",
                     "path", "mesh", "head_axis", "batch_axis", "unroll"))
def _decode(model, variables, prompt_ids, rng, temperature, max_new_tokens,
            max_len, greedy, path="kernel", mesh=None, head_axis=None,
            batch_axis=None, unroll=1):
    """Compiled decode body. Module-level with the model as a STATIC arg
    (flax modules hash by structure): repeated ``generate`` calls with the
    same model/shapes hit the jit cache — a per-call ``@jax.jit`` closure
    would recompile the prefill+scan program on every invocation.
    ``path`` (+ mesh/axes for the shard_mapped kernel; Mesh hashes by
    devices and axis names) is part of the jit cache key — a bare global
    flag would be ignored on a cache hit."""
    with decode_path_context(path, mesh, head_axis, batch_axis):
        return _decode_body(model, variables, prompt_ids, rng, temperature,
                            max_new_tokens, max_len, greedy, unroll)


def _decode_body(model, variables, prompt_ids, rng, temperature,
                 max_new_tokens, max_len, greedy, unroll=1):
    cfg = model.config
    b, s = prompt_ids.shape

    def pick(logits, step_rng):
        logits = logits.astype(jnp.float32)
        if greedy:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(step_rng, logits / temperature)

    cache = init_kv_cache(cfg, b, max_len)
    logits, cache = model.apply(variables, prompt_ids, cache=cache,
                                cache_index=0)
    rng, step_rng = jax.random.split(rng)
    first = pick(logits[:, -1], step_rng)

    def body(carry, i):
        tok, cache, rng = carry
        logits, cache = model.apply(variables, tok[:, None], cache=cache,
                                    cache_index=s + i)
        rng, step_rng = jax.random.split(rng)
        nxt = pick(logits[:, -1], step_rng)
        return (nxt, cache, rng), nxt

    # lax.scan handles the zero-length xs of max_new_tokens == 1. unroll
    # replicates the body per while iteration (decode_floor_probe: the
    # fixed per-iteration platform cost is what bounds small-batch
    # decode) — token stream identical at any unroll.
    (_, _, _), rest = jax.lax.scan(
        body, (first, cache, rng), jnp.arange(max_new_tokens - 1),
        unroll=min(unroll, max(max_new_tokens - 1, 1)))
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def llama_tp_param_specs(params, axis: str = "model"):
    """Megatron-style tensor-parallel ``PartitionSpec`` tree for
    ``LlamaLM`` params, for the GSPMD path: ``device_put`` params with
    ``NamedSharding(mesh, spec)`` and ``jax.jit`` the step — XLA derives
    the activation collectives from the shardings (no shard_map needed).

    Layout (the classic column→row pairing, so each block needs ONE
    psum after attention and one after the FFN):
      wq/wk/wv  (dim, heads, head_dim)  — heads sharded (column-parallel)
      wo        (heads, head_dim, dim)  — heads sharded (row-parallel)
      w_gate/up (dim, ffn_hidden)       — hidden sharded (column)
      w_down    (ffn_hidden, dim)       — hidden sharded (row)
      lm_head   (dim, vocab)            — vocab sharded (column; the loss's
                                          lse reduces over vocab via psum)
      tok_embeddings (vocab, dim)       — vocab sharded
      norms / scales                    — replicated

    Requires num_heads, num_kv_heads, ffn_hidden and vocab_size divisible
    by the axis size. Compose with a ``data`` axis for dp x tp."""
    from jax.sharding import PartitionSpec as P

    rules = {
        "wq": P(None, axis, None),
        "wk": P(None, axis, None),
        "wv": P(None, axis, None),
        "wo": P(axis, None, None),
        "w_gate": P(None, axis),
        "w_up": P(None, axis),
        "w_down": P(axis, None),
        "lm_head": P(None, axis),
        "tok_embeddings": P(axis, None),
    }

    def spec(path, x):
        names = {getattr(k, "key", str(k)) for k in path}
        for name, s in rules.items():
            if name in names:
                return s
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)
