"""Attention kernels: Pallas flash attention for TPU + XLA reference path.

No reference-repo equivalent (Horovod 0.16 predates transformers); this is
the long-context compute core required by the rebuild (task brief:
"long-context ... first-class"), and the ``attention_fn`` seam of
``horovod_tpu.models.bert.SelfAttention`` plugs into it.

Design: classic FlashAttention-2 online-softmax blocking. The grid is
(batch*heads, q_blocks, k_blocks); Pallas streams one (block_k, d) K/V tile
per innermost grid step from HBM into VMEM (BlockSpec index_maps drive the
double-buffered DMA pipeline), so VMEM holds O(block_q*d + block_k*d) — not
O(seq_k*d) — and the ceiling on sequence length is HBM, not VMEM. Running
max / normalizer / output accumulate in VMEM scratch across the innermost
dimension (TPU grids execute sequentially), and the
(block_q x d) @ (d x block_k) products keep the MXU fed.

Backward is a Pallas FA-2 backward (two kernels: a dq pass streaming K/V
and a dk/dv pass streaming Q/dO), reconstituting probabilities from the
saved per-row log-sum-exp instead of storing the S x S matrix. Set
``HOROVOD_FLASH_XLA_BWD=1`` to fall back to the rematerialized XLA backward.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import profiler

NEG_INF = -1e30


def _check_gqa_heads(q, k, v, name: str) -> None:
    if (v.shape[2] != k.shape[2]) or (q.shape[2] % k.shape[2]):
        raise ValueError(
            f"{name}: query heads ({q.shape[2]}) must be a multiple of "
            f"K/V heads ({k.shape[2]}, v {v.shape[2]}) — grouped-query "
            "attention folds each group of H/Hkv query heads onto one "
            "K/V head")


def repeat_kv(q, k, v):
    """Repeat grouped K/V heads (axis 2) up to q's head count — the ONE
    place the GQA head-ordering convention (group-contiguous, query head
    h reads K/V head h // group) is materialized as data; the flash grid
    encodes the same convention as index maps instead."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


FLASH_AUTO_MIN_SEQ = 512
# v5e-tuned default inner tiles (see flash_attention docstring). Swept on
# hardware with dispatch-amortized, DCE-proof, baseline-subtracted timing
# (examples/flash_attention_benchmark.py): at B=4 S=2048 H=8 D=64 bf16
# causal, (512, 1024) is the sweep's best both before and after the
# round-3 input-dtype MXU rework — 0.43 ms fwd / 1.68 ms fwd+bwd (vs
# 1.26-1.6 / ~5.4 for the XLA softmax path); the next size up
# (block_q=1024) exceeds the 16 MiB scoped-VMEM limit.
FLASH_DEFAULT_BLOCK_Q = 512
FLASH_DEFAULT_BLOCK_K = 1024


def _auto_interpret() -> bool:
    """Compiled by Mosaic on ``tpu``, Pallas interpreter on ``cpu`` (the
    hermetic tests). Any other backend is an error: silently interpreting
    on a mis-named or unexpected platform would hide that the kernels
    never reached the chip."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        "Pallas kernels here run compiled on 'tpu' or interpreted on "
        f"'cpu'; jax.default_backend() is {backend!r}")


def reference_attention(q, k, v, key_mask=None, causal=False,
                        sm_scale: Optional[float] = None):
    """Plain XLA attention; also the backward-path recompute.

    Shapes: q (B, Sq, H, D); k/v (B, Sk, Hkv, D) with H % Hkv == 0
    (grouped-query attention: K/V repeat across each group of
    H // Hkv query heads); key_mask (B, Sk) bool."""
    d = q.shape[-1]
    _check_gqa_heads(q, k, v, "reference_attention")
    k, v = repeat_kv(q, k, v)
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if key_mask is not None:
        logits = jnp.where(key_mask[:, None, None, :], logits, NEG_INF)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        ki = jnp.arange(sk)[None, :]
        logits = jnp.where((ki <= qi)[None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


# Lane width of the m/l scratch accumulators. TPU VMEM wants a 128-wide
# trailing dim; the running max/normalizer live column-broadcast across it.
_STATE_LANES = 128


def _allowed_mask(mask_ref, has_mask: bool, causal: bool, qb, kb,
                  block_q: int, block_k: int, q_offset: int):
    """The (block_q, block_k) allowed-entry mask, or None when every entry
    is allowed (no key mask given AND not causal) so the callers skip the
    where/zeroing VPU passes entirely. ``has_mask`` is static — the
    public entry knows at trace time whether a key mask was supplied."""
    allowed = None
    if has_mask:
        allowed = jnp.broadcast_to((mask_ref[0, 0] != 0)[None, :],
                                   (block_q, block_k))
    if causal:
        q_pos = qb * block_q + q_offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        tri = k_pos <= q_pos
        allowed = tri if allowed is None else (allowed & tri)
    return allowed


def _flash_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr, *, block_k: int, sm_scale: float,
                  causal: bool, num_kb: int, block_q: int, q_offset: int,
                  has_mask: bool):
    # Grid (bh, qb, kb), kb innermost. Block shapes: q (1, block_q, d)
    # (constant across kb — fetched once), k/v (1, block_k, d) (a NEW tile
    # streams in from HBM each kb step), mask (1, 1, block_k). Running
    # softmax state persists in VMEM scratch across the kb loop.
    # ``q_offset = sk - sq``: under the decode convention the sq query rows
    # are the LAST sq positions of the sk-long key axis, so query row i sits
    # on the causal diagonal at key column i + q_offset (matches
    # reference_attention's ``qi = arange(sq) + (sk - sq)``).
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: K blocks strictly above the diagonal touch no allowed entry;
    # skip their compute entirely (the DMA still runs — grid fetches are
    # static — but the MXU work, the dominant cost, is elided).
    live = ((kb * block_k <= qb * block_q + block_q - 1 + q_offset)
            if causal else True)

    @pl.when(live)
    def _body():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        # MXU in the INPUT dtype with f32 accumulation: bf16 q/k run at
        # full MXU rate (the previous astype(f32)-before-dot forced an
        # f32 matmul at a fraction of it — measured 43.7% of the whole
        # Llama-300M step inside these kernels); sm_scale applies to the
        # f32 product, which is algebraically identical.
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        allowed = _allowed_mask(mask_ref, has_mask, causal, qb, kb,
                                block_q, block_k, q_offset)
        if allowed is not None:
            s = jnp.where(allowed, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # Explicit zeroing, not exp alone: in a fully-masked row m_new stays
        # at the NEG_INF init, where exp(s - m_new) would be exp(0) = 1 per
        # masked key and the row would silently emit mean(v).
        p = jnp.exp(s - m_new)
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p drops to the V dtype for the MXU (f32 inputs: no-op, tests
        # stay exact; bf16: full-rate matmul, the universal flash
        # convention — probabilities carry ~8 mantissa bits there).
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        # Fully-masked rows (l == 0) produce zeros, not NaNs.
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # Log-sum-exp per row, saved for the backward pass
        # (FlashAttention-2): exp(s - lse) reconstitutes the softmax without
        # storing the S x S probs.
        lse_ref[0, 0] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]


def _fold_heads(q, k, v, key_mask):
    """Fold heads into batch: q (B, Sq, H, D) -> (B*H, Sq, D) and k/v
    (B, Sk, Hkv, D) -> (B*Hkv, Sk, D) contiguous MXU tiles, plus the mask
    as (B, 1, Sk) int32 (TPU block shapes must tile (8,128) or equal the
    array dims; the singleton row dim satisfies the equality escape).
    Under GQA (Hkv < H) the K/V tiles are NOT repeated — the pallas
    index_maps route each query head's grid row to its group's K/V row,
    so the K/V HBM footprint stays at Hkv/H of the repeated form (DMA
    traffic is unchanged: tiles are re-fetched per query-head row).
    Shared by the forward and backward pallas_calls so their layouts
    cannot drift apart."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    if key_mask is None:
        maskf = jnp.ones((b, 1, sk), dtype=jnp.int32)
    else:
        maskf = key_mask.astype(jnp.int32).reshape(b, 1, sk)
    return qf, kf, vf, maskf


def _gqa_index_maps(h: int, hkv: int):
    """Index maps routing a (b*h) grid row to its K/V row (b*hkv) and its
    mask row (b). ``bh = b*h + head``; the head's K/V group is
    ``head // (h // hkv)``."""
    group = h // hkv

    def kv(bh):
        return (bh // h) * hkv + (bh % h) // group

    def mask(bh):
        return bh // h

    return kv, mask


def _fit_block(block: int, seq: int) -> int:
    """Largest power-of-two-halving of ``block`` (clamped to ``seq``) that
    divides ``seq`` — tuned defaults must never reject a shape the kernel
    supports (e.g. S=384 with the 256-default halves to 128)."""
    block = min(block, seq)
    while block > 1 and seq % block:
        block //= 2
    return max(block, 1)


def _flash_forward(q, k, v, key_mask, causal, sm_scale, block_q, block_k,
                   interpret, has_mask: bool = True):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash_attention: seq lengths ({sq},{sk}) must be divisible by "
            f"blocks ({block_q},{block_k}); pad to a block multiple")

    qf, kf, vf, maskf = _fold_heads(q, k, v, key_mask)
    kv_row, mask_row = _gqa_index_maps(h, hkv)
    num_kb = sk // block_k
    # kb innermost: K/V tiles stream HBM→VMEM one per step; q block and the
    # o/lse output blocks are revisited (their index_maps ignore kb), so
    # they stay VMEM-resident across the whole kb sweep.
    grid = (b * h, sq // block_q, num_kb)
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, sm_scale=scale,
                          causal=causal, num_kb=num_kb, block_q=block_q,
                          q_offset=sk - sq, has_mask=has_mask),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j: (kv_row(bh), j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j: (kv_row(bh), j, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh, i, j: (mask_row(bh), 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _STATE_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATE_LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name=profiler.KERNEL_FLASH_FWD,
    )(qf, kf, vf, maskf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3), lse


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, block_k: int,
                         sm_scale: float, causal: bool, num_kb: int,
                         block_q: int, q_offset: int, has_mask: bool):
    # Grid (bh, qb, kb), kb innermost: K/V tiles stream from HBM while
    # q/do/lse/delta stay resident. Recompute p block-by-block from q, k and
    # the saved lse; no S x S materialization (FA-2 backward, dq pass).
    # q_offset: see _flash_kernel — decode-convention diagonal shift.
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = ((kb * block_k <= qb * block_q + block_q - 1 + q_offset)
            if causal else True)

    @pl.when(live)
    def _body():
        lse = lse_ref[0, 0][:, None]          # (block_q, 1)
        delta = delta_ref[0, 0][:, None]      # (block_q, 1)
        # All dots in the INPUT dtype with f32 accumulation (see
        # _flash_kernel); sm_scale moves onto the f32 product / the
        # finalize write.
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        allowed = _allowed_mask(mask_ref, has_mask, causal, qb, kb,
                                block_q, block_k, q_offset)
        # Explicit zeroing (not exp of -inf): fully-masked rows keep p = 0,
        # so their gradients vanish as they must (out is identically 0).
        p = jnp.exp(s - lse)
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                           delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                           block_q: int, sm_scale: float, causal: bool,
                           num_qb: int, block_k: int, q_offset: int,
                           inner_steps: int, has_mask: bool):
    # GQA-native grid (b*hkv, kb, t), t innermost sweeping the query GROUP
    # x q blocks (t = g * num_qb + qb): this program's K/V-head block stays
    # resident while Q/dO/lse/delta tiles stream from HBM for every query
    # head in the group, and dk/dv accumulate in VMEM scratch across the
    # whole sweep — the K/V-head gradient is written ONCE per (b*hkv, kb),
    # i.e. Hkv/H of the HBM writes of a per-query-head grid, with no
    # full-H partial in HBM and no XLA group-sum afterwards. MHA is the
    # group == 1 case (inner_steps == num_qb).
    # q_offset: see _flash_kernel — decode-convention diagonal shift.
    kb, t = pl.program_id(1), pl.program_id(2)
    qb = t % num_qb

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = ((kb * block_k <= qb * block_q + block_q - 1 + q_offset)
            if causal else True)

    @pl.when(live)
    def _body():
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        # All dots in the INPUT dtype with f32 accumulation (see
        # _flash_kernel); sm_scale moves onto the f32 product here and
        # onto dk at finalize (dk = scale * ds^T q).
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        allowed = _allowed_mask(mask_ref, has_mask, causal, qb, kb,
                                block_q, block_k, q_offset)
        p = jnp.exp(s - lse)
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == inner_steps - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, key_mask, out, lse, g, causal, sm_scale,
                    block_q, block_k, interpret, dlse=None,
                    has_mask: bool = True):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)

    qf, kf, vf, maskf = _fold_heads(q, k, v, key_mask)
    kv_row, mask_row = _gqa_index_maps(h, hkv)
    dof = g.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    outf = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta_i = sum_d dO_i O_i — the softmax-normalizer correction term;
    # cheap elementwise XLA, fused into the surrounding graph.
    delta = jnp.sum(dof.astype(jnp.float32) * outf.astype(jnp.float32),
                    axis=-1).reshape(b * h, 1, sq)
    if dlse is not None:
        # A cotangent on the lse output (ring attention's cross-block
        # merge differentiates through it) is EXACTLY a shift of delta:
        # dL/ds_ij = p_ij (dp_ij - delta_i) + p_ij dlse_i
        #          = p_ij (dp_ij - (delta_i - dlse_i)),
        # since d lse_i / d s_ij = p_ij. dv is unaffected.
        delta = delta - dlse.reshape(b * h, 1, sq).astype(jnp.float32)

    num_kb = sk // block_k
    num_qb = sq // block_q
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          sm_scale=scale, causal=causal, num_kb=num_kb,
                          block_q=block_q, q_offset=sk - sq,
                          has_mask=has_mask),
        grid=(b * h, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j: (kv_row(bh), j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, i, j: (kv_row(bh), j, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh, i, j: (mask_row(bh), 0, j)),
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=profiler.KERNEL_FLASH_BWD_DQ,
    )(qf, kf, vf, maskf, dof, lse, delta)

    # GQA-native dkdv: grid rows are K/V heads (b*hkv), the query group is
    # swept in-kernel (t = g * num_qb + qb, innermost), so dk/dv come out
    # at (b*hkv, sk, d) directly — no full-H partials in HBM, no XLA
    # group-sum. Q/dO/lse/delta index maps route the t step to query head
    # kvh * group + t // num_qb (group-contiguous, matching repeat_kv).
    group = h // hkv
    inner = group * num_qb

    def q_row(bh, t):
        return (bh // hkv) * h + (bh % hkv) * group + t // num_qb

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, block_q=block_q,
                          sm_scale=scale, causal=causal, num_qb=num_qb,
                          block_k=block_k, q_offset=sk - sq,
                          inner_steps=inner, has_mask=has_mask),
        grid=(b * hkv, num_kb, inner),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bh, j, t: (q_row(bh, t), t % num_qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, t: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, t: (bh, j, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh, j, t: (bh // hkv, 0, j)),
            pl.BlockSpec((1, block_q, d),
                         lambda bh, j, t: (q_row(bh, t), t % num_qb, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bh, j, t: (q_row(bh, t), 0, t % num_qb)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bh, j, t: (q_row(bh, t), 0, t % num_qb)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j, t: (bh, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j, t: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name=profiler.KERNEL_FLASH_BWD_DKV,
    )(qf, kf, vf, maskf, dof, lse, delta)

    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk = dk.reshape(b, hkv, sk, d).transpose(0, 2, 1, 3)
    dv = dv.reshape(b, hkv, sk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


# The mask rides as a *differentiable* float32 argument with a zero
# cotangent: nondiff_argnums may not receive tracers (jit/shard_map callers
# pass traced masks), so only the static config lives there.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, maskf, causal, sm_scale, block_q, block_k, interpret,
           has_mask):
    out, _ = _flash_forward(q, k, v, maskf != 0, causal, sm_scale, block_q,
                            block_k, interpret, has_mask=has_mask)
    return out


def _flash_fwd_rule(q, k, v, maskf, causal, sm_scale, block_q, block_k,
                    interpret, has_mask):
    out, lse = _flash_forward(q, k, v, maskf != 0, causal, sm_scale, block_q,
                              block_k, interpret, has_mask=has_mask)
    return out, (q, k, v, maskf, out, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, has_mask,
                    res, g):
    q, k, v, maskf, out, lse = res
    from ..common.config import flash_xla_bwd

    if flash_xla_bwd():
        # Escape hatch: rematerialized backward through the XLA reference
        # path (materializes the S x S probs; O(S^2) memory). Read at trace
        # time — set it before the train step is first compiled; already-
        # compiled executables keep the backward they were traced with.
        def f(q, k, v):
            out = reference_attention(q, k, v, key_mask=maskf != 0,
                                      causal=causal, sm_scale=sm_scale)
            # Match the flash forward exactly: rows with NO allowed key
            # emit zeros in the kernel, but reference_attention softmaxes
            # their constant NEG_INF logits into uniform probs (mean(v)).
            # Differentiating the unzeroed form would leak those dead
            # rows' cotangents into dv/dk. O(S^2) bools — this whole
            # branch is the O(S^2) path already.
            sq, sk = q.shape[1], k.shape[1]
            allowed = (maskf != 0)[:, None, :]
            if causal:
                qi = jnp.arange(sq)[:, None] + (sk - sq)
                allowed = allowed & (jnp.arange(sk)[None, :] <= qi)[None]
            row_valid = allowed.any(-1)  # (b, sq)
            return jnp.where(row_valid[:, :, None, None], out, 0.0)

        _, vjp = jax.vjp(f, q, k, v)
        dq, dk, dv = vjp(g)
        return dq, dk, dv, jnp.zeros_like(maskf)
    dq, dk, dv = _flash_backward(q, k, v, maskf != 0, out, lse, g, causal,
                                 sm_scale, block_q, block_k, interpret,
                                 has_mask=has_mask)
    return dq, dk, dv, jnp.zeros_like(maskf)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, key_mask=None, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = FLASH_DEFAULT_BLOCK_Q,
                    block_k: int = FLASH_DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None):
    """Flash attention forward. ``interpret=None`` compiles on ``tpu`` and
    selects Pallas interpreter mode on ``cpu`` (the hermetic tests run the
    same kernel); any other backend raises.

    ``causal`` with ``sq != sk`` follows the decode convention (matching
    ``reference_attention``): the sq query rows are the LAST sq positions
    of the key axis, i.e. query row i attends keys ``<= i + (sk - sq)``.
    For sq > sk, rows before key position 0 are fully masked and emit
    zeros (reference_attention degenerates to uniform probs there).

    Grouped-query attention is native: pass k/v with Hkv < H heads
    (H % Hkv == 0) and each group of H/Hkv query heads reads one K/V
    head via the grid index_maps. This keeps the K/V footprint at
    Hkv/H on BOTH passes (no repeated copy in HBM; under remat, no
    repeated copy per recompute), and the backward dkdv kernel
    accumulates each K/V head's gradient in VMEM across its query
    group — dk/dv are written once per K/V head (Hkv/H the HBM
    writes), never materialized at full H. Streaming DMA traffic for
    K/V tiles is unchanged: each query head still reads its group's
    tiles.

    ``block_q``/``block_k`` set the VMEM working set AND the HBM→VMEM
    streaming granule: per grid step one (block_k, d) K and V tile is DMAed
    in (double-buffered by Pallas), so peak VMEM is
    O(block_q*d + 2*block_k*d) independent of sequence length — S is bounded
    by HBM, not VMEM. Defaults hardware-swept on v5e at S=2048, D=64 (see
    module constants; block_q=1024 trips the 16 MiB scoped-VMEM limit);
    both are clamped/halved to divide the sequence length."""
    if interpret is None:
        interpret = _auto_interpret()
    b, sq, sk = k.shape[0], q.shape[1], k.shape[1]
    _check_gqa_heads(q, k, v, "flash_attention")
    # Awkward sequence lengths (e.g. ViT's 197 = 196 patches + CLS, a
    # PRIME) would make _fit_block degrade to pathological 1-row blocks.
    # Auto-pad to the next 128 multiple instead: padded keys are masked
    # out (fully-masked rows emit zeros), padded query rows are sliced
    # off, and under causal the q/k pads are equal so the diagonal offset
    # sk - sq is preserved. TPU pads the S x S tiles to the 128 lane
    # granule anyway — explicit padding costs little extra compute and
    # buys the streaming kernel (no S^2 materialization) at any length.
    def _pad_to(n):
        return (n + 127) // 128 * 128

    def _degenerate(block, seq):
        # Pad only when the SEQUENCE is the problem: off the 8-sublane
        # granule, or its divisors force the fitted block far below the
        # request (fit == block means the caller asked for that size).
        fit = _fit_block(block, seq)
        return seq % 8 != 0 or (fit < block and fit < min(64, seq))

    needs_pad = _degenerate(block_q, sq) or _degenerate(block_k, sk)
    if needs_pad and (not causal or sq == sk):
        sqp, skp = _pad_to(sq), _pad_to(sk)
        if causal:  # keep skp - sqp == sk - sq
            sqp = skp = max(sqp, skp)
        # Pad only if it actually improves the block fit — e.g. an
        # explicit block 48 never divides a 128-multiple either, and
        # padding would just enlarge the degenerate grid.
        if not (_fit_block(block_q, sqp) > _fit_block(block_q, sq)
                or _fit_block(block_k, skp) > _fit_block(block_k, sk)):
            needs_pad = False
    if needs_pad and (not causal or sq == sk):
        mask = (jnp.arange(skp) < sk)[None, :]
        if key_mask is not None:
            mask = mask & jnp.pad(key_mask.astype(bool),
                                  ((0, 0), (0, skp - sk)))
        mask = jnp.broadcast_to(mask, (b, skp))
        out = _flash(
            jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0), (0, 0))),
            jnp.pad(k, ((0, 0), (0, skp - sk), (0, 0), (0, 0))),
            jnp.pad(v, ((0, 0), (0, skp - sk), (0, 0), (0, 0))),
            mask.astype(jnp.float32), causal, sm_scale, block_q, block_k,
            interpret, True)
        return out[:, :sq]
    # has_mask is static: with key_mask=None the kernels skip the mask
    # broadcast/where VPU passes entirely (the placeholder ones-mask
    # still rides along so the custom_vjp arity is fixed).
    return _flash(q, k, v,
                  (jnp.ones((b, sk), jnp.float32) if key_mask is None
                   else key_mask.astype(jnp.float32)),
                  causal, sm_scale, block_q, block_k, interpret,
                  key_mask is not None)


def make_attention_fn(causal: bool = False, use_flash="auto",
                      block_q: int = FLASH_DEFAULT_BLOCK_Q,
                      block_k: int = FLASH_DEFAULT_BLOCK_K,
                      sm_scale: Optional[float] = None):
    """Adapter for ``horovod_tpu.models.bert.SelfAttention(attention_fn=...)``
    — signature (q, k, v, mask) with mask of shape (B, Sk) or None.

    ``use_flash="auto"`` (default) picks the kernel per trace-time sequence
    length: below ``FLASH_AUTO_MIN_SEQ`` the plain XLA softmax path wins
    (measured on v5e: BERT-base seq=128 runs 1240 vs 934 seq/s — the
    O(S^2) memory flash avoids is tiny there and the kernel overhead
    isn't); at long S flash's O(S) memory and blocking win. Pass
    True/False to force.

    The returned fn carries ``supports_gqa = True``: both paths accept
    k/v with fewer (grouped) heads than q, so GQA models can skip the
    K/V repeat entirely (``LlamaAttention`` checks this attribute)."""

    def fn(q, k, v, mask):
        flash = use_flash
        if flash == "auto":
            flash = q.shape[1] >= FLASH_AUTO_MIN_SEQ
        if flash:
            return flash_attention(q, k, v, key_mask=mask, causal=causal,
                                   sm_scale=sm_scale,
                                   block_q=block_q, block_k=block_k)
        return reference_attention(q, k, v, key_mask=mask, causal=causal,
                                   sm_scale=sm_scale)

    fn.supports_gqa = True
    return fn
