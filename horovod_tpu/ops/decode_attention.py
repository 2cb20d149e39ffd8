"""Pallas decode-step attention over the KV cache (single-token queries).

WHY A KERNEL: the XLA formulation of cached decode attention forces a
layout trade-off that costs ~47% of the decode step. Attention reduces
over the cache's seq axis, so XLA lays the loop-carried cache buffers out
seq-minor (seq on the 128-lane tile axis) — and then each step's one-row
``dynamic_update_slice`` read-modify-writes every tile of the buffer, a
full ~6 MB rewrite per layer per step on Llama-300M
(``artifacts/decode_ceiling_r5.json``; six XLA-level reformulations were
measured and none escape it — the layout demand follows the reduction
wherever it's expressed). A Mosaic kernel consumes its operands in the
DEFAULT major-to-minor layout, so with the in-loop reads kernelized the
carried cache keeps its natural layout and the one-row cache write
becomes a true in-place row update. Measured effect (Llama-300M):
decode 10.3k -> 18.8k tok/s at b32.

The kernel is bandwidth-bound by design: grid = (batch, L-tiles), each
step streams one (block_l, Hkv*D) K and V tile HBM->VMEM while the
running softmax state accumulates in scratch (the FlashAttention
pattern — VMEM holds O(block_l * Hkv * D), so the window length is
bounded by HBM, not VMEM). FLOPs are ~2·L·D·H per program — noise next
to the cache bytes — so memory-rate streaming IS the roofline.

Used by ``horovod_tpu.models.llama._cached_attention`` for s == 1;
interpret mode runs the same kernel on the CPU backend (hermetic tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..common import profiler
from .attention import NEG_INF, _auto_interpret

# Default L-tile: 2 * block_l * (Hkv*D) * 2 bytes of streamed K/V per
# step — 1 MiB at Llama-8B widths (f = 1024), comfortably inside scoped
# VMEM at any window length.
DECODE_BLOCK_L = 256


def _decode_kernel(idx_ref, w_ref, k_ref, v_ref, o_ref, l_ref,
                   m_scr, l_scr, acc_scr, *, group: int, sm_scale: float,
                   block_l: int, num_lb: int):
    # Grid (batch, L-tiles), L innermost: one (block_l, f) K and V tile
    # streams HBM->VMEM per step; the running softmax state persists in
    # scratch across the L sweep. ``idx_ref`` is the scalar-prefetched
    # cache index. w (1, f, h) is the query arranged BLOCK-DIAGONALLY by
    # the host-side wrapper so ONE MXU pass computes every head's scores
    # (per-head dots have N = group = 2 and are nearly all latency:
    # measured ~58 us/layer that way).
    #
    # Mosaic legality drives the shapes: everything is 2D, reductions run
    # over axis 0, and the accumulator is kept TRANSPOSED as (f, h) so
    # the running-max rescale is a plain (f, h) * (1, h) broadcast —
    # (1, h) -> (h, 1) relayouts and splits of tiled minor dims are not
    # legal in-kernel. The outputs are likewise (d, h) context (caller
    # transposes the tiny tensor in XLA) and the (1, h) normalizer
    # (caller divides).
    t = pl.program_id(1)
    h = w_ref.shape[2]
    f = k_ref.shape[2]                                 # hkv * d
    d = o_ref.shape[1]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Tiles fully above the causal bound contribute nothing; their DMA
    # still runs (grid fetches are static) but the compute is skipped.
    @pl.when(t * block_l <= idx_ref[0])
    def _body():
        k2 = k_ref[0]                                  # (block_l, f)
        v2 = v_ref[0]
        s = lax.dot_general(k2, w_ref[0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        pos = (t * block_l
               + lax.broadcasted_iota(jnp.int32, (block_l, h), 0))
        valid = pos <= idx_ref[0]
        s = jnp.where(valid, s, NEG_INF)               # (block_l, h)
        m = m_scr[0:1]                                 # (1, h)
        l = l_scr[0:1]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        # Explicit zeroing: in a fully-masked column m_new stays NEG_INF
        # and exp(s - m_new) would be 1 per masked key.
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m - m_new)                     # (1, h)
        l_scr[...] = jnp.broadcast_to(
            l * alpha + jnp.sum(p, axis=0, keepdims=True), l_scr.shape)
        # Contribution in TRANSPOSED form: (f, h) = v^T-free dot
        # contracting the tile axis; history rescales by alpha as a
        # row-broadcast.
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            v2, p.astype(v2.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(t == num_lb - 1)
    def _finalize():
        full = acc_scr[...]                            # (f, h) unnormalized
        # Keep each query head's OWN K/V head block: column hq reads rows
        # [kv(hq)*d, kv(hq)*d + d); zero the rest, then collapse the
        # d-strided row blocks with a tiled-identity selector.
        own = (lax.broadcasted_iota(jnp.int32, (f, h), 0) // d
               == lax.broadcasted_iota(jnp.int32, (f, h), 1) // group)
        sel = (lax.broadcasted_iota(jnp.int32, (d, f), 1) % d
               == lax.broadcasted_iota(jnp.int32, (d, f), 0))
        ctx = lax.dot_general(sel.astype(jnp.float32),
                              jnp.where(own, full, 0.0),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (d, h)
        o_ref[0] = ctx.astype(o_ref.dtype)
        l_ref[0] = l_scr[0:1]


def _pick_block_l(L: int, f: int, itemsize: int, requested: int) -> int:
    """L-tile choice. A single whole-window tile streams best (tiling
    measured ~18% slower at L=384 from smaller DMAs + tile overhead), so
    tile only when the window would blow the VMEM budget — and then pick
    the largest DIVISOR of L at or under the requested tile (a
    power-of-2 halving would collapse to pathological tiles for windows
    without large 2-power factors; ``init_kv_cache`` rounds big windows
    to a 128 multiple so a decent divisor exists there). For awkward
    hand-built windows with no usable divisor, a big single tile beats
    16-row DMAs as long as it fits at all."""
    window_bytes = 2 * L * f * itemsize
    if window_bytes <= (4 << 20):
        return L
    block_l = next(q for q in range(min(requested, L), 0, -1)
                   if L % q == 0)
    if block_l < 64 and window_bytes <= (8 << 20):
        return L
    return block_l


def decode_attention(q, k_cache, v_cache, cache_index, num_kv_heads,
                     sm_scale=None, block_l: int = DECODE_BLOCK_L,
                     interpret=None):
    """Masked single-token attention over the FLAT cache window.

    ``q``: (B, 1, H, D); ``k_cache``/``v_cache``: (B, L, Hkv*D) — the
    row-flattened GQA cache (flat so no reshape ever touches the cache
    buffers; splitting the tiled minor dims is not Mosaic-legal in-kernel
    and an XLA-side reshape would re-open the layout question);
    ``cache_index``: the query's global position t — keys at positions
    <= t are attended (the new row must already be written into the
    cache). H % Hkv == 0 (grouped-query). Returns (B, 1, H, D)."""
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"decode_attention is single-token (s={s})")
    hkv = num_kv_heads
    L, f = k_cache.shape[1], k_cache.shape[2]
    if h % hkv or f != hkv * d:
        raise ValueError(
            f"H ({h}) must be a multiple of Hkv ({hkv}) and the flat cache "
            f"width ({f}) must equal Hkv*D ({hkv * d})")
    group = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _auto_interpret()
    block_l = _pick_block_l(L, f, k_cache.dtype.itemsize, block_l)
    num_lb = L // block_l
    idx = jnp.asarray(cache_index, jnp.int32).reshape(1)
    # Block-diagonal query arrangement (see _decode_kernel): W[b, kv1*d+dd,
    # h'] = q[b, h', dd] for kv1 == h' // group, else 0. Touches only the
    # fresh per-step q — never the cache buffers, whose layout freedom is
    # the whole point of this kernel. Built as broadcast * constant mask
    # (the mask is loop-invariant and hoists out of the decode scan; an
    # eye-einsum build measured ~25 us/layer).
    qt = jnp.swapaxes(q[:, 0], 1, 2)                       # (b, d, h)
    qt = jnp.broadcast_to(qt[:, None], (b, hkv, d, h)).reshape(b, f, h)
    blockmask = (jnp.arange(f)[:, None] // d
                 == jnp.arange(h)[None, :] // group).astype(q.dtype)
    w = qt * blockmask

    ctx_dh, l = pl.pallas_call(
        functools.partial(_decode_kernel, group=group, sm_scale=scale,
                          block_l=block_l, num_lb=num_lb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, num_lb),
            in_specs=[
                pl.BlockSpec((1, f, h), lambda i, t, idx: (i, 0, 0)),
                pl.BlockSpec((1, block_l, f), lambda i, t, idx: (i, t, 0)),
                pl.BlockSpec((1, block_l, f), lambda i, t, idx: (i, t, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, d, h), lambda i, t, idx: (i, 0, 0)),
                pl.BlockSpec((1, 1, h), lambda i, t, idx: (i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((8, h), jnp.float32),
                pltpu.VMEM((8, h), jnp.float32),
                pltpu.VMEM((f, h), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, d, h), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, h), jnp.float32),
        ],
        interpret=interpret,
        name=profiler.KERNEL_DECODE,
    )(idx, w, k_cache, v_cache)
    # Normalize + transpose OUTSIDE the kernel: tiny (b, d, h) tensors,
    # no cache involvement ((1, h) -> (h, 1) is not Mosaic-legal).
    out = ctx_dh / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype).reshape(b, 1, h, d)


def _paged_decode_kernel(lens_ref, tables_ref, w_ref, k_ref, v_ref, o_ref,
                         l_ref, m_scr, l_scr, acc_scr, *, group: int,
                         sm_scale: float, block_size: int, num_bps: int):
    # Paged twin of ``_decode_kernel``: grid (batch, table slots), the
    # KV tile for step (i, t) fetched from PHYSICAL block
    # ``tables_ref[i, t]`` of the shared pool (the index_map does the
    # indirection — the gather never materializes), and the causal bound
    # is PER SEQUENCE (``lens_ref[i]``), so one program batch mixes
    # sequences at arbitrary positions. Slots past a sequence's last
    # block alias the reserved null block; their rows sit above the
    # causal bound and contribute exact zeros.
    i = pl.program_id(0)
    t = pl.program_id(1)
    h = w_ref.shape[2]
    d = o_ref.shape[1]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(t * block_size <= lens_ref[i])
    def _body():
        k2 = k_ref[0]                                  # (block_size, f)
        v2 = v_ref[0]
        s = lax.dot_general(k2, w_ref[0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        pos = (t * block_size
               + lax.broadcasted_iota(jnp.int32, (block_size, h), 0))
        valid = pos <= lens_ref[i]
        s = jnp.where(valid, s, NEG_INF)
        m = m_scr[0:1]
        l = l_scr[0:1]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_scr[...] = jnp.broadcast_to(
            l * alpha + jnp.sum(p, axis=0, keepdims=True), l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            v2, p.astype(v2.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(t == num_bps - 1)
    def _finalize():
        f = acc_scr.shape[0]
        full = acc_scr[...]
        own = (lax.broadcasted_iota(jnp.int32, (f, h), 0) // d
               == lax.broadcasted_iota(jnp.int32, (f, h), 1) // group)
        sel = (lax.broadcasted_iota(jnp.int32, (d, f), 1) % d
               == lax.broadcasted_iota(jnp.int32, (d, f), 0))
        ctx = lax.dot_general(sel.astype(jnp.float32),
                              jnp.where(own, full, 0.0),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (d, h)
        o_ref[0] = ctx.astype(o_ref.dtype)
        l_ref[0] = l_scr[0:1]


def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           num_kv_heads, sm_scale=None, interpret=None):
    """Single-token attention over a PAGED cache: the KV rows of every
    sequence live in fixed-size blocks of one shared pool, addressed
    through a per-sequence block table (the vLLM/PagedAttention layout,
    on this repo's row-flat GQA cache).

    ``q``: (B, 1, H, D); ``k_pool``/``v_pool``: (N, block_size, Hkv*D) —
    the physical pool, block 0 reserved as the null block (all-zero,
    never allocated; see ``serving.kv_blocks``); ``block_tables``:
    (B, T) int32 — sequence i's logical block t is physical block
    ``block_tables[i, t]`` (unused slots point at the null block);
    ``context_lens``: (B,) int32 — the per-sequence query position
    (keys at positions <= lens[i] attend; the new row must already be
    written, see :func:`paged_cache_write`). Returns (B, 1, H, D).

    The kernel is ``_decode_kernel`` with two generalizations: the KV
    tile index comes from the scalar-prefetched block table (the
    indirection costs nothing — it rewrites the DMA source address), and
    the causal bound is per sequence, which is what lets one decode
    batch carry sequences at heterogeneous positions (continuous
    batching). Unlike the contiguous kernel there is no whole-window
    single-tile fast path: the L-tile IS the block."""
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"paged_decode_attention is single-token (s={s})")
    hkv = num_kv_heads
    n_blocks, block_size, f = k_pool.shape
    if h % hkv or f != hkv * d:
        raise ValueError(
            f"H ({h}) must be a multiple of Hkv ({hkv}) and the pool "
            f"width ({f}) must equal Hkv*D ({hkv * d})")
    if v_pool.shape != k_pool.shape:
        raise ValueError(
            f"k/v pools disagree: {k_pool.shape} vs {v_pool.shape}")
    if block_tables.shape[0] != b or context_lens.shape != (b,):
        raise ValueError(
            f"block_tables {block_tables.shape} / context_lens "
            f"{context_lens.shape} do not cover the batch ({b})")
    num_bps = block_tables.shape[1]
    group = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _auto_interpret()
    lens = jnp.asarray(context_lens, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    # Block-diagonal query arrangement — identical to decode_attention.
    qt = jnp.swapaxes(q[:, 0], 1, 2)                       # (b, d, h)
    qt = jnp.broadcast_to(qt[:, None], (b, hkv, d, h)).reshape(b, f, h)
    blockmask = (jnp.arange(f)[:, None] // d
                 == jnp.arange(h)[None, :] // group).astype(q.dtype)
    w = qt * blockmask

    ctx_dh, l = pl.pallas_call(
        functools.partial(_paged_decode_kernel, group=group, sm_scale=scale,
                          block_size=block_size, num_bps=num_bps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, num_bps),
            in_specs=[
                pl.BlockSpec((1, f, h), lambda i, t, lens, tbl: (i, 0, 0)),
                pl.BlockSpec((1, block_size, f),
                             lambda i, t, lens, tbl: (tbl[i, t], 0, 0)),
                pl.BlockSpec((1, block_size, f),
                             lambda i, t, lens, tbl: (tbl[i, t], 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, d, h), lambda i, t, lens, tbl: (i, 0, 0)),
                pl.BlockSpec((1, 1, h), lambda i, t, lens, tbl: (i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((8, h), jnp.float32),
                pltpu.VMEM((8, h), jnp.float32),
                pltpu.VMEM((f, h), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, d, h), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, h), jnp.float32),
        ],
        interpret=interpret,
        name=profiler.KERNEL_PAGED_DECODE,
    )(lens, tables, w, k_pool, v_pool)
    out = ctx_dh / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype).reshape(b, 1, h, d)


def paged_gather_attention(q, k_pool, v_pool, block_tables, context_lens,
                           num_kv_heads, sm_scale=None):
    """XLA fallback for the paged layout (``decode_kernel_disabled()``,
    exotic shardings): gather each sequence's blocks into a contiguous
    window — a real copy, the cost the kernel's index_map indirection
    exists to avoid — then run the masked einsum with the per-sequence
    causal bound. Same semantics as :func:`paged_decode_attention`."""
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"paged_gather_attention is single-token (s={s})")
    hkv = num_kv_heads
    _, block_size, f = k_pool.shape
    group = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    window = block_tables.shape[1] * block_size
    k_win = k_pool[block_tables].reshape(b, window, hkv, d)
    v_win = v_pool[block_tables].reshape(b, window, hkv, d)
    qg = q.reshape(b, s, hkv, group, d)
    logits = jnp.einsum("bshgd,blhd->bshgl", qg, k_win).astype(
        jnp.float32) * scale
    mask = (jnp.arange(window)[None, :]
            <= jnp.asarray(context_lens)[:, None])          # (b, window)
    logits = jnp.where(mask[:, None, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bshgl,blhd->bshgd", probs, v_win).reshape(b, s, h, d)


def paged_cache_write(k_pool, v_pool, k_new, v_new, block_tables,
                      context_lens):
    """Write each sequence's fresh K/V row (position ``context_lens[i]``)
    into its block: one (B, Hkv*D) scatter per pool — rows land at
    ``(block_tables[i, lens // bs], lens % bs)``. ``k_new``/``v_new``:
    (B, 1, Hkv, D) already in the pool dtype. Inactive batch slots point
    at the null block with lens 0 — their write lands there, harmless
    and masked everywhere. Returns the updated (k_pool, v_pool)."""
    b = k_new.shape[0]
    block_size = k_pool.shape[1]
    lens = jnp.asarray(context_lens, jnp.int32)
    blk = jnp.asarray(block_tables, jnp.int32)[
        jnp.arange(b), lens // block_size]
    off = lens % block_size
    k_pool = k_pool.at[blk, off].set(k_new.reshape(b, -1))
    v_pool = v_pool.at[blk, off].set(v_new.reshape(b, -1))
    return k_pool, v_pool


def sharded_paged_decode_step(q, k_new, v_new, k_pool, v_pool, block_tables,
                              context_lens, num_kv_heads, *, mesh,
                              head_axis, batch_axis=None, sm_scale=None,
                              interpret=None):
    """One TP-sharded PAGED decode step: per-shard block-row write +
    per-shard paged kernel inside ``jax.shard_map`` over the heads axis —
    the paged twin of :func:`sharded_decode_step`, same contract: no
    collective inside the step, the head concat is the ``out_spec``, the
    psum after wo stays GSPMD's job.

    The pool shards on its FLAT head-width axis (each shard holds its
    Hkv/tp head columns of every physical block), so block tables and
    context lens are replicated scalars of the step — the indirection is
    identical on every shard, and each shard's one-row write stays
    in-place on its own slice.

    ``batch_axis`` is rejected: unlike the contiguous cache (a batch
    dim to shard, ``sharded_decode_step``'s ``cache_spec``), the pool
    has NO batch dim — under a dp-sharded batch each dp group would
    write only its own sequences' rows into its copy of a pool the
    out_spec declares replicated, and the replicas would silently
    diverge. dp x tp paged serving needs per-dp-group pools (one
    engine per dp replica today)."""
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(
            f"sharded_paged_decode_step is single-token (s={s})")
    if batch_axis is not None:
        raise NotImplementedError(
            "paged decode does not support a dp-sharded batch: the "
            "shared block pool has no batch dim to shard, so dp "
            "replicas of it would diverge — run one serving engine per "
            "dp replica instead")
    hkv = num_kv_heads
    tp = mesh.shape[head_axis]
    if hkv % tp or h % hkv:
        raise ValueError(
            f"heads not shardable over {head_axis!r} (size {tp}): need "
            f"Hkv ({hkv}) % tp == 0 and H ({h}) % Hkv == 0")
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    head_spec = P(None, None, head_axis, None)
    pool_spec = P(None, None, head_axis)
    table_spec = P(None, None)
    lens_spec = P(None)

    def local_step(q_l, kn_l, vn_l, kp_l, vp_l, tbl_l, lens_l):
        kp_l, vp_l = paged_cache_write(kp_l, vp_l, kn_l, vn_l, tbl_l,
                                       lens_l)
        ctx = paged_decode_attention(q_l, kp_l, vp_l, tbl_l, lens_l,
                                     hkv // tp, sm_scale=scale,
                                     interpret=interpret)
        return ctx, kp_l, vp_l

    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(head_spec, head_spec, head_spec, pool_spec, pool_spec,
                  table_spec, lens_spec),
        out_specs=(head_spec, pool_spec, pool_spec),
        check_vma=False,
    )(q, k_new, v_new, k_pool, v_pool,
      jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(context_lens, jnp.int32))


def sharded_decode_step(q, k_new, v_new, k_cache, v_cache, cache_index,
                        num_kv_heads, *, mesh, head_axis,
                        batch_axis=None, sm_scale=None,
                        block_l: int = DECODE_BLOCK_L, interpret=None):
    """One TP-sharded decode step: per-shard cache-row write + per-shard
    Pallas kernel, inside ``jax.shard_map`` over the heads axis.

    Attention is per-head independent and Megatron TP shards heads
    (``models.llama.llama_tp_param_specs``: wq/wk/wv column-parallel on
    the head axis), so the kernel is valid per shard: each program holds
    H/tp query heads and the matching Hkv/tp K/V head rows of the
    row-flat cache, writes ITS OWN one-row cache update, and runs the
    unmodified single-device kernel on its slice. No collective runs
    inside the step — the head concat is the ``out_spec``, and the psum
    after wo stays GSPMD's job. GSPMD cannot partition the custom call
    itself; shard_map sidesteps that by making every shard a complete
    single-device kernel invocation, which also keeps the per-shard
    cache buffer in the kernel-friendly layout where the row write is a
    true in-place update (the whole point — see module docstring).

    ``q``: (B, 1, H, D); ``k_new``/``v_new``: (B, 1, Hkv, D) fresh rows
    ALREADY cast to the cache dtype; ``k_cache``/``v_cache``:
    (B, L, Hkv*D) row-flat. ``mesh``: the device mesh; ``head_axis``:
    the mesh axis sharding heads (tp = its size must divide Hkv);
    ``batch_axis``: optional mesh axis sharding the batch dim (dp x tp
    serving). Returns ``(ctx, k_cache, v_cache)`` with the new rows
    written — the caller never touches the cache buffers itself.
    """
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"sharded_decode_step is single-token (s={s})")
    hkv = num_kv_heads
    tp = mesh.shape[head_axis]
    if hkv % tp or h % hkv:
        raise ValueError(
            f"heads not shardable over {head_axis!r} (size {tp}): need "
            f"Hkv ({hkv}) % tp == 0 and H ({h}) % Hkv == 0")
    if batch_axis is not None and b % mesh.shape[batch_axis]:
        raise ValueError(
            f"batch ({b}) not divisible by {batch_axis!r} axis size "
            f"({mesh.shape[batch_axis]})")
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    head_spec = P(batch_axis, None, head_axis, None)
    cache_spec = P(batch_axis, None, head_axis)

    def local_step(q_l, kn_l, vn_l, kc_l, vc_l, idx):
        bl = kn_l.shape[0]
        kc_l = lax.dynamic_update_slice(
            kc_l, kn_l.reshape(bl, 1, -1), (0, idx, 0))
        vc_l = lax.dynamic_update_slice(
            vc_l, vn_l.reshape(bl, 1, -1), (0, idx, 0))
        ctx = decode_attention(q_l, kc_l, vc_l, idx, hkv // tp,
                               sm_scale=scale, block_l=block_l,
                               interpret=interpret)
        return ctx, kc_l, vc_l

    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(head_spec, head_spec, head_spec, cache_spec, cache_spec,
                  P()),
        out_specs=(head_spec, cache_spec, cache_spec),
        check_vma=False,
    )(q, k_new, v_new, k_cache, v_cache,
      jnp.asarray(cache_index, jnp.int32))
