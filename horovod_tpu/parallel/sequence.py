"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

No reference-repo equivalent (SURVEY.md §5: "Long-context — ABSENT"); this is
the rebuild's first-class long-context layer, built directly on the
collective substrate the reference's architecture maps to (the ICI ring that
``NCCLHierarchicalAllreduce`` approximates with NCCL rings is here the
transport for K/V rotation).

* ``ring_attention`` — blockwise attention with K/V shards rotating around
  the mesh axis via ``lax.ppermute`` (one neighbor hop per step, riding ICI),
  accumulating with the online-softmax recurrence. Sequence length scales
  linearly with the number of chips; per-chip memory stays O(S_local).
* ``ulysses_attention`` — all-to-all head/sequence reshard: each chip
  attends over the FULL sequence for 1/N of the heads, then reshards back.
  Cheaper than ring for moderate S (two all-to-alls), requires H % N == 0.

Both are shard_map-tier functions: call them inside
``jax.shard_map`` with the sequence axis sharded over ``axis_name``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attend(q, k, v, sm_scale, q_pos, k_pos, causal, key_mask):
    """One (Sq_local x Sk_block) attention block in f32: returns
    (unnormalized acc, running max, running sum) contributions. ``q_pos`` /
    ``k_pos`` are the GLOBAL positions of the local rows/keys (vectors), so
    any sequence layout — contiguous or zigzag — uses the same math.
    Grouped K/V heads (Hkv < H) are repeated here — the dense path runs at
    short S where the extra copy is cheap; the flash path routes groups in
    its grid instead."""
    from ..ops.attention import repeat_kv

    k, v = repeat_kv(q, k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :], s, NEG_INF)
    if causal:
        s = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None, :, :],
                      s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # (b,h,q,1)
    # Guard fully-masked blocks: exp(NEG_INF - NEG_INF) would be 1.
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe) * (m > NEG_INF / 2)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return acc, m_safe, l


def zigzag_positions(idx, s_local, axis_size):
    """Global positions held by shard ``idx`` in the zigzag layout: the
    sequence is cut into 2N blocks and shard i holds blocks (i, 2N-1-i), so
    every shard owns an equal share of early AND late positions and causal
    ring steps do balanced work on every device."""
    half = s_local // 2
    lo = idx * half + jnp.arange(half)
    hi = (2 * axis_size - 1 - idx) * half + jnp.arange(half)
    return jnp.concatenate([lo, hi])


def _zigzag_order(axis_size):
    """Block order of the zigzag layout: shard i holds blocks
    (i, 2N-1-i)."""
    order = []
    for i in range(axis_size):
        order += [i, 2 * axis_size - 1 - i]
    return order


def _zigzag_split(x, axis_size, axis):
    n2 = 2 * axis_size
    s = x.shape[axis]
    if s % n2:
        raise ValueError(
            f"zigzag layout needs the sequence ({s}) divisible by "
            f"2*axis_size ({n2})")
    return jnp.split(x, n2, axis=axis)


def zigzag_shard(x, axis_size, axis: int = 1):
    """Reorder a GLOBAL sequence axis into zigzag shard order: after this,
    splitting the axis into ``axis_size`` equal chunks gives each shard its
    (i, 2N-1-i) block pair. Inverse: ``zigzag_unshard``."""
    blocks = _zigzag_split(x, axis_size, axis)
    return jnp.concatenate([blocks[i] for i in _zigzag_order(axis_size)],
                           axis=axis)


def zigzag_unshard(x, axis_size, axis: int = 1):
    """Inverse of ``zigzag_shard``."""
    blocks = _zigzag_split(x, axis_size, axis)
    order = _zigzag_order(axis_size)
    inverse = [0] * len(order)
    for pos, blk in enumerate(order):
        inverse[blk] = pos
    return jnp.concatenate([blocks[inverse[i]] for i in range(len(order))],
                           axis=axis)


def _half_attend(qh, kh, vh, sm_scale, mask, tri):
    """Attention of q rows over one K/V half-block (``tri``: the two blocks
    share a global offset, so causality is the plain within-block triangle).
    Thin wrapper over ``_block_attend`` — one online-softmax kernel, one set
    of fully-masked-row guards."""
    return _block_attend(qh, kh, vh, sm_scale, jnp.arange(qh.shape[1]),
                         jnp.arange(kh.shape[1]), tri, mask)


def _merge_contrib(a, b):
    """Merge two online-softmax contributions for the same q rows."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    alpha = jnp.exp(m_a - m)
    beta = jnp.exp(m_b - m)
    return acc_a * alpha + acc_b * beta, m, l_a * alpha + l_b * beta


def _zigzag_causal_cases(q, k, v, key_mask, my_idx, src, attend):
    """Causal zigzag step computing ONLY the allowed half-block products —
    each ring step costs half a dense block on every device (this is where
    the layout's load balancing becomes real FLOPs savings, not masking).
    ``attend(qh, kh, vh, mask_h, tri)`` returns the (acc, m, l)
    contribution of one half-block — the dense and flash paths share this
    case analysis so the load-balancing invariant is encoded once.

    With q halves (block i, block 2N-1-i) and the source's K/V halves
    (block j, block 2N-1-j), causality reduces to three cases:
      j == i: lo x lo triangular; hi x lo full; hi x hi triangular
      j <  i: both q halves attend lo fully (hi keys are all in the future)
      j >  i: only the hi queries attend, over both key halves fully
    """
    b, s_local, hn, d = q.shape
    h = s_local // 2
    qlo, qhi = q[:, :h], q[:, h:]
    klo, khi = k[:, :h], k[:, h:]
    vlo, vhi = v[:, :h], v[:, h:]
    mlo = key_mask[:, :h] if key_mask is not None else None
    mhi = key_mask[:, h:] if key_mask is not None else None

    def none_rows(n):
        return (jnp.zeros((b, hn, n, d), jnp.float32),
                jnp.full((b, hn, n, 1), NEG_INF / 2, jnp.float32),
                jnp.zeros((b, hn, n, 1), jnp.float32))

    def cat(lo, hi):
        return tuple(jnp.concatenate([x, y], axis=2)
                     for x, y in zip(lo, hi))

    def eq_case():
        lo = attend(qlo, klo, vlo, mlo, True)
        hi = _merge_contrib(attend(qhi, klo, vlo, mlo, False),
                            attend(qhi, khi, vhi, mhi, True))
        return cat(lo, hi)

    def lt_case():  # src holds strictly earlier lo block
        return attend(q, klo, vlo, mlo, False)

    def gt_case():  # only hi queries are late enough to see src's keys
        return cat(none_rows(h), attend(qhi, k, v, key_mask, False))

    return lax.cond(src == my_idx, eq_case,
                    lambda: lax.cond(src < my_idx, lt_case, gt_case))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_block_pair(q, maskf, k_blk, v_blk, diag_causal, scale):
    """(out, lse) of one ring block via the Pallas kernel, forward AND
    backward: the lse output carries real gradient through the cross-block
    merge, and the Pallas FA-2 backward models it exactly — a cotangent on
    lse is a per-row shift of the delta term (see ``_flash_backward``'s
    ``dlse``), so the backward streams K/V tiles too instead of
    rematerializing the (S_local x S_local) dense block."""
    from ..ops.attention import (
        FLASH_DEFAULT_BLOCK_K,
        FLASH_DEFAULT_BLOCK_Q,
        _auto_interpret,
        _flash_forward,
    )

    return _flash_forward(q, k_blk, v_blk, maskf, diag_causal, scale,
                          FLASH_DEFAULT_BLOCK_Q, FLASH_DEFAULT_BLOCK_K,
                          _auto_interpret())


def _flash_block_pair_fwd(q, maskf, k_blk, v_blk, diag_causal, scale):
    out, lse = _flash_block_pair(q, maskf, k_blk, v_blk, diag_causal, scale)
    return (out, lse), (q, maskf, k_blk, v_blk, out, lse)


def _flash_block_pair_bwd(diag_causal, scale, res, cts):
    from ..ops.attention import (
        FLASH_DEFAULT_BLOCK_K,
        FLASH_DEFAULT_BLOCK_Q,
        _auto_interpret,
        _flash_backward,
    )

    q, maskf, k_blk, v_blk, out, lse = res
    do, dlse = cts
    dq, dk, dv = _flash_backward(
        q, k_blk, v_blk, maskf, out, lse, do, diag_causal, scale,
        FLASH_DEFAULT_BLOCK_Q, FLASH_DEFAULT_BLOCK_K, _auto_interpret(),
        dlse=dlse)
    return dq, None, dk, dv


_flash_block_pair.defvjp(_flash_block_pair_fwd, _flash_block_pair_bwd)


def _flash_contrib_triple(qh, kh, vh, mask_h, tri, scale):
    """One block (or zigzag half-block) through the Pallas kernel, as an
    online-softmax contribution triple (acc, m, l) for ``qh``'s rows: the
    normalised (out, lse) pair re-enters the merge as acc=out, m=lse, l=1
    (out_i carries weight exp(lse_i) in the cross-block merge). ``tri``:
    block and queries share a global offset, so causality is the plain
    within-block triangle — exactly the kernel's causal mode."""
    b, _, hn, _ = qh.shape
    if mask_h is None:
        mask_h = jnp.ones((b, kh.shape[1]), bool)
    o, lse = _flash_block_pair(qh, mask_h, kh, vh, tri, scale)
    a = o.transpose(0, 2, 1, 3).astype(jnp.float32)
    m = lse.reshape(b, hn, qh.shape[1])[..., None]
    return a, m, jnp.ones_like(m)


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = False,
                   sm_scale: Optional[float] = None, key_mask=None,
                   layout: str = "contiguous", use_flash="auto"):
    """Attention over a sequence sharded along ``axis_name``.

    Args (local shards, inside shard_map):
      q, k, v: (B, S_local, H, D); global sequence = concat over the axis in
        rank order. k/v may carry FEWER (grouped) heads — Hkv with
        H % Hkv == 0: since the ring rotates K/V (not Q), GQA cuts the
        per-step ICI bytes to Hkv/H, and the flash inner kernel routes
        query-head groups natively (the dense path repeats locally).
        key_mask: optional (B, S_local) bool for local keys.
      layout: "contiguous" (shard i holds positions [i*S_local, ...)) or
        "zigzag" (shard i holds blocks (i, 2N-1-i) — see ``zigzag_shard``;
        balances causal work across devices, since with contiguous layout
        device N-1 computes every ring step while device 0 is fully masked
        after the first).
      use_flash: run each ring block through the Pallas flash kernel
        instead of materialising the (S_local x S_local) score matrix —
        the per-block (out, lse) pair merges into the online softmax as
        (acc=out, m=lse, l=1); zigzag streams each causal half-block the
        same way. "auto" (default) enables it once the per-KERNEL-CALL
        token count reaches FLASH_AUTO_MIN_SEQ: S_local for contiguous
        (and non-causal zigzag), S_local/2 for causal zigzag, whose
        calls run on half-blocks.
    Returns: (B, S_local, H, D) — attention of local queries over the FULL
      global sequence, in the same layout as the inputs.
    """
    from ..ops.attention import _check_gqa_heads

    _check_gqa_heads(q, k, v, "ring_attention")
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_local, hn, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring_attention layout: {layout!r}")
    if layout == "zigzag" and s_local % 2:
        raise ValueError(
            f"zigzag layout needs an even local sequence (got {s_local})")
    if use_flash == "auto":
        from ..ops.attention import FLASH_AUTO_MIN_SEQ

        # Causal zigzag streams HALF-blocks through the kernel, so the
        # dense-vs-flash crossover applies at s_local/2.
        flash_tokens = (s_local // 2 if causal and layout == "zigzag"
                        else s_local)
        use_flash = flash_tokens >= FLASH_AUTO_MIN_SEQ

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def positions(idx):
        if layout == "zigzag":
            return zigzag_positions(idx, s_local, axis_size)
        return idx * s_local + jnp.arange(s_local)

    q_pos = positions(my_idx)

    def _empty_contrib():
        return (jnp.zeros((b, hn, s_local, d), jnp.float32),
                jnp.full((b, hn, s_local, 1), NEG_INF / 2, jnp.float32),
                jnp.zeros((b, hn, s_local, 1), jnp.float32))

    def flash_half(qh, kh, vh, mh, tri):
        return _flash_contrib_triple(qh, kh, vh, mh, tri, scale)

    def dense_half(qh, kh, vh, mh, tri):
        return _half_attend(qh, kh, vh, scale, mh, tri)

    def contributions(k_blk, v_blk, mask_blk, src):
        if use_flash:
            if not causal:
                return flash_half(q, k_blk, v_blk, mask_blk, False)
            if layout == "zigzag":
                # Same balanced three-case analysis as the dense path,
                # each half-block streamed through the Pallas kernel.
                return _zigzag_causal_cases(q, k_blk, v_blk, mask_blk,
                                            my_idx, src, flash_half)
            # Contiguous causal: past blocks attend fully, the diagonal
            # block is standard intra-block causal, future blocks skip.
            return lax.cond(
                src < my_idx,
                lambda: flash_half(q, k_blk, v_blk, mask_blk, False),
                lambda: lax.cond(
                    src == my_idx,
                    lambda: flash_half(q, k_blk, v_blk, mask_blk, True),
                    _empty_contrib))
        if causal and layout == "zigzag":
            # Only the allowed half-blocks are computed — balanced ~half a
            # dense block per device per step.
            return _zigzag_causal_cases(q, k_blk, v_blk, mask_blk,
                                        my_idx, src, dense_half)
        if causal and layout == "contiguous":
            # Blocks entirely in the future are skipped, not masked: device
            # i computes i+1 of the N steps (zigzag balances this).
            def compute():
                a, bm, bl = _block_attend(q, k_blk, v_blk, scale, q_pos,
                                          positions(src), causal, mask_blk)
                return a, bm, bl

            return lax.cond(src <= my_idx, compute, _empty_contrib)
        a, bm, bl = _block_attend(q, k_blk, v_blk, scale, q_pos,
                                  positions(src), causal, mask_blk)
        return a, bm, bl

    def step(carry, _):
        k_blk, v_blk, mask_blk, src, m, l, acc = carry
        a, bm, bl = contributions(k_blk, v_blk, mask_blk, src)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(bm - m_new)
        l_new = l * alpha + bl * beta
        acc_new = acc * alpha + a * beta
        # Rotate K/V (and mask) to the next neighbor over ICI; the block we
        # receive originated at src-1.
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        mask_next = (lax.ppermute(mask_blk, axis_name, perm)
                     if mask_blk is not None else None)
        src_next = (src - 1) % axis_size
        return (k_next, v_next, mask_next, src_next, m_new, l_new, acc_new), None

    m0 = jnp.full((b, hn, s_local, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hn, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, hn, s_local, d), jnp.float32)
    carry = (k, v, key_mask, my_idx, m0, l0, acc0)
    (_, _, _, _, m, l, acc), _ = lax.scan(step, carry, None, length=axis_size)

    out = acc / jnp.maximum(l, 1e-30)  # zeros for fully-masked rows
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "seq", causal: bool = False,
                      sm_scale: Optional[float] = None, attention_fn=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern): reshard
    (B, S_local, H, D) -> (B, S_global, H_local, D), attend over the full
    sequence with 1/N of the heads, reshard back. Two ``lax.all_to_all``s on
    ICI replace N-1 ring hops."""
    from ..ops.attention import _check_gqa_heads

    axis_size = lax.psum(1, axis_name)
    hn = q.shape[2]
    # GQA invariants up front (v heads == k heads, H % Hkv == 0): a bad v
    # shape would otherwise surface later as a confusing inner-attention
    # or collective error.
    _check_gqa_heads(q, k, v, "ulysses_attention")
    if hn % axis_size or k.shape[2] % axis_size:
        raise ValueError(
            f"ulysses_attention: query heads ({hn}) and K/V heads "
            f"({k.shape[2]}) must both divide by axis size ({axis_size}); "
            "use ring_attention instead")

    def scatter_heads(x):
        # (B, S_local, H, D) -> (B, S_global, H/N, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if attention_fn is None:
        # make_attention_fn's auto selection: the resharded arrays hold
        # the FULL sequence, so long-context calls hit the Pallas kernel
        # and short ones the plain XLA path.
        from ..ops.attention import make_attention_fn

        attention_fn = make_attention_fn(causal=causal, sm_scale=sm_scale)
    return gather_heads(attention_fn(qg, kg, vg, None))
