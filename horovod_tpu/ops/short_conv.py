"""The gated short convolution ``y = C * conv_K(B * u)`` as one Pallas
kernel each way: the pointwise part of ``models/lfm2.py``'s
``ShortConvMixer``, between its two projections.

The plain form is ``c_gate * causal_conv(b_gate * u, taps)``
(``ops/linear_attention.causal_conv``): XLA pads ``B * u`` in float32,
sums the shifted copies, writes intermediates out and recomputes them
under ``jax.checkpoint``. The least those passes have to move is four
arrays of tokens x width forward (two gates and ``u`` in, ``y`` out) and
seven backward; :func:`gated_short_conv_packed` moves that and no more:

* **It reads the gates where the in-projection wrote them.** ``packed``
  is the projection's one ``(batch, S, 3 * dim)`` result, ``[B | C | u]``
  along the last axis; a block is ``rows`` whole rows of it and the kernel
  takes its three thirds as lane-aligned slices in VMEM. A ``jnp.split``
  ahead of a custom call would be three copies.
* **Forward** (``hvd_shortconv_fwd``): a grid over (batch, blocks of
  rows), the sequence innermost and in order; the last rows of ``B * u``
  of a block stay in VMEM scratch for the next one (zeros at a sequence's
  start). Float32 from the loads to the one rounding at ``y``.
* **Backward** (``hvd_shortconv_bwd``): one kernel that reads ``packed``
  and ``dy`` once and writes the three gradients as one ``(batch, S, 3 *
  dim)`` array, so that the projection's two gradients take it as it
  lies. It recomputes ``conv(B * u)`` in VMEM. The transposed convolution
  looks ``K - 1`` rows AHEAD and the recomputed one ``K - 1`` rows BACK,
  so the sequence is swept backwards: the first rows of ``dy * C`` of a
  block stay in scratch for the block before it, and the rows of ``B`` and
  ``u`` behind a block come as a second, 16-row block of the same array.
  The taps' gradient ``(K, dim)`` accumulates in float32 in an output
  block that stays in VMEM across the whole grid.
* **Residuals**: ``packed`` (which the projection's backward holds
  anyway) and the taps; not ``B * u``.

**Which shapes take the kernels** (:func:`block_rows`): a width that is
a whole number of 128-lane tiles, at most ``_HALO // 2 + 1`` taps, and a
sequence that the row block divides; the block is the largest power of
two of rows that keeps a block at ``_BLOCK_ELEMENTS`` elements a third or
under (256 rows at width 2048), between 16 and 512. Anything else is the
caller's to run in the plain form.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import profiler
from . import attention

__all__ = ["gated_short_conv_packed", "block_rows"]

_LANES = 128
# Rows of the block behind that the backward kernel reads, and of the
# scratch that carries rows across blocks: one bf16 tile of sublanes.
_HALO = 16
# Rows x width of one third of a block, at most.
_BLOCK_ELEMENTS = 1 << 19
# Lanes a pass of the kernel's body works on at a time, at most (128, 256
# and 512 run alike on the chip: PERF.md section 6, PR 47).
_CHUNK = 256
_VMEM_LIMIT = 64 << 20


def block_rows(seq: int, dim: int, taps: int):
    """Rows of the sequence a kernel's block holds for ``(batch, seq, 3 *
    dim)`` gates and ``(taps, dim)`` taps, or ``None`` where the shapes
    take the plain form: a width off the 128-lane tile, more taps than
    the carried rows hold, or a sequence the block does not divide."""
    if dim % _LANES or not 1 <= taps - 1 <= _HALO // 2:
        return None
    rows = _HALO
    while rows * 2 * dim <= _BLOCK_ELEMENTS and rows < 512:
        rows *= 2
    return rows if seq % rows == 0 else None


def _each_chunk(dim, body):
    """``body(cols)`` for each chunk of lanes of one third of the width, as
    ONE traced loop: a mixer's step holds three of these kernels and a
    Python loop here would trace and lower each chunk's operations anew."""
    width = math.gcd(dim, _CHUNK)

    def chunk(c, carry):
        body(lambda third=0: pl.ds(
            pl.multiple_of(third * dim + c * width, width), width))
        return carry

    lax.fori_loop(0, dim // width, chunk, 0)


def _thirds(ref, cols):
    """The block's ``B``, ``C`` and ``u`` at ``cols`` as float32."""
    return [ref[0, :, cols(third)].astype(jnp.float32) for third in range(3)]


def _behind(x, before, k):
    """``x`` (rows, lanes) moved ``k`` rows down, its first ``k`` rows the
    last ``k`` of ``before`` (8, lanes)."""
    return pltpu.roll(jnp.concatenate([before, x], axis=0), k, 0)[8:]


def _ahead(x, after, k):
    """``x`` moved ``k`` rows up, its last ``k`` rows the first ``k`` of
    ``after`` (8, lanes)."""
    rows = x.shape[0]
    return pltpu.roll(jnp.concatenate([x, after], axis=0),
                      rows + 8 - k, 0)[:rows]


def _fwd_kernel(p_ref, w_ref, y_ref, carry_ref, *, dim, taps):
    @pl.when(pl.program_id(1) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    def chunk(cols):
        b, c, u = _thirds(p_ref, cols)
        x = b * u
        before = carry_ref[:, cols()]
        v = w_ref[taps - 1:taps, cols()] * x
        for k in range(1, taps):
            v += w_ref[taps - 1 - k:taps - k, cols()] * _behind(x, before, k)
        y_ref[0, :, cols()] = (c * v).astype(y_ref.dtype)
        carry_ref[:, cols()] = x[-8:]

    _each_chunk(dim, chunk)


def _bwd_kernel(p_ref, halo_b_ref, halo_u_ref, dy_ref, w_ref, g_ref, dw_ref,
                carry_ref, *, dim, taps):
    # The grid walks the sequence's blocks from the last to the first.
    step = pl.program_id(1)
    starts_sequence = step == pl.num_programs(1) - 1

    @pl.when(step == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    @pl.when((step == 0) & (pl.program_id(0) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def chunk(cols):
        b, c, u = _thirds(p_ref, cols)
        dy = dy_ref[0, :, cols()].astype(jnp.float32)
        x = b * u
        before = jnp.where(
            starts_sequence, 0.0,
            (halo_b_ref[0, :, cols()].astype(jnp.float32)
             * halo_u_ref[0, :, cols()].astype(jnp.float32))[8:])
        dv = dy * c
        after = carry_ref[:, cols()]
        # Tap K - 1 - k reads k rows back; its transpose k rows ahead.
        back = [x] + [_behind(x, before, k) for k in range(1, taps)]
        v = dx = None
        for k in range(taps):
            w = w_ref[taps - 1 - k:taps - k, cols()]
            v = w * back[k] if k == 0 else v + w * back[k]
            ahead = dv if k == 0 else _ahead(dv, after, k)
            dx = w * ahead if k == 0 else dx + w * ahead
            dw_ref[taps - 1 - k:taps - k, cols()] += jnp.sum(
                dv * back[k], axis=0, keepdims=True)
        for third, grad in enumerate((dx * u, dy * v, dx * b)):
            g_ref[0, :, cols(third)] = grad.astype(g_ref.dtype)
        carry_ref[:, cols()] = dv[:8]

    _each_chunk(dim, chunk)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _forward(packed, taps, rows):
    batch, seq, width = packed.shape
    dim, k = width // 3, taps.shape[0]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dim=dim, taps=k),
        grid=(batch, seq // rows),
        in_specs=[pl.BlockSpec((1, rows, width), lambda n, i: (n, i, 0)),
                  pl.BlockSpec((k, dim), lambda n, i: (0, 0))],
        out_specs=pl.BlockSpec((1, rows, dim), lambda n, i: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, seq, dim), packed.dtype),
        scratch_shapes=[pltpu.VMEM((8, dim), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=attention._auto_interpret(),
        name=profiler.KERNEL_SHORTCONV_FWD,
    )(packed, taps.astype(jnp.float32))


def _backward(packed, taps, dy, rows):
    batch, seq, width = packed.shape
    dim, k = width // 3, taps.shape[0]
    blocks, halos = seq // rows, rows // _HALO

    def block(n, i):
        return (n, blocks - 1 - i, 0)

    def halo(third):
        # The 16 rows before the block, of one third of the width; the
        # sequence's first block reads its own and the kernel zeroes them.
        return pl.BlockSpec(
            (1, _HALO, dim),
            lambda n, i: (n, jnp.maximum((blocks - 1 - i) * halos - 1, 0),
                          third))

    return pl.pallas_call(
        functools.partial(_bwd_kernel, dim=dim, taps=k),
        grid=(batch, blocks),
        in_specs=[pl.BlockSpec((1, rows, width), block), halo(0), halo(2),
                  pl.BlockSpec((1, rows, dim), block),
                  pl.BlockSpec((k, dim), lambda n, i: (0, 0))],
        out_specs=[pl.BlockSpec((1, rows, width), block),
                   pl.BlockSpec((k, dim), lambda n, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(packed.shape, packed.dtype),
                   jax.ShapeDtypeStruct((k, dim), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((8, dim), jnp.float32)],
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=attention._auto_interpret(),
        name=profiler.KERNEL_SHORTCONV_BWD,
    )(packed, packed, packed, dy, taps.astype(jnp.float32))


@jax.custom_vjp
def gated_short_conv_packed(packed, taps):
    """``C * conv(B * u)`` from ``packed`` ``(batch, S, 3 * dim)`` = ``[B |
    C | u]`` along the last axis and ``taps`` ``(K, dim)``: channel ``c``
    of token ``t`` is ``C[t, c] * sum_i taps[i, c] (B * u)[t - (K - 1) +
    i, c]``, tokens before the sequence read as zero. Float32 inside, one
    rounding to ``packed``'s type at the result and at each gradient; the
    gradient of ``packed`` comes back packed the same way. Only shapes
    :func:`block_rows` names a block for; the others are
    ``ops.linear_attention.causal_conv``'s."""
    return _forward(packed, taps, _rows_of(packed, taps))


def _rows_of(packed, taps):
    rows = block_rows(packed.shape[1], packed.shape[2] // 3, taps.shape[0])
    if packed.shape[2] % 3 or rows is None:
        raise ValueError(
            f"gated_short_conv_packed: gates {packed.shape} with taps "
            f"{taps.shape} take no kernel (block_rows); run the plain form")
    return rows


def _vjp_forward(packed, taps):
    return _forward(packed, taps, _rows_of(packed, taps)), (packed, taps)


def _vjp_backward(residuals, dy):
    packed, taps = residuals
    grads, d_taps = _backward(packed, taps, dy, _rows_of(packed, taps))
    return grads, d_taps.astype(taps.dtype)


gated_short_conv_packed.defvjp(_vjp_forward, _vjp_backward)
