"""The band of the streamed flash kernels as their grids see it: which
blocks the inner axes walk and fetch (``_band_grid``, PR 33), what a head's
sweep makes of steps, copies and pairs (``_BandAxis.walk``,
``_band_pairs``), and the key block the backward kernels fit to the band
(``_fit_band``, PR 37); the numerics the index arithmetic could break,
against the XLA reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_helpers import (_rand, _sq_loss, assert_matches_reference,
                               kernel_grids, out_and_grads)
from horovod_tpu.ops.attention import flash_attention, reference_attention


# --------------------------------------------------------------------------
# The band's extent (PR 33): the streamed grids' inner axis counts the
# blocks of the band (``_band_grid``), its index maps follow the band and
# clamp past its end. ``_band_blocks`` stays the one definition of which
# block is live: the closed forms are held to a walk of it over every
# block, for both grid orders.
@pytest.mark.parametrize("sq,sk", [(512, 512), (128, 512), (512, 128)])
@pytest.mark.parametrize("block_q,block_k", [
    (16, 16), (16, 64), (64, 16), (32, 128), (128, 128),
    # What the band's fit (PR 37) makes of a key block wider than the
    # window, at window 128: the window's width under a query block of its
    # own width (above) or of half of it, and both at half the window.
    (64, 128), (64, 64)])
@pytest.mark.parametrize("window", [None, 1, 40, 100, 128, 200, 1000])
def test_band_grid_is_band_blocks_solved_for_one_index(sq, sk, block_q,
                                                       block_k, window):
    from horovod_tpu.ops.attention import _band_blocks, _band_grid

    num_qb, num_kb = sq // block_q, sk // block_k
    live = np.asarray(_band_blocks(
        window, np.arange(num_qb)[:, None], np.arange(num_kb)[None, :],
        block_q, block_k, sk - sq)[0])
    keys, queries = _band_grid(sq, sk, block_q, block_k, True, window)
    for axis, rows in ((keys, live), (queries, live.T)):
        assert (axis.outer, axis.inner) == rows.shape and axis.banded
        assert axis.extent == max(int(rows.sum(1).max()), 1)
        for o, row in enumerate(rows):
            first, last = axis.first(o), axis.last(o)
            # The live blocks are first..last, none where last < first.
            assert sorted(np.flatnonzero(row)) == list(range(first,
                                                             last + 1))
            for j in range(axis.extent):
                block, tile, ended = axis.block(o, j)
                assert block == first + j and ended == (block > last)
                assert 0 <= tile < axis.inner
                if ended and last >= first:
                    assert tile == last     # the tile of the step before
                elif not ended:
                    assert tile == block and row[block]
        steps, ran, tiles = axis.walk()
        assert steps == axis.outer * axis.extent
        assert ran == rows.sum() and tiles <= max(ran, 1) + axis.outer
    # The traced arithmetic (an index map, ``program_id``) is the same.
    traced = jax.jit(lambda o, j: keys.block(o, j) + queries.block(o, j))
    for o, j in [(0, 0), (num_qb - 1, keys.extent - 1)]:
        if o < queries.outer and j < queries.extent:
            want = keys.block(o, j) + queries.block(o, j)
            assert [int(x) for x in traced(o, j)] == [int(x) for x in want]


def test_band_grid_without_a_band_is_the_whole_grid():
    from horovod_tpu.ops.attention import _band_grid

    keys, queries = _band_grid(256, 512, 16, 64, False, None)
    assert (keys.extent, queries.extent) == (8, 16)
    assert not keys.banded and keys.block(3, 5) == (5, 5, False)
    assert keys.walk() == (16 * 8, 16 * 8, 16 * 8)


# What the static counter reads at the decoder cells' own shapes:
# sequence 8192 under the default blocks 512 x 1024. ``extent of inner``
# steps an outer block, and ``(steps, live, tiles)`` a head beside
# ``outer * inner`` each of the grid over the whole sequence.
CELL_BANDS = {
    # Laguna's sliding layers: a query block's band is 1 or 2 key tiles,
    # and every tile is copied in once a head (a query block starts on the
    # tile the one before it ended on).
    "laguna_window_512_keys": dict(
        window=512, axis=0, extent=2, inner=8, walk=(32, 23, 8)),
    # ... and a key block's band 3 of 16 query blocks, each of the group's
    # 8 query heads in turn.
    "laguna_window_512_queries": dict(
        window=512, axis=1, extent=3, inner=16, group=8,
        walk=(8 * 8 * 3, 8 * 23, 8 * 23)),
    # SmallThinker's window layers.
    "smallthinker_window_4096_keys": dict(
        window=4096, axis=0, extent=5, inner=8, walk=(80, 60, 58)),
    "smallthinker_window_4096_queries": dict(
        window=4096, axis=1, extent=10, inner=16, group=7,
        walk=(7 * 80, 7 * 60, 7 * 60)),
    # Plain causal (every decoder cell's full layers): the axis stays 8
    # long, 72 of its 128 steps a head are live, and the clamp keeps the
    # 56 tiles above the diagonal out of the copies.
    "causal_keys": dict(
        window=None, axis=0, extent=8, inner=8, walk=(128, 72, 70)),
    "causal_queries": dict(
        window=None, axis=1, extent=16, inner=16, group=6,
        walk=(6 * 128, 6 * 72, 6 * 72)),
}


@pytest.mark.parametrize("case", sorted(CELL_BANDS))
def test_band_counter_at_the_cells_shapes(case):
    from horovod_tpu.ops.attention import (FLASH_DEFAULT_BLOCK_K,
                                           FLASH_DEFAULT_BLOCK_Q,
                                           _band_grid)

    want = CELL_BANDS[case]
    axis = _band_grid(8192, 8192, FLASH_DEFAULT_BLOCK_Q,
                      FLASH_DEFAULT_BLOCK_K, True,
                      want["window"])[want["axis"]]
    assert (axis.extent, axis.inner) == (want["extent"], want["inner"])
    assert axis.walk(want.get("group", 1)) == want["walk"]


# Numerical cases the band's index arithmetic could break, forward and all
# three gradients against the XLA reference in interpret mode.
BAND_CASES = {
    # A window with a key mask on the streamed path (every third key
    # masked: no row loses its whole window).
    "key_mask": dict(sq=128, sk=128, h=4, hkv=2, window=24, mask=True,
                     kw=dict(block_q=16, block_k=32)),
    # A window under the decode convention, unequal blocks.
    "sq_lt_sk": dict(sq=64, sk=256, h=2, hkv=2, window=72,
                     kw=dict(block_q=32, block_k=64)),
    # sq > sk: the first 64 query rows lie before key 0.
    "sq_gt_sk": dict(sq=128, sk=64, h=2, hkv=1, window=None,
                     kw=dict(block_q=16, block_k=16)),
    "sq_gt_sk_window": dict(sq=128, sk=64, h=2, hkv=1, window=24,
                            kw=dict(block_q=32, block_k=16)),
    # A window that is a multiple of neither block.
    "odd_window": dict(sq=128, sk=128, h=2, hkv=2, window=37,
                       kw=dict(block_q=16, block_k=64)),
    # The Laguna grouping, 8 query heads a K/V head: dk/dv's
    # t = g * extent + j with fewer steps a head (4) than query blocks (8).
    "group_8": dict(sq=256, sk=256, h=8, hkv=1, window=48,
                    kw=dict(block_q=32, block_k=64)),
}


@pytest.mark.parametrize("case", sorted(BAND_CASES))
def test_band_grid_numerics_against_reference(case):
    from horovod_tpu.ops.attention import _band_grid

    c = BAND_CASES[case]
    sq, sk = c["sq"], c["sk"]
    q = _rand((1, sq, c["h"], 16), 100)
    k, v = (_rand((1, sk, c["hkv"], 16), 101 + i) for i in range(2))
    mask = (jnp.arange(sk) % 3 != 1)[None, :] if c.get("mask") else None
    # Rows before key 0 (sq > sk) see no key: the kernels emit zeros and
    # zero gradients there, the reference a mean of v, so the loss is of
    # the rows that see one.
    seen = (jnp.arange(sq) >= sq - sk)[None, :, None, None]
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, key_mask=mask, causal=True, window=c["window"],
        **c["kw"]) * seen
    ref = lambda q, k, v: reference_attention(  # noqa: E731
        q, k, v, key_mask=mask, causal=True, window=c["window"]) * seen
    assert_matches_reference(flash, ref, q, k, v)
    if sq > sk:     # the kernels' own zeros there, in the output and in dq
        unmasked, (dq, _, _) = out_and_grads(
            lambda q, k, v: flash_attention(
                q, k, v, key_mask=mask, causal=True, window=c["window"],
                **c["kw"]), q, k, v)
        assert not np.asarray(unmasked)[0, :sq - sk].any()
        assert not np.asarray(dq)[0, :sq - sk].any()
    if case == "group_8":
        queries = _band_grid(sq, sk, 32, 64, True, 48)[1]
        assert queries.extent == 4 < queries.inner == 8


# --------------------------------------------------------------------------
# The band's fit (PR 37): the key block of a streamed backward call is
# halved while its half still covers the window (and is whole lanes), so a
# window narrower than the key block runs dq and dk/dv on tiles as wide as
# the window; the forward keeps the sequence's blocks. ``expect`` is the
# key block the backward kernels end with, read from their grids.
def _case(window, expect, sq=512, sk=512, h=2, hkv=1, block_q=128,
          block_k=256, mask=False):
    return dict(window=window, expect=expect, sq=sq, sk=sk, h=h, hkv=hkv,
                block_q=block_q, block_k=block_k, mask=mask)


BAND_FIT_CASES = {
    # The window below, equal to and above the caller's key block of 256.
    "below": _case(64, 128),
    "equal": _case(256, 256),
    "above": _case(500, 256),
    # No power of two: 384 keeps the smallest halving of 1024 that covers
    # it, 48 stops at the 128 lanes.
    "window_384": _case(384, 512, sq=1024, sk=1024, block_q=256,
                        block_k=1024),
    "window_48": _case(48, 128),
    # The decode convention: the 128 queries are the last of 512.
    "sq_lt_sk": _case(100, 128, sq=128, block_q=64),
    # A query group of 1 (above: 2) and Laguna's 8, under a key mask.
    "group_1_key_mask": _case(128, 128, h=1, mask=True),
    "group_8_key_mask": _case(64, 128, h=8, sq=256, mask=True),
    "group_8": _case(128, 128, h=8, sq=256),
}


@pytest.mark.parametrize("case", sorted(BAND_FIT_CASES))
def test_blocks_fitted_to_the_band_match_reference(case):
    from horovod_tpu.ops.attention import _band_grid, _fit_band

    c = BAND_FIT_CASES[case]
    sq, sk, h, hkv = c["sq"], c["sk"], c["h"], c["hkv"]
    q = _rand((1, sq, h, 16), 110)
    k, v = (_rand((1, sk, hkv, 16), 111 + i) for i in range(2))
    mask = (jnp.arange(sk) % 5 != 2)[None, :] if c["mask"] else None
    kw = dict(key_mask=mask, causal=True, window=c["window"])
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block_q=c["block_q"], block_k=c["block_k"], **kw)
    ref = lambda q, k, v: reference_attention(q, k, v, **kw)  # noqa: E731
    # The output and, for one cotangent, the three gradients: one pass
    # through each kernel.
    assert_matches_reference(flash, ref, q, k, v, cot=_rand(q.shape, 113))
    # The forward ran on the caller's blocks, dq and dk/dv on the key
    # block ``expect``; each grid is the band's at its blocks.
    block_q = c["block_q"]
    assert _fit_band(c["block_k"], True, c["window"]) == c["expect"]
    keys, _ = _band_grid(sq, sk, block_q, c["block_k"], True, c["window"])
    fit_keys, fit_queries = _band_grid(sq, sk, block_q, c["expect"], True,
                                       c["window"])
    grids = kernel_grids(
        jax.grad(_sq_loss(flash), argnums=(0, 1, 2)), q, k, v)
    assert grids == {
        "hvd_flash_fwd": (h, sq // block_q, keys.extent),
        "hvd_flash_bwd_dq": (h, sq // block_q, fit_keys.extent),
        "hvd_flash_bwd_dkv": (hkv, sk // c["expect"],
                              h // hkv * fit_queries.extent),
    }


@pytest.mark.parametrize("causal,window,block_k,want", [
    # Only a window at or below half the key block engages it ...
    (True, 512, 1024, 512),
    (True, 384, 1024, 512),
    (True, 256, 1024, 256),
    (True, 1, 1024, 128),           # ... down to 128 lanes
    (True, 513, 1024, 1024),
    (True, 4096, 1024, 1024),
    (True, None, 1024, 1024),
    (False, None, 1024, 1024),
    # A caller's own block is an upper bound like the default. One whose
    # half is not whole lanes stays, and so do the small blocks of the
    # tests in this file.
    (True, 100, 768, 384),
    (True, 100, 384, 384),
    (True, 40, 64, 64),
])
def test_fit_band_rule(causal, window, block_k, want):
    from horovod_tpu.ops.attention import _fit_band

    assert _fit_band(block_k, causal, window) == want


@pytest.mark.parametrize("window,block_k,walk,most", [
    # Laguna's sliding layers: 2.97 times the band's pairs under
    # 512 x 1024 (the forward still), at most 2.0 times on the backward
    # kernels' fitted key block.
    (512, 512, (32, 31, 16), 2.0),
    # SmallThinker's window layers and plain causal keep 512 x 1024 and
    # their counts.
    (4096, 1024, (80, 60, 58), 1.25),
    (None, 1024, (128, 72, 70), 1.125),
])
def test_pairs_computed_over_the_band_at_the_cells_shapes(window, block_k,
                                                          walk, most):
    from horovod_tpu.ops.attention import (FLASH_DEFAULT_BLOCK_K,
                                           FLASH_DEFAULT_BLOCK_Q,
                                           _band_grid, _band_pairs,
                                           _fit_band)

    s, block_q = 8192, FLASH_DEFAULT_BLOCK_Q
    assert _fit_band(FLASH_DEFAULT_BLOCK_K, True, window) == block_k
    assert _band_grid(s, s, block_q, block_k, True, window)[0].walk() == walk
    computed, band = _band_pairs(s, s, block_q, block_k, True, window)
    # The band by hand: every row sees itself and up to window - 1 before.
    w = window or s
    assert band == w * (w + 1) // 2 + (s - w) * w
    assert computed == walk[1] * block_q * block_k
    assert band < computed <= most * band
    before = _band_pairs(s, s, block_q, FLASH_DEFAULT_BLOCK_K, True, window)
    assert before[1] == band and (before[0] > computed) == (window == 512)
    if window == 512:
        assert before[0] == 23 * block_q * 1024 > 2.96 * band


def test_band_pairs_by_hand():
    from horovod_tpu.ops.attention import _band_pairs

    # 8 positions, 2-blocks, window 3: rows see 1, 2, 3, 3, ... keys; a
    # query block's band touches key blocks qb - 1 and qb (block 0: one).
    assert _band_pairs(8, 8, 2, 2, True, 3) == (7 * 4, 1 + 2 + 6 * 3)
    # The decode convention: the 2 queries are positions 6 and 7.
    assert _band_pairs(2, 8, 2, 2, True, 3) == (2 * 4, 3 + 3)
    # No band: every pair, every tile.
    assert _band_pairs(4, 8, 2, 2, False, None) == (32, 32)
