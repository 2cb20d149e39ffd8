"""The causal window (``window=``) through both flash kernel paths
against the XLA reference, which blocks the streamed kernels skip or
mask, and which their grids walk and fetch at all (``_band_grid``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_helpers import (B, D, H, KERNELS, PATHS, S,
                               _assert_grads_close, _rand, _sq_loss,
                               both_paths, kernel_grids)
from horovod_tpu.ops.attention import (flash_attention, make_attention_fn,
                                       reference_attention)


# --------------------------------------------------------------------------
# The window (PR 26): with ``causal``, query i sees the keys
# ``i - window < j <= i``. The streamed kernels skip blocks wholly outside
# the band and mask only the blocks an edge of it crosses; the one-tile
# kernels take the bound as one more term of their mask.
WINDOW_PATHS = {
    # 64 x 64 in one tile; the window cuts it (sk > window).
    "one_tile": dict(sq=64, sk=64, kw={}),
    # 16-blocks: with window 24 a query block sees 2-3 key blocks of 4-8.
    "streamed": dict(sq=128, sk=128, kw=dict(block_q=16, block_k=16)),
    # Unequal blocks: both edges can cross one block.
    "streamed_wide_k": dict(sq=128, sk=128, kw=dict(block_q=16, block_k=64)),
    # Decode convention: the 32 queries are the last of 128 positions.
    "streamed_sq_lt_sk": dict(sq=32, sk=128,
                              kw=dict(block_q=16, block_k=16)),
}


@pytest.mark.parametrize("path", sorted(WINDOW_PATHS))
@pytest.mark.parametrize("window", [2, 24, 40, 1000])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_window_matches_reference_causal_gqa(path, window, dtype):
    case = WINDOW_PATHS[path]
    dtype = jnp.dtype(dtype)
    q = _rand((B, case["sq"], 4, D), 70, dtype)
    k, v = (_rand((B, case["sk"], 2, D), 71 + i, dtype) for i in range(2))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, **case["kw"])
    ref = lambda q, k, v: reference_attention(  # noqa: E731
        q, k, v, causal=True, window=window)
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(ref(q, k, v), np.float32),
        atol=2e-5 if f32 else 2e-2, rtol=1e-4 if f32 else 2e-2)
    _assert_grads_close(flash, ref, q, k, v, 2e-3 if f32 else 5e-2)


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 256)])
def test_streamed_kernels_at_a_window_below_the_key_block(block_q, block_k):
    """The regime of a window-512 layer under the default 512 x 1024
    blocks (PR 32's cell), scaled down: window 128 below a key block of
    256, 8 query heads over 2, at 1024 positions. A query block sees one
    or two of the four key tiles, and the grid's inner axis is those two.
    Forward and both backward kernels against the XLA reference."""
    q = _rand((1, 1024, 8, 32), 60)
    k, v = (_rand((1, 1024, 2, 32), 61 + i) for i in range(2))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=128, block_q=block_q, block_k=block_k)
    ref = lambda q, k, v: reference_attention(  # noqa: E731
        q, k, v, causal=True, window=128)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=1e-4)
    _assert_grads_close(flash, ref, q, k, v, 2e-3)
    # Streamed, not one tile; and of a query block's key tiles at most
    # two are live.
    from horovod_tpu.ops.attention import _band_blocks, _one_tile_path

    assert not _one_tile_path(q, k, block_q, block_k)
    for qb in range(1024 // block_q):
        live = [bool(_band_blocks(128, qb, kb, block_q, block_k, 0)[0])
                for kb in range(1024 // block_k)]
        assert 1 <= sum(live) <= 2
    # The calls' own grids: two key steps a query block where the whole
    # sequence is four; dk/dv sweeps a group of 4 query heads over the
    # query blocks a key block's band touches, not over all of them.
    # (Three query blocks of 128 a key block, two of 256.)
    grids = kernel_grids(
        jax.grad(_sq_loss(flash), argnums=(0, 1, 2)), q, k, v)
    assert grids == {
        "hvd_flash_fwd": (8, 1024 // block_q, 2),
        "hvd_flash_bwd_dq": (8, 1024 // block_q, 2),
        "hvd_flash_bwd_dkv": (2, 1024 // block_k,
                              4 * {128: 3, 256: 2}[block_q]),
    }


def test_reference_window_is_the_band_written_out():
    # window 3 at 6 positions, by hand: row i averages v over i-2..i.
    v = jnp.arange(6, dtype=jnp.float32).reshape(1, 6, 1, 1)
    q = k = jnp.zeros((1, 6, 1, 1))
    out = reference_attention(q, k, v, causal=True, window=3)[0, :, 0, 0]
    np.testing.assert_allclose(out, [0.0, 0.5, 1.0, 2.0, 3.0, 4.0],
                               rtol=1e-6)


@both_paths
def test_flash_window_with_key_mask(path):
    q, k, v = (_rand((B, S, H, D), 80 + i) for i in range(3))
    mask_np = np.random.RandomState(83).rand(B, S) > 0.3
    mask = jnp.asarray(mask_np)     # rows whose whole window is masked
    flash = flash_attention(q, k, v, key_mask=mask, causal=True, window=5,
                            **PATHS[path])
    ref = reference_attention(q, k, v, key_mask=mask, causal=True, window=5)
    band = np.tril(np.ones((S, S), bool)) & ~np.tril(
        np.ones((S, S), bool), -5)
    live = (band[None] & mask_np[:, None, :]).any(-1)       # (B, S)
    np.testing.assert_allclose(np.asarray(flash)[live],
                               np.asarray(ref)[live], atol=2e-5, rtol=1e-4)
    assert not np.asarray(flash)[~live].any()       # zeros, as without


def test_window_needs_causal_and_a_positive_width():
    q = k = v = jnp.zeros((1, 16, 1, 8))
    for fn in (flash_attention, reference_attention):
        with pytest.raises(ValueError, match="needs causal=True"):
            fn(q, k, v, window=4)
        with pytest.raises(ValueError, match="at least 1"):
            fn(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match="needs causal=True"):
        make_attention_fn(window=4)


def test_make_attention_fn_hands_the_window_to_both_paths():
    q, k, v = (_rand((B, S, H, D), 90 + i) for i in range(3))
    want = reference_attention(q, k, v, causal=True, window=7)
    for use_flash in (True, False):
        got = make_attention_fn(causal=True, use_flash=use_flash,
                                window=7)(q, k, v, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)


def _band_bodies(s, window, **kw):
    """How many ``pl.when`` bodies each streamed kernel holds: a banded
    kernel has the init, the finalize and TWO bodies (one that builds the
    band's mask, for blocks an edge crosses, and one that does not)."""
    x = jax.ShapeDtypeStruct((1, s, 2, 32), jnp.float32)
    f = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=True, **kw).sum(),
        argnums=(0, 1, 2))
    found = {}

    def walk(jaxpr, name=None):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                found[name] = 0
            elif eqn.primitive.name == "cond" and name is not None:
                found[name] += 1
            for inner in jax.core.jaxprs_in_params(eqn.params):
                walk(inner, name)

    walk(jax.make_jaxpr(f)(x, x, x).jaxpr)
    return found


@pytest.mark.parametrize("window", [None, 40])
def test_streamed_kernels_mask_only_the_blocks_an_edge_crosses(window):
    assert _band_bodies(128, window, block_q=16, block_k=16) == \
        dict.fromkeys(KERNELS, 4)


def test_band_blocks_skip_and_edge_by_hand():
    from horovod_tpu.ops.attention import _band_blocks

    # 16-blocks, window 40, query block 4 (positions 64..79; query 64
    # sees keys 25..64, query 79 keys 40..79): key block 0 (0..15) lies
    # wholly below the band, blocks 1 and 2 (16..47) are crossed by the
    # window's edge, block 3 (48..63) lies wholly inside, block 4 holds
    # the diagonal, 5+ lie above it.
    got = [tuple(bool(x) for x in _band_blocks(40, 4, kb, 16, 16, 0))
           for kb in range(7)]
    assert got == [(False, False), (True, True), (True, True),
                   (True, False), (True, True), (False, False),
                   (False, False)]
    # No window: everything at or below the diagonal block is live, and
    # only the diagonal block is an edge.
    got = [tuple(bool(x) for x in _band_blocks(None, 4, kb, 16, 16, 0))
           for kb in range(6)]
    assert got == [(True, False)] * 4 + [(True, True), (False, False)]


# --------------------------------------------------------------------------
# The band's extent (PR 33): the streamed grids' inner axis counts the
# blocks of the band (``_band_grid``), its index maps follow the band and
# clamp past its end. ``_band_blocks`` stays the one definition of which
# block is live: the closed forms are held to a walk of it over every
# block, for both grid orders.
@pytest.mark.parametrize("sq,sk", [(512, 512), (128, 512), (512, 128)])
@pytest.mark.parametrize("block_q,block_k", [(16, 16), (16, 64), (64, 16),
                                             (32, 128), (128, 128)])
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
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=1e-4)
    _assert_grads_close(flash, ref, q, k, v, 2e-3)
    unmasked = flash_attention(q, k, v, key_mask=mask, causal=True,
                               window=c["window"], **c["kw"])
    assert not np.asarray(unmasked)[0, :max(sq - sk, 0)].any()
    dq = jax.grad(lambda q: (flash_attention(
        q, k, v, key_mask=mask, causal=True, window=c["window"],
        **c["kw"]) ** 2).sum())(q)
    assert not np.asarray(dq)[0, :max(sq - sk, 0)].any()
    if case == "group_8":
        queries = _band_grid(sq, sk, 32, 64, True, 48)[1]
        assert queries.extent == 4 < queries.inner == 8
