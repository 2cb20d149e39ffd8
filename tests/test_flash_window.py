"""The causal window (``window=``) through both flash kernel paths
against the XLA reference, and which blocks the streamed kernels skip or
mask. What their grids walk and fetch at all (``_band_grid``) and the
blocks they take (``_fit_band``) are ``test_flash_band.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_helpers import (B, D, H, KERNELS, PATHS, S, _rand, _sq_loss,
                               assert_matches_reference, both_paths,
                               kernel_grids)
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
def test_flash_window_matches_reference_causal_gqa(path, window, dtype,
                                                   reference_results):
    case = WINDOW_PATHS[path]
    dtype = jnp.dtype(dtype)
    q = _rand((B, case["sq"], 4, D), 70, dtype)
    k, v = (_rand((B, case["sk"], 2, D), 71 + i, dtype) for i in range(2))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, **case["kw"])
    ref = lambda q, k, v: reference_attention(  # noqa: E731
        q, k, v, causal=True, window=window)
    # The two streamed cases of 128 x 128 positions share their reference.
    assert_matches_reference(
        flash, ref, q, k, v, shared=(
            reference_results, (case["sq"], case["sk"], window, dtype)))


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 256)])
def test_streamed_kernels_at_a_window_below_the_key_block(block_q, block_k):
    """The regime of a window-512 layer under the default 512 x 1024
    blocks (PR 32's cell), scaled down: window 128 below a key block of
    256, 8 query heads over 2, at 1024 positions. A query block of the
    forward sees one or two of the four key tiles, and the grid's inner
    axis is those two; since PR 37 the backward kernels' key block is
    fitted to the band (``_fit_band``): 128, the window's width, two or
    three of eight tiles a query block. Forward and both backward kernels
    against the XLA reference."""
    q = _rand((1, 1024, 8, 32), 60)
    k, v = (_rand((1, 1024, 2, 32), 61 + i) for i in range(2))
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=128, block_q=block_q, block_k=block_k)
    ref = lambda q, k, v: reference_attention(  # noqa: E731
        q, k, v, causal=True, window=128)
    assert_matches_reference(flash, ref, q, k, v)
    # Streamed, not one tile; and of a query block's key tiles at most
    # two are live in the forward.
    from horovod_tpu.ops.attention import (_band_blocks, _fit_band,
                                           _one_tile_path)

    assert not _one_tile_path(q, k, block_q, block_k)
    assert _fit_band(block_k, True, 128) == 128
    for qb in range(1024 // block_q):
        live = [bool(_band_blocks(128, qb, kb, block_q, block_k, 0)[0])
                for kb in range(1024 // block_k)]
        assert 1 <= sum(live) <= 2
    # The calls' own grids. Forward: two key steps a query block where
    # the whole sequence is four. dq, on key tiles of 128: two or three of
    # eight. dk/dv sweeps a group of 4 query heads over the query blocks a
    # key block's band touches (two), not over all of them.
    grids = kernel_grids(
        jax.grad(_sq_loss(flash), argnums=(0, 1, 2)), q, k, v)
    assert grids == {
        "hvd_flash_fwd": (8, 1024 // block_q, 2),
        "hvd_flash_bwd_dq": (8, 1024 // block_q, 1 + block_q // 128),
        "hvd_flash_bwd_dkv": (2, 1024 // 128, 4 * 2),
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
