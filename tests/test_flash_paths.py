"""The two paths through the flash kernels (one tile, streamed), each
against the XLA reference, and the rule that chooses between them from
shapes alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_helpers import (B, D, H, KERNELS, PATHS, S, _rand,
                               assert_matches_reference, both_paths,
                               kernel_grids, out_and_grads)
from horovod_tpu.ops.attention import flash_attention, reference_attention


@both_paths
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_paths_forward_and_grad(path, dtype, masked, causal,
                                      reference_results):
    dtype = jnp.dtype(dtype)
    q, k, v = (_rand((B, S, H, D), 30 + i, dtype) for i in range(3))
    mask = None
    if masked:
        mask_np = np.random.RandomState(33).rand(B, S) > 0.3
        mask_np[:, 0] = True      # no fully-masked row, causal or not
        mask = jnp.asarray(mask_np)
    assert_matches_reference(
        lambda q, k, v: flash_attention(q, k, v, key_mask=mask,
                                        causal=causal, **PATHS[path]),
        lambda q, k, v: reference_attention(q, k, v, key_mask=mask,
                                            causal=causal),
        q, k, v, shared=(reference_results, (dtype, masked, causal)))


@both_paths
@pytest.mark.parametrize("sq,sk", [(16, 64), (32, 64)])
def test_flash_paths_causal_sq_ne_sk(path, sq, sk, reference_results):
    # Decode convention: the sq query rows are the LAST sq key positions.
    q = _rand((B, sq, H, D), 40)
    k, v = _rand((B, sk, H, D), 41), _rand((B, sk, H, D), 42)
    assert_matches_reference(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        **PATHS[path]),
        lambda q, k, v: reference_attention(q, k, v, causal=True),
        q, k, v, shared=(reference_results, ("sq_ne_sk", sq, sk)))


@both_paths
@pytest.mark.parametrize("how", ["key_mask", "causal_sq_gt_sk"])
def test_flash_paths_fully_masked_rows(path, how):
    # Rows with no allowed key: zeros out, zero (finite) gradients, and
    # the valid rows' gradients see nothing of them.
    if how == "key_mask":
        q, k, v = (_rand((B, S, H, D), 50 + i) for i in range(3))
        mask_np = np.random.RandomState(53).rand(B, S) > 0.3
        mask_np[0, :] = False
        mask = jnp.asarray(mask_np)
        dead = np.zeros((B, S), bool)
        dead[0] = True
        kw = dict(key_mask=mask)
    else:
        sq, sk = 64, 32
        q = _rand((B, sq, H, D), 54)
        k, v = _rand((B, sk, H, D), 55), _rand((B, sk, H, D), 56)
        dead = np.broadcast_to(np.arange(sq) < sq - sk, (B, sq))
        kw = dict(causal=True)
    flash = lambda q, k, v: flash_attention(q, k, v, **kw, **PATHS[path])  # noqa: E731

    def ref(q, k, v):
        out = reference_attention(q, k, v, **kw)
        return jnp.where(jnp.asarray(dead)[:, :, None, None], 0.0, out)

    out, gf = out_and_grads(flash, q, k, v)
    want, gr = out_and_grads(ref, q, k, v)
    out = np.asarray(out)
    np.testing.assert_array_equal(out[dead], 0.0)
    np.testing.assert_allclose(out, np.asarray(want), atol=2e-5, rtol=1e-4)
    assert all(np.isfinite(np.asarray(g)).all() for g in gf)
    np.testing.assert_array_equal(np.asarray(gf[0])[dead], 0.0)
    if how == "key_mask":      # batch 0 has no live key at all
        np.testing.assert_array_equal(np.asarray(gf[1])[0], 0.0)
        np.testing.assert_array_equal(np.asarray(gf[2])[0], 0.0)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("path,blocks", [
    ("one_tile", {}), ("streamed", {"block_q": 64, "block_k": 64})])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_paths_awkward_len_auto_pad(path, blocks, causal,
                                          reference_results):
    # ViT's 197 pads to 256: one tile at the defaults, four blocks a side
    # at 64; the pad mask joins the caller's own either way.
    s = 197
    q, k, v = (_rand((B, s, H, D), 70 + i) for i in range(3))
    mask = jnp.asarray(np.random.RandomState(73).rand(B, s) > 0.2
                       ).at[:, 0].set(True)
    assert_matches_reference(
        lambda q, k, v: flash_attention(q, k, v, key_mask=mask,
                                        causal=causal, **blocks),
        lambda q, k, v: reference_attention(q, k, v, key_mask=mask,
                                            causal=causal),
        q, k, v, shared=(reference_results, ("pad_197", causal)))


@both_paths
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,hkv,d", [
    (2, 2, 8),
    (12, 12, 64),     # one tile: a step's block is 4 heads of the 12
    (8, 2, 64),       # a query group of 4 over each K/V head
])
def test_flash_paths_lse_out_and_dlse_in(path, causal, h, hkv, d,
                                         reference_results):
    # What ring attention leans on: the forward returns the row
    # log-sum-exp, and the backward takes a cotangent on it (a shift of
    # delta); both stay (B * H, 1, S) rows whatever layout the kernels
    # take their operands in. Checked against jax's own vjp of a dense
    # (out, lse) pair.
    from horovod_tpu.ops.attention import (NEG_INF, _flash_backward,
                                           _flash_forward, repeat_kv)

    blocks = PATHS[path] or {"block_q": 512, "block_k": 1024}
    bq, bk = blocks["block_q"], blocks["block_k"]
    b, s = 2, 32
    q = _rand((b, s, h, d), 80)
    k, v = _rand((b, s, hkv, d), 81), _rand((b, s, hkv, d), 82)
    mask_np = np.random.RandomState(83).rand(b, s) > 0.3
    mask_np[:, 0] = True
    mask = jnp.asarray(mask_np)

    def dense(q, k, v):
        k, v = repeat_kv(q, k, v)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / d ** 0.5
        allowed = mask[:, None, None, :]
        if causal:
            allowed = allowed & (jnp.arange(s)[None, :]
                                 <= jnp.arange(s)[:, None])[None, None]
        logits = jnp.where(allowed, logits, NEG_INF)
        lse = jax.nn.logsumexp(logits, axis=-1)             # (b, h, s)
        out = jnp.einsum("bhqk,bkhd->bqhd",
                         jnp.exp(logits - lse[..., None]), v)
        return out, lse.reshape(b * h, 1, s)

    do, dlse = _rand(q.shape, 84), _rand((b * h, 1, s), 85)

    @jax.jit
    def kernels(q, k, v, do, dlse):
        out, lse = _flash_forward(q, k, v, mask, causal, None, bq, bk, True)
        return out, lse, _flash_backward(q, k, v, mask, out, lse, do,
                                         causal, None, bq, bk, True,
                                         dlse=dlse)

    @jax.jit
    def exact(q, k, v, do, dlse):
        (out, lse), vjp = jax.vjp(dense, q, k, v)
        return out, lse, vjp((do, dlse))

    case = ("lse", causal, h, hkv, d)
    if case not in reference_results:
        reference_results[case] = exact(q, k, v, do, dlse)
    ref_out, ref_lse, ref_grads = reference_results[case]
    out, lse, got = kernels(q, k, v, do, dlse)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-5, rtol=1e-4)
    assert lse.shape == ref_lse.shape
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5, rtol=1e-5)
    for a, r in zip(got, ref_grads):
        assert a.shape == r.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=2e-5, rtol=1e-3)


def _kernel_grids(s, d=16, h=1, hkv=None, **kw):
    """``{pallas_call name: rank of its grid}`` of a traced ``jax.grad``
    of flash attention. Both paths call their kernels by the same three
    names (the benchmark's per-kernel metrics read them); what tells them
    apart is the grid: (batch, K/V heads) on the one-tile path, (heads,
    q blocks, k blocks) where the kernels stream."""
    x = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, hkv or h, d), jnp.bfloat16)
    m = jax.ShapeDtypeStruct((1, s), jnp.bool_)
    f = jax.grad(lambda q, k, v, m: flash_attention(
        q, k, v, key_mask=m, interpret=True, **kw).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    grids = kernel_grids(f, x, kv, kv, m)
    return {name: len(grids[name]) for name in sorted(grids)}


ONE_TILE_GRIDS = dict.fromkeys(KERNELS, 2)
STREAMED_GRIDS = dict.fromkeys(KERNELS, 3)


@pytest.mark.parametrize("case,s,kw,grids", [
    # BERT-base s512's own shape: one tile at the default blocks.
    ("s512_defaults", 512, dict(d=64, h=2), ONE_TILE_GRIDS),
    ("s512_causal_gqa", 512, dict(d=64, h=4, hkv=2, causal=True),
     ONE_TILE_GRIDS),
    ("s128_defaults", 128, {}, ONE_TILE_GRIDS),
    # A window below sk cuts the tile, not the path: the band's fit
    # (PR 37) comes after the path is chosen, in the streamed backward.
    ("s512_window_below_sk", 512,
     dict(d=64, h=4, hkv=2, causal=True, window=128), ONE_TILE_GRIDS),
    # Past one default block a side the kernels stream, as before.
    ("s2048_defaults", 2048, {}, STREAMED_GRIDS),
    ("s1024_defaults", 1024, {}, STREAMED_GRIDS),     # two query blocks
    ("s512_small_blocks", 512, dict(block_q=128, block_k=128),
     STREAMED_GRIDS),
    ("s512_one_side", 512, dict(block_k=256), STREAMED_GRIDS),
    # One block a side by request, but the tile is past the VMEM rule.
    ("s2048_one_block_declined", 2048,
     dict(block_q=2048, block_k=2048), STREAMED_GRIDS),
])
def test_flash_path_is_chosen_from_shapes(case, s, kw, grids):
    assert _kernel_grids(s, **kw) == grids


def _grad_grids(sq, sk, h, hkv, **kw):
    q = jax.ShapeDtypeStruct((1, sq, h, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, sk, hkv, 128), jnp.bfloat16)
    return kernel_grids(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, **kw).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("window", [None, 64, 256, 1000])
def test_one_tile_neighbours_keep_their_path_under_a_window(window):
    # The largest tile of the default blocks, 512 queries on 1024 keys,
    # with a query group: ``_one_tile_path`` compares the sequence-fitted
    # blocks with (sq, sk), so no window narrower than the key block
    # turns the call into a streamed one, forward or backward.
    grids = _grad_grids(512, 1024, 4, 2, window=window)
    assert {name: len(grids[name]) for name in sorted(grids)} == \
        ONE_TILE_GRIDS


@pytest.mark.parametrize("sq,sk,window,block_k", [
    (2048, 2048, 512, 512),         # Laguna's sliding layers, shorter
    (2048, 2048, 100, 128),
    (1024, 4096, 256, 256),         # the decode convention
    (2048, 2048, 1024, 1024),       # a window of the key block's width
    (2048, 2048, None, 1024),
])
def test_streamed_kernels_take_their_blocks_from_one_place(sq, sk, window,
                                                           block_k):
    # Each kernel's grid is the band's at its blocks. The forward's are
    # the defaults fitted to the sequence, (heads, sq / 512, key steps);
    # dq and dk/dv agree on theirs, the same with the key block fitted to
    # the band as well: dq (heads, sq / 512, key steps), dk/dv (K/V heads,
    # sk / block_k, group x query steps).
    from horovod_tpu.ops.attention import _band_grid, _fit_band

    assert _fit_band(1024, True, window) == block_k
    keys, _ = _band_grid(sq, sk, 512, 1024, True, window)
    fit_keys, fit_queries = _band_grid(sq, sk, 512, block_k, True, window)
    assert _grad_grids(sq, sk, 8, 2, window=window) == {
        "hvd_flash_fwd": (8, sq // 512, keys.extent),
        "hvd_flash_bwd_dq": (8, sq // 512, fit_keys.extent),
        "hvd_flash_bwd_dkv": (2, sk // block_k, 4 * fit_queries.extent),
    }


def test_one_tile_rule():
    from horovod_tpu.ops.attention import _one_tile_heads

    # BERT-base s512, Llama-style GQA at 512, f32 tests: taken.
    assert _one_tile_heads(512, 512, 64, 2, group=1, hkv=12) >= 1
    assert _one_tile_heads(512, 512, 128, 2, group=4, hkv=8) >= 1
    assert _one_tile_heads(512, 1024, 128, 4, group=1, hkv=8) >= 1
    # Heads a step always divide the K/V heads (one mask row a step).
    for hkv in (1, 2, 3, 8, 12):
        n = _one_tile_heads(128, 128, 64, 2, group=1, hkv=hkv)
        assert n >= 1 and hkv % n == 0
    # Tiles whose f32 scores alone overrun scoped VMEM: declined.
    assert _one_tile_heads(1024, 1024, 64, 2, group=1, hkv=8) == 0
    assert _one_tile_heads(2048, 2048, 64, 2, group=1, hkv=8) == 0
    # A query group too large to hold beside the tile: declined.
    assert _one_tile_heads(512, 1024, 128, 4, group=32, hkv=1) == 0
