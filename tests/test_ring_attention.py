"""Sequence-parallel attention (ring, zigzag ring, Ulysses) over a ``seq``
mesh axis against the XLA reference, with and without the flash kernels
on each ring block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from attention_helpers import B, S, _qkv
from horovod_tpu.ops.attention import reference_attention
from horovod_tpu.parallel import make_mesh
from horovod_tpu.parallel.sequence import ring_attention, ulysses_attention


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention(causal):
    q, k, v = _qkv(4)
    mesh = make_mesh({"seq": 8})
    ref = reference_attention(q, k, v, causal=causal)

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=causal),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_zigzag(causal):
    # Zigzag layout: shard the sequence as block pairs (i, 2N-1-i) so causal
    # ring steps do balanced work; results must match plain attention after
    # the unshard.
    from horovod_tpu.parallel.sequence import zigzag_shard, zigzag_unshard

    q, k, v = _qkv(8)
    mesh = make_mesh({"seq": 8})
    ref = reference_attention(q, k, v, causal=causal)

    qz, kz, vz = (zigzag_shard(x, 8) for x in (q, k, v))
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=causal, layout="zigzag"),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = zigzag_unshard(f(qz, kz, vz), 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_zigzag_shard_roundtrip():
    from horovod_tpu.parallel.sequence import zigzag_shard, zigzag_unshard

    x = jnp.arange(2 * 32 * 3).reshape(2, 32, 3)
    back = zigzag_unshard(zigzag_shard(x, 4), 4)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_ring_attention_zigzag_gradient():
    from horovod_tpu.parallel.sequence import zigzag_shard, zigzag_unshard

    q, k, v = _qkv(9)
    mesh = make_mesh({"seq": 8})

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=True, layout="zigzag"),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))

    def loss_ring(q, k, v):
        qz, kz, vz = (zigzag_shard(x, 8) for x in (q, k, v))
        return (zigzag_unshard(f(qz, kz, vz), 8) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    gr_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gr_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gr_ring, gr_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_ring_attention_key_mask():
    q, k, v = _qkv(5)
    mask = jnp.asarray(np.random.RandomState(6).rand(B, S) > 0.3)
    mesh = make_mesh({"seq": 8})
    ref = reference_attention(q, k, v, key_mask=mask)
    f = jax.jit(jax.shard_map(
        lambda q, k, v, m: ring_attention(q, k, v, axis_name="seq",
                                          key_mask=m),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = f(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention(causal):
    q, k, v = _qkv(7)
    # H=2 heads must divide the axis size: use a 2-device submesh.
    mesh = make_mesh({"seq": 2}, devices=jax.devices()[:2])
    ref = reference_attention(q, k, v, causal=causal)
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="seq",
                                          causal=causal),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_ulysses_head_divisibility():
    q, k, v = _qkv()
    mesh = make_mesh({"seq": 8})
    f = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="seq"),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False,
    )
    with pytest.raises(ValueError, match="heads"):
        f(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_inner(causal):
    # Same semantics as the dense-block ring, with the Pallas kernel per
    # block (forced on at test sizes; auto only enables it >= 512 tokens).
    q, k, v = _qkv(11)
    mesh = make_mesh({"seq": 8})
    ref = reference_attention(q, k, v, causal=causal)

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=causal, use_flash=True),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_ring_attention_flash_inner_key_mask():
    q, k, v = _qkv(12)
    mask = jnp.asarray(np.random.RandomState(13).rand(B, S) > 0.3)
    mesh = make_mesh({"seq": 8})
    ref = reference_attention(q, k, v, key_mask=mask)

    f = jax.jit(jax.shard_map(
        lambda q, k, v, m: ring_attention(q, k, v, axis_name="seq",
                                          key_mask=m, use_flash=True),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = f(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("path", ["one_tile", "streamed"])
def test_ring_attention_flash_inner_gradient(path, monkeypatch):
    # The ring's per-shard blocks (8 rows here) are one tile at the
    # default blocks; 4-row blocks make the same shards stream. Either way
    # the cross-block merge differentiates through lse (``dlse``).
    if path == "streamed":
        import horovod_tpu.ops.attention as attention

        monkeypatch.setattr(attention, "FLASH_DEFAULT_BLOCK_Q", 4)
        monkeypatch.setattr(attention, "FLASH_DEFAULT_BLOCK_K", 4)
    q, k, v = _qkv(14)
    mesh = make_mesh({"seq": 8})

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=True, use_flash=True),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))

    def loss_ring(q, k, v):
        return (f(q, k, v).astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_zigzag(causal):
    # Zigzag + flash: each causal half-block streams through the Pallas
    # kernel; results must match plain attention after the unshard.
    from horovod_tpu.parallel.sequence import zigzag_shard, zigzag_unshard

    q, k, v = _qkv(15)
    mesh = make_mesh({"seq": 8})
    ref = reference_attention(q, k, v, causal=causal)

    qz, kz, vz = (zigzag_shard(x, 8) for x in (q, k, v))
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=causal, layout="zigzag",
                                       use_flash=True),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False))
    out = zigzag_unshard(f(qz, kz, vz), 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_ring_attention_flash_zigzag_gradient():
    from horovod_tpu.parallel.sequence import zigzag_shard, zigzag_unshard

    q, k, v = _qkv(17)
    mesh = make_mesh({"seq": 8})

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=True, layout="zigzag",
                                       use_flash=True),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False))

    def loss_ring(q, k, v):
        qz, kz, vz = (zigzag_shard(x, 8) for x in (q, k, v))
        return (zigzag_unshard(f(qz, kz, vz), 8) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_ulysses_auto_flash_long_seq():
    # From FLASH_AUTO_MIN_SEQ the resharded (full-sequence) attention takes
    # the Pallas kernel path; pin it against the reference.
    rng = np.random.RandomState(16)
    b, s, h, d = 1, 512, 2, 16
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3
    q, k, v = mk(), mk(), mk()
    mesh = make_mesh({"seq": 2}, devices=jax.devices()[:2])
    ref = reference_attention(q, k, v, causal=True)

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="seq",
                                          causal=True),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)


def test_ring_attention_flash_zigzag_key_mask():
    # Zigzag + flash + key mask: the mask halves must follow the zigzag
    # shard order alongside K/V. Non-fully-masked batch checked against
    # the reference (flash defines fully-masked rows as zeros).
    from horovod_tpu.parallel.sequence import zigzag_shard, zigzag_unshard

    q, k, v = _qkv(19)
    mask_np = np.random.RandomState(21).rand(B, S) > 0.3
    # Key 0 visible everywhere: under causal masking row i sees keys 0..i,
    # so this guarantees no fully-masked row — where flash (zeros) and the
    # reference (uniform softmax over all -inf) deliberately differ.
    mask_np[:, 0] = True
    mask = jnp.asarray(mask_np)
    mesh = make_mesh({"seq": 8})
    ref = reference_attention(q, k, v, key_mask=mask, causal=True)

    qz, kz, vz = (zigzag_shard(x, 8) for x in (q, k, v))
    mz = zigzag_shard(mask, 8, axis=1)
    f = jax.jit(jax.shard_map(
        lambda q, k, v, m: ring_attention(q, k, v, axis_name="seq",
                                          causal=True, layout="zigzag",
                                          key_mask=m, use_flash=True),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3 + (P(None, "seq"),),
        out_specs=P(None, "seq"), check_vma=False))
    out = zigzag_unshard(f(qz, kz, vz, mz), 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
