"""Grouped-query attention over a sequence-parallel mesh: ring (both
layouts, with and without the flash kernels inside) and Ulysses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops.attention import reference_attention
from horovod_tpu.parallel import make_mesh
from horovod_tpu.parallel.sequence import ring_attention, ulysses_attention


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_attention_gqa(layout):
    """Ring attention with grouped K/V heads: the ring rotates Hkv-head
    blocks (Hkv/H the ICI bytes) and must match the gathered reference."""
    rng = np.random.RandomState(3)
    b, s, h, hkv, d = 2, 64, 4, 2, 8
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32)) * 0.3
    ref = reference_attention(q, k, v, causal=True)

    mesh = make_mesh({"seq": 8})
    if layout == "zigzag":
        from horovod_tpu.parallel.sequence import zigzag_shard, zigzag_unshard

        q_in, k_in, v_in = (zigzag_shard(x, 8) for x in (q, k, v))
    else:
        q_in, k_in, v_in = q, k, v

    f = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                       causal=True, layout=layout),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = f(q_in, k_in, v_in)
    if layout == "zigzag":
        out = zigzag_unshard(out, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_ring_attention_gqa_gradient():
    rng = np.random.RandomState(4)
    b, s, h, hkv, d = 1, 64, 4, 2, 8
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32)) * 0.3
    mesh = make_mesh({"seq": 8})

    def ring_loss(q, k, v):
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                           causal=True),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False)
        return (f(q, k, v).astype(jnp.float32) ** 2).sum()

    def ref_loss(q, k, v):
        return (reference_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    g0 = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g1 = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=1e-3)


def test_ulysses_gqa_heads_validation():
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 64, 8, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 64, 2, 8).astype(np.float32))
    mesh = make_mesh({"seq": 8})
    f = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="seq"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)
    with pytest.raises(ValueError, match="K/V heads"):
        f(q, k, k)


def test_ulysses_rejects_mismatched_v_heads():
    # Advisor round-2: a bad v shape must fail the GQA invariant check at
    # entry, not as a confusing inner-attention/collective error.
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 64, 8, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 64, 8, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 64, 4, 8).astype(np.float32))
    mesh = make_mesh({"seq": 8})
    f = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="seq"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)
    with pytest.raises(ValueError, match="ulysses_attention"):
        f(q, k, v)


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_attention_gqa_flash_inner(layout):
    """GQA through the Pallas inner kernel (use_flash=True forces it at
    short S; interpret mode runs the real kernel on CPU), forward and
    backward — the grouped dk/dv and the dlse term are exercised."""
    rng = np.random.RandomState(6)
    b, s, h, hkv, d = 1, 64, 4, 2, 8
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32)) * 0.3
    mesh = make_mesh({"seq": 8})
    from horovod_tpu.parallel.sequence import zigzag_shard, zigzag_unshard

    def ring_loss(q, k, v):
        if layout == "zigzag":
            q, k, v = (zigzag_shard(x, 8) for x in (q, k, v))
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                           causal=True, layout=layout,
                                           use_flash=True),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False)
        out = f(q, k, v)
        if layout == "zigzag":
            out = zigzag_unshard(out, 8)
        return (out.astype(jnp.float32) ** 2).sum(), out

    def ref_loss(q, k, v):
        out = reference_attention(q, k, v, causal=True)
        return (out.astype(jnp.float32) ** 2).sum(), out

    (l0, out0), g0 = jax.jit(jax.value_and_grad(
        ring_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (l1, out1), g1 = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               atol=2e-5, rtol=1e-4)
    for a, b_ in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=1e-3)


def test_ring_attention_rejects_bad_gqa_heads():
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 64, 6, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 64, 4, 8).astype(np.float32))
    mesh = make_mesh({"seq": 8})
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)
    with pytest.raises(ValueError, match="multiple of K/V heads"):
        f(q, k, k)


def test_ulysses_gqa_matches_reference():
    """Ulysses with grouped K/V: both head counts divide the axis; the
    full-sequence inner attention routes the groups."""
    rng = np.random.RandomState(8)
    b, s, h, hkv, d = 1, 64, 4, 2, 8
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32)) * 0.3
    ref = reference_attention(q, k, v, causal=True)

    mesh = make_mesh({"data": 4, "seq": 2})
    f = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="seq",
                                          causal=True),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False,
    ))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
