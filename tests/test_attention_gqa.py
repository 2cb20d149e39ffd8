"""Grouped-query attention through the flash kernels (K/V carry fewer
heads, no repeat) against the XLA reference, on both kernel paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_helpers import (PATHS, _rand, assert_matches_reference,
                               both_paths)
from horovod_tpu.ops import attention
from model_helpers import jit_apply, jit_init

# One compiled program a call (test_flash_attention.py has the reason).
flash_attention = jax.jit(attention.flash_attention, static_argnames=(
    "causal", "block_q", "block_k"))
reference_attention = jax.jit(attention.reference_attention,
                              static_argnames=("causal",))


class TestGroupedQueryAttention:
    """GQA: k/v carry fewer heads; the kernel routes query-head groups to
    their K/V row via index_maps (no repeat)."""

    def _qkv(self, b=2, sq=32, sk=32, h=4, hkv=2, d=8, seed=0):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, sq, h, d) * 0.3, jnp.float32)
        k = jnp.asarray(rng.randn(b, sk, hkv, d) * 0.3, jnp.float32)
        v = jnp.asarray(rng.randn(b, sk, hkv, d) * 0.3, jnp.float32)
        return q, k, v

    def test_forward_matches_repeated_mha(self):
        q, k, v = self._qkv()
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        k_rep = jnp.repeat(k, 2, axis=2)
        v_rep = jnp.repeat(v, 2, axis=2)
        ref = flash_attention(q, k_rep, v_rep, causal=True,
                              block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_forward_matches_xla_reference(self):
        q, k, v = self._qkv()
        mask = jnp.asarray(
            np.random.RandomState(1).rand(2, 32) > 0.25)
        out = flash_attention(q, k, v, key_mask=mask, block_q=16, block_k=16)
        ref = reference_attention(q, k, v, key_mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_grads_match_xla_reference(self):
        q, k, v = self._qkv()

        def loss(fn):
            return lambda q, k, v: (
                fn(q, k, v).astype(jnp.float32) ** 2).sum()

        flash = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=16, block_k=16)
        ref = lambda q, k, v: reference_attention(q, k, v, causal=True)  # noqa: E731
        g0 = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        g1 = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
        # dk/dv include the group sum over each K/V head's query heads.
        for a, b in zip(g0, g1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    def test_rejects_indivisible_heads(self):
        q, k, v = self._qkv(h=4, hkv=3)
        with pytest.raises(ValueError, match="multiple"):
            flash_attention(q, k, v)

    def test_grads_causal_sq_ne_sk(self):
        # GQA grid (b*hkv rows, group swept in-kernel) combined with the
        # sq != sk decode-convention diagonal offset.
        q, k, v = self._qkv(sq=16, sk=32)

        def loss(fn):
            return lambda q, k, v: (
                fn(q, k, v).astype(jnp.float32) ** 2).sum()

        flash = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=8, block_k=8)
        ref = lambda q, k, v: reference_attention(q, k, v, causal=True)  # noqa: E731
        g0 = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        g1 = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g0, g1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    def test_llama_gqa_no_repeat_matches_repeat_path(self):
        """LlamaAttention with a supports_gqa fn must equal the repeated
        twin (same params; only the K/V routing differs). The twin's fn
        deliberately LACKS supports_gqa, so LlamaAttention takes the
        jnp.repeat branch and the fn sees full-head K/V."""
        from horovod_tpu.models import LLAMA_TINY, LlamaLM
        from horovod_tpu.ops.attention import make_attention_fn

        ids = jnp.asarray(
            np.random.RandomState(0).randint(0, LLAMA_TINY.vocab_size,
                                             (1, 32)), jnp.int32)

        def repeat_path_fn(q, k, v, mask):  # no supports_gqa attribute
            assert k.shape[2] == q.shape[2], "repeat branch not taken"
            return reference_attention(q, k, v, key_mask=mask, causal=True)

        repeat_model = LlamaLM(LLAMA_TINY, attention_fn=repeat_path_fn)
        variables = jit_init(repeat_model, ids)
        gqa_model = LlamaLM(LLAMA_TINY, attention_fn=make_attention_fn(
            causal=True, use_flash=True, block_q=16, block_k=16))
        out_repeat = jit_apply(repeat_model)(variables, ids)
        out_gqa = jit_apply(gqa_model)(variables, ids)
        np.testing.assert_allclose(np.asarray(out_repeat, np.float32),
                                   np.asarray(out_gqa, np.float32),
                                   atol=5e-2, rtol=5e-2)


@both_paths
@pytest.mark.parametrize("causal,sq", [(False, 32), (True, 32), (True, 16)])
def test_flash_paths_gqa(path, causal, sq, reference_results):
    # Hkv < H: dk/dv come out at Hkv heads, each the sum over its query
    # group; with sq != sk also the decode-convention diagonal.
    b, sk, h, hkv, d = 2, 32, 4, 2, 8
    q = _rand((b, sq, h, d), 60)
    k, v = _rand((b, sk, hkv, d), 61), _rand((b, sk, hkv, d), 62)
    mask = jnp.asarray(np.random.RandomState(63).rand(b, sk) > 0.25
                       ).at[:, 0].set(True)
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, key_mask=mask, causal=causal, **PATHS[path])
    ref = lambda q, k, v: reference_attention(  # noqa: E731
        q, k, v, key_mask=mask, causal=causal)
    case = ("paths_gqa", causal, sq)
    out, (dq, dk, dv) = assert_matches_reference(
        flash, ref, q, k, v, shared=(reference_results, case))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(reference_results[case][0]), atol=1e-5)
    assert dq.shape == q.shape and dk.shape == k.shape == dv.shape
