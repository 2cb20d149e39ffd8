"""The parts of the benchmark's plain float32 reference for LFM2
(``benchmarks/reference/lfm2-24b-a2b.py``) against what they stand for:
attention in blocks of queries is the masked softmax, the convolution is
three shifted sums, the routing weights are a hand count's. The model
against the reference through three AdamW steps, the bias left as it was
on both sides, is ``test_lfm2.py``."""

import jax
import jax.numpy as jnp
import numpy as np

from lfm2_helpers import reference  # noqa: F401


def test_reference_attention_in_blocks_is_the_masked_softmax(
        reference, monkeypatch):
    """The reference's attention, a block of 64 queries at a time, is the
    causal softmax over all keys, with a group of 4 query heads a key
    head."""
    from horovod_tpu.ops.attention import reference_attention

    monkeypatch.setattr(reference, "QUERY_BLOCK", 64)
    q = jax.random.normal(jax.random.PRNGKey(0), (256, 8, 16))
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (256, 2, 16))
            for i in (1, 2))
    ours = jax.jit(lambda q, k, v: reference._attention(
        lambda a: a, q, k, v))(q, k, v)
    want = jax.jit(lambda q, k, v: reference_attention(
        q[None], k[None], v[None], causal=True)[0])(q, k, v)
    np.testing.assert_allclose(ours, want, rtol=0, atol=2e-6)


def test_reference_convolution_is_three_shifted_sums_by_hand(reference):
    x = jnp.arange(1.0, 11.0).reshape(5, 2)
    taps = jnp.array([[100.0, 0.5], [10.0, 0.0], [1.0, 2.0]])
    got = reference._short_conv(lambda a: a, x, taps)
    # Channel 0: 100 x[t-2] + 10 x[t-1] + x[t] on 1, 3, 5, 7, 9.
    np.testing.assert_allclose(got[:, 0], [1, 13, 135, 357, 579])
    # Channel 1: 0.5 x[t-2] + 2 x[t] on 2, 4, 6, 8, 10.
    np.testing.assert_allclose(got[:, 1], [4, 8, 13, 18, 23])


def test_reference_routing_weights_by_hand(reference):
    config = {"num_experts_per_tok": 2, "routed_scaling_factor": 1.0}
    scores = jnp.array([[0.9, 0.8, 0.7, 0.1], [0.2, 0.3, 0.6, 0.5]])
    bias = jnp.array([0.0, 0.0, 0.15, 0.0])
    got = reference.routing_weights(scores, bias, config)
    # Token 0: the bias lifts expert 2 over expert 1 (0.85 > 0.8); its
    # weight is its own score, 0.7, not 0.85.
    np.testing.assert_allclose(
        got[0], [0.9 / (1.6 + 1e-6), 0, 0.7 / (1.6 + 1e-6), 0], rtol=1e-6)
    np.testing.assert_allclose(
        got[1], [0, 0, 0.6 / (1.1 + 1e-6), 0.5 / (1.1 + 1e-6)], rtol=1e-6)
    plain = reference.routing_weights(scores, 0.0 * bias, config)
    assert np.flatnonzero(plain[0]).tolist() == [0, 1]
