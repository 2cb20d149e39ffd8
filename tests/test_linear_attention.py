"""``ops/linear_attention.py`` at small sizes on the CPU: the chunked gated
delta rule against the token-by-token recurrence (outputs, the state and
all five gradients), and the two pointwise parts of a layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import linear_attention as la


def _inputs(seed, b=2, s=128, h=3, d_k=16, d_v=32, dtype=jnp.float32,
            alike=0.0, g_range=(-6.0, 1.0), beta_shift=0.0):
    """q and k as a layer hands them over (L2-normed, q scaled); ``alike``
    adds a common part to every key, ``g = -exp(uniform(g_range))``,
    ``beta = 2 sigmoid(2 normal + beta_shift)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = la.l2_normalize(jax.random.normal(ks[0], (b, s, h, d_k))) \
        * d_k ** -0.5
    k = la.l2_normalize(jax.random.normal(ks[1], (b, s, h, d_k)) + alike)
    v = jax.random.normal(ks[2], (b, s, h, d_v))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h), minval=g_range[0],
                                    maxval=g_range[1]))
    beta = 2.0 * jax.nn.sigmoid(
        2.0 * jax.random.normal(ks[4], (b, s, h)) + beta_shift)
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


@functools.lru_cache(maxsize=None)
def _chunked(chunk):
    """One compiled program a chunk size, whatever test asks."""
    return jax.jit(lambda *a: la.gated_delta_rule(
        *a, chunk=chunk, output_final_state=True))


_reference = jax.jit(lambda *a: la.reference_gated_delta_rule(
    *a, output_final_state=True))


def _close(ours, theirs, tol):
    scale = float(jnp.max(jnp.abs(theirs))) + 1e-30
    assert float(jnp.max(jnp.abs(ours.astype(jnp.float32) - theirs))) \
        <= tol * scale


EDGES = {
    "plain": {},
    "keys-alike": {"alike": 1.0},
    "beta-near-2": {"beta_shift": 8.0},
    "alpha-near-0": {"g_range": (2.0, 4.0)},      # alpha e^-7 to e^-55
    "alpha-near-1": {"g_range": (-14.0, -9.0)},
}


@pytest.mark.parametrize("edge", sorted(EDGES))
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunked_equals_token_by_token_in_float32(chunk, edge):
    args = _inputs(1, **EDGES[edge])
    o, state = _chunked(chunk)(*args)
    ref, ref_state = _reference(*args)
    _close(o, ref, 1e-5)
    _close(state, ref_state, 1e-5)


@pytest.mark.parametrize("edge", ["plain", "keys-alike", "beta-near-2"])
def test_all_five_gradients_equal_token_by_token(edge):
    args = _inputs(2, **EDGES[edge])
    target = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    ours = jax.jit(jax.grad(
        lambda *a: jnp.sum(la.gated_delta_rule(*a) * target),
        argnums=(0, 1, 2, 3, 4)))(*args)
    theirs = jax.jit(jax.grad(
        lambda *a: jnp.sum(la.reference_gated_delta_rule(*a) * target),
        argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(ours, theirs):
        _close(a, b, 1e-5)


def test_bfloat16_operands_stay_within_their_rounding():
    """bf16 q, k, v with float32 state, decays and triangular system: the
    outputs within 1.5e-2 of the largest and the gradients within 3e-2 of
    theirs (a bf16 operand carries 8 bits: 4e-3 a rounding, a few of them
    in a row through T, U and the state)."""
    args = _inputs(3, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    target = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    o = jax.jit(la.gated_delta_rule)(*args)
    assert o.dtype == jnp.bfloat16
    _close(o, _reference(*exact)[0], 1.5e-2)
    ours = jax.jit(jax.grad(lambda *a: jnp.sum(
        la.gated_delta_rule(*a).astype(jnp.float32) * target),
        argnums=(0, 1, 2, 3, 4)))(*args)
    theirs = jax.jit(jax.grad(lambda *a: jnp.sum(
        la.reference_gated_delta_rule(*a) * target),
        argnums=(0, 1, 2, 3, 4)))(*exact)
    for a, b in zip(ours, theirs):
        _close(a, b, 3e-2)


@pytest.mark.parametrize("s", [1, 37, 100])
def test_a_sequence_that_is_no_multiple_of_the_chunk(s):
    args = _inputs(4, s=s)
    o, state = _chunked(16)(*args)
    ref, ref_state = _reference(*args)
    assert o.shape == ref.shape
    _close(o, ref, 1e-5)
    _close(state, ref_state, 1e-5)


def test_chunk_must_be_a_power_of_two():
    with pytest.raises(ValueError, match="power of"):
        la.gated_delta_rule(*_inputs(5, s=48), chunk=24)


def test_state_resets_are_not_free():
    """The state crosses chunks: running each chunk from a zero state is
    another function."""
    q, k, v, g, beta = _inputs(6, s=64, g_range=(-6.0, -3.0))
    rule = jax.jit(lambda *a: la.gated_delta_rule(*a, chunk=16))
    whole = rule(q, k, v, g, beta)
    apart = rule(*(x.reshape((8, 16) + x.shape[2:])
                   for x in (q, k, v, g, beta))).reshape(whole.shape)
    np.testing.assert_allclose(whole[:, :16], apart[:, :16], atol=1e-6)
    assert float(jnp.max(jnp.abs(whole[:, 16:] - apart[:, 16:]))) > 1e-2


def test_convolution_sees_no_later_token():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 6))
    w = jax.random.uniform(jax.random.PRNGKey(1), (4, 6), minval=-0.5,
                           maxval=0.5)
    conv = jax.jit(la.causal_conv_silu)
    y = conv(x, w)
    t = 11
    moved = conv(x.at[:, t].add(1.0), w)
    np.testing.assert_array_equal(y[:, :t], moved[:, :t])
    assert bool(jnp.all(jnp.any(y[:, t:t + 4] != moved[:, t:t + 4],
                                axis=-1)))
    np.testing.assert_array_equal(y[:, t + 4:], moved[:, t + 4:])
    # By hand: taps on tokens t-3..t, zeros before the sequence.
    by_hand = jax.nn.silu(sum(
        w[i] * (x[:, t - 3 + i] if t - 3 + i >= 0 else 0.0)
        for i in range(4)))
    np.testing.assert_allclose(y[:, t], by_hand, rtol=1e-5, atol=1e-6)
    first = jax.nn.silu(w[3] * x[:, 0])
    np.testing.assert_allclose(y[:, 0], first, rtol=1e-5, atol=1e-6)


def test_gated_head_norm_and_l2_normalize():
    o = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8))
    gate = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, 8))
    scale = jnp.linspace(0.5, 1.5, 8)
    got = la.gated_head_norm(o, gate, scale, 1e-6)
    want = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * scale \
        * gate * jax.nn.sigmoid(gate)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    unit = la.l2_normalize(o)
    np.testing.assert_allclose(jnp.sum(unit * unit, -1), 1.0, rtol=1e-5)
    assert la.l2_normalize(o.astype(jnp.bfloat16)).dtype == jnp.bfloat16
