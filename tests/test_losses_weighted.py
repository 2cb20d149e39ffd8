"""``weighted_chunked_causal_lm_loss`` (PR 48): the head's fused sweep
under per-position weights that are differentiated, for one or several
exits through one head. Value and all three gradients against autodiff of
the unchunked weighted loss; stacked exits against separate calls; weights
of all ones against the unweighted sweep, bit for bit; and the unweighted
sweep's lowered text under its old callers' shapes against the parent
commit's. Every call one jitted program."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (chunked_causal_lm_loss, token_nll,
                                weighted_chunked_causal_lm_loss)

DIM, VOCAB, SEQ = 64, 512, 16


def _problem(rows, dtype, batch=None, seed=3):
    """Hidden states (rows, SEQ, DIM), a head kernel, ids (batch, SEQ)
    and positive weights (rows, SEQ) that differ position by position."""
    k_h, k_w, k_i, k_p = jax.random.split(jax.random.PRNGKey(seed), 4)
    hidden = jax.random.normal(k_h, (rows, SEQ, DIM), jnp.float32)
    kernel = 0.2 * jax.random.normal(k_w, (DIM, VOCAB), jnp.float32)
    ids = jax.random.randint(k_i, (batch or rows, SEQ), 0, VOCAB)
    weights = jax.random.uniform(k_p, (rows, SEQ), jnp.float32, 0.1, 1.0)
    return hidden.astype(dtype), kernel, ids, weights


def _plain(ids, ahead=1):
    """The unchunked weighted loss on full logits, for autodiff: exits
    stacked on the batch axis, the count that of ``ids``' positions."""
    def loss(h, w, p):
        exits = h.shape[0] // ids.shape[0]
        targets = jnp.tile(ids, (exits, 1))[:, ahead:]
        nll = token_nll((h @ w.astype(h.dtype))[:, :-ahead], targets)
        return (p[:, :-ahead] * nll).sum() / (
            ids.shape[0] * (ids.shape[1] - ahead))

    return loss


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-12)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("num_chunks,ahead", [(1, 1), (4, 1), (8, 2)])
def test_value_and_three_gradients_match_autodiff_of_the_unchunked_loss(
        num_chunks, ahead, dtype, tol):
    hidden, kernel, ids, weights = _problem(2, dtype)

    def swept(h, w, p):
        return weighted_chunked_causal_lm_loss(
            h, w, ids, p, num_chunks=num_chunks, ahead=ahead)

    l0, g0 = jax.jit(jax.value_and_grad(_plain(ids, ahead),
                                        argnums=(0, 1, 2)))(
        hidden, kernel, weights)
    l1, g1 = jax.jit(jax.value_and_grad(swept, argnums=(0, 1, 2)))(
        hidden, kernel, weights)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    # Differentiated or not, the same loss to the bit.
    assert float(l1) == float(jax.jit(swept)(hidden, kernel, weights))
    for a, b in zip(g0, g1):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert _rel(a, b) < tol, (_rel(a, b), tol)
    # A weight's gradient is its position's own nll over the count: zero
    # where there is no target, positive elsewhere.
    dp = np.asarray(g1[2])
    assert not dp[:, -ahead:].any() and (dp[:, :-ahead] > 0).all()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 1e-2)])
def test_stacked_exits_are_the_sum_of_separate_calls(dtype, tol):
    """Four exits of two sequences in one sweep against four calls, each
    on its own exit: the value, every exit's states' and weights'
    gradients, and the head's gradient summed over the calls. The count
    is the two sequences', not the eight rows'."""
    exits, batch = 4, 2
    hidden, kernel, ids, weights = _problem(exits * batch, dtype,
                                            batch=batch)

    def stacked(h, w, p):
        return weighted_chunked_causal_lm_loss(h, w, ids, p, num_chunks=4)

    def separate(h, w, p):
        return sum(weighted_chunked_causal_lm_loss(
            h[e * batch:(e + 1) * batch], w, ids,
            p[e * batch:(e + 1) * batch], num_chunks=4)
            for e in range(exits))

    l0, g0 = jax.jit(jax.value_and_grad(separate, argnums=(0, 1, 2)))(
        hidden, kernel, weights)
    l1, g1 = jax.jit(jax.value_and_grad(stacked, argnums=(0, 1, 2)))(
        hidden, kernel, weights)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(g0, g1):
        assert _rel(a, b) < tol, (_rel(a, b), tol)
    # With a distribution over the exits as the weights the value is an
    # expectation: no larger than the worst exit's own mean.
    p = jnp.full((exits * batch, SEQ), 1.0 / exits)
    means = [float(jax.jit(chunked_causal_lm_loss, static_argnames=(
        "num_chunks",))(hidden[e * batch:(e + 1) * batch], kernel, ids,
                        num_chunks=4)) for e in range(exits)]
    np.testing.assert_allclose(
        float(jax.jit(stacked)(hidden, kernel, p)), np.mean(means),
        rtol=2e-3 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ahead", [1, 2])
def test_weights_of_all_ones_are_the_unweighted_loss_to_the_last_bit(
        dtype, ahead):
    hidden, kernel, ids, _ = _problem(2, dtype)
    ones = jnp.ones((2, SEQ), jnp.float32)
    l0, g0 = jax.jit(jax.value_and_grad(
        lambda h, w: chunked_causal_lm_loss(h, w, ids, num_chunks=4,
                                            ahead=ahead),
        argnums=(0, 1)))(hidden, kernel)
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda h, w: weighted_chunked_causal_lm_loss(
            h, w, ids, ones, num_chunks=4, ahead=ahead),
        argnums=(0, 1)))(hidden, kernel)
    assert float(l0) == float(l1)
    for a, b in zip(g0, g1):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_takes_a_cotangent_that_is_not_one():
    hidden, kernel, ids, weights = _problem(2, jnp.float32)

    def swept(h, w, p):
        return weighted_chunked_causal_lm_loss(h, w, ids, p, num_chunks=4)

    def wrap(loss):
        return lambda h, w, p: 3.0 * loss(h, w, p) * loss(h, 2.0 * w, p * p)

    g0 = jax.jit(jax.grad(wrap(_plain(ids)), argnums=(0, 1, 2)))(
        hidden, kernel, weights)
    g1 = jax.jit(jax.grad(wrap(swept), argnums=(0, 1, 2)))(
        hidden, kernel, weights)
    for a, b in zip(g0, g1):
        assert _rel(a, b) < 1e-6, _rel(a, b)


def test_one_loop_three_products_a_chunk_and_nothing_recomputed():
    hidden, kernel, ids, weights = _problem(8, jnp.bfloat16, batch=2)

    def products(jaxpr):
        loops = found = 0
        for eqn in jaxpr.eqns:
            loops += eqn.primitive.name in ("scan", "while")
            found += eqn.primitive.name == "dot_general" and any(
                VOCAB in v.aval.shape for v in eqn.invars + eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                inner = products(sub)
                loops, found = loops + inner[0], found + inner[1]
        return loops, found

    def swept(h, w, p):
        return weighted_chunked_causal_lm_loss(h, w, ids, p, num_chunks=4)

    assert products(jax.make_jaxpr(swept)(hidden, kernel, weights).jaxpr) \
        == (1, 1)
    assert products(jax.make_jaxpr(jax.value_and_grad(
        swept, argnums=(0, 1, 2)))(hidden, kernel, weights).jaxpr) == (1, 3)


@pytest.mark.parametrize("hidden,weights,ids,why", [
    ((3, SEQ, DIM), (3, SEQ), (2, SEQ), "whole number of exits"),
    ((2, SEQ, DIM), (2, SEQ - 1), (2, SEQ), "whole number of exits"),
    ((2, 12, DIM), (2, 12), (2, 12), "divisible by num_chunks"),
])
def test_shapes_that_are_no_exits_of_the_ids_raise(hidden, weights, ids,
                                                   why):
    with pytest.raises(ValueError, match=why):
        weighted_chunked_causal_lm_loss(
            jnp.zeros(hidden), jnp.zeros((DIM, VOCAB)),
            jnp.zeros(ids, jnp.int32), jnp.zeros(weights), num_chunks=8)


# The first 16 hexadecimal digits of the SHA-256 of what the unweighted
# sweep's gradient lowers to (no debug information) at a decoder cell's
# kind of shapes, tiny, recorded on the parent commit 8a747de by
# ``_unweighted_text``: the weighted sweep went in beside it and changed
# nothing of it.
PARENT = {
    (jnp.bfloat16, 1): "01f410622e393f6f",
    (jnp.bfloat16, 2): "bfbf53957e632cc6",
    (jnp.float32, 1): "7bf0daba1cb6dc06",
}


def _unweighted_text(dtype, ahead):
    hidden = jax.ShapeDtypeStruct((2, 64, DIM), dtype)
    kernel = jax.ShapeDtypeStruct((DIM, VOCAB), jnp.float32)
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return jax.jit(jax.value_and_grad(
        lambda h, w, i: chunked_causal_lm_loss(h, w, i, num_chunks=8,
                                               ahead=ahead),
        argnums=(0, 1))).lower(hidden, kernel, ids).as_text()


@pytest.mark.parametrize("dtype,ahead", sorted(PARENT, key=str))
def test_the_unweighted_sweep_lowers_to_the_parents_text(dtype, ahead):
    text = _unweighted_text(dtype, ahead)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT[(dtype, ahead)]
