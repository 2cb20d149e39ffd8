"""LFM2 (``models/lfm2.py``) and what it forced of ``moe_apply_held``
and the causal convolution, at a tiny size on seeded weights: the model
against the benchmark's plain float32 reference through three AdamW steps
(loss, first gradient, the parameters after); the convolution sees no
later token and is three shifted sums; the bias changes the chosen set
and not the weights, takes a zero gradient and is left by the optimizer
as it was; the tied matrix's gradient is the sum of its two paths; the
routed parts of the eight disjoint shares add up to the uncut reference's
layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.models import (Lfm2LM, causal_lm_loss,
                                chunked_causal_lm_loss)
from horovod_tpu.models.lfm2 import (CONV, Lfm2Block, decay_mask,
                                     gated_short_conv)
from horovod_tpu.ops.linear_attention import causal_conv, causal_conv_silu
from horovod_tpu.ops.short_conv import block_rows
from horovod_tpu.parallel.moe import sigmoid_top_k, softmax_top_k
from decoder_helpers import (assert_shares_add_up,
                             assert_three_adamw_steps_match,
                             seeded_ids_and_params, share)
from lfm2_helpers import (SEQ, _config, _reference_config,  # noqa: F401
                          reference, seeded)

OPTIMIZER = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.1)


def test_three_adamw_steps_match_the_plain_reference(seeded, reference):
    """Each step's loss, the first gradient and the parameters after
    three steps, with a share of the experts held (the layer that holds
    all eight is the last test's), both mixers, the dense layer, the QK
    norms, the bias and the tied head on; the bias comes out bit for bit
    as it went in, on both sides."""
    ids, params = seeded
    held = (0, 5, 7)
    cfg = _config(held)
    assert {"conv", "full_attention"} == set(cfg.layer_types)
    params = share(params, held)
    model = Lfm2LM(cfg)
    assert_three_adamw_steps_match(
        lambda p: causal_lm_loss(model.apply({"params": p}, ids)[0], ids),
        params, ids, reference, _reference_config(cfg, **OPTIMIZER),
        optax.adamw(mask=decay_mask, **OPTIMIZER),
        size=lambda x: np.max(np.abs(x)))


def test_a_step_through_the_fused_mixers_matches_the_plain_reference(
        reference):
    """At a width and a sequence that take the kernels (128 lanes, two
    blocks of 512 rows), each block recomputed as the benchmark's step
    recomputes it: a dense and a sparse convolution layer against the
    reference, loss, first gradient and the parameters after a step."""
    seq = 1024
    cfg = dataclasses.replace(_config((0, 5, 7)), dim=128, num_layers=2,
                              layer_types=(CONV, CONV), remat=True)
    assert block_rows(seq, cfg.dim, cfg.conv_taps) == seq // 2
    model = Lfm2LM(cfg)
    ids, params = seeded_ids_and_params(
        model, seq, lambda path, x: x * 25.0 if "router" in {
            str(getattr(k, "key", k)) for k in path} else x)
    assert_three_adamw_steps_match(
        lambda p: causal_lm_loss(model.apply({"params": p}, ids)[0], ids),
        params, ids, reference, _reference_config(cfg, **OPTIMIZER),
        optax.adamw(mask=decay_mask, **OPTIMIZER),
        size=lambda x: np.max(np.abs(x)), steps=1)


def test_the_convolution_sees_no_later_token_and_is_three_shifted_sums(
        reference):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (3, 6))
    got = causal_conv(x, taps)
    for row, want in zip(got, x):
        np.testing.assert_allclose(
            row, reference._short_conv(lambda a: a, want, taps), atol=1e-6)
    # Token 17 moves tokens 17, 18, 19 and nothing before or after them.
    moved = np.any(np.asarray(
        causal_conv(x.at[:, 17].add(1.0), taps) != got), axis=(0, 2))
    assert np.flatnonzero(moved).tolist() == [17, 18, 19]
    # The activation is the caller's: Olmo-Hybrid's is the SiLU of these
    # sums, the mixer's none, with a gate on either side.
    np.testing.assert_allclose(causal_conv_silu(x, taps),
                               jax.nn.silu(got), atol=1e-6)
    b_gate, c_gate = x[:, ::-1], x * 0.5
    np.testing.assert_allclose(gated_short_conv(b_gate, c_gate, x, taps),
                               c_gate * causal_conv(b_gate * x, taps))
    grads = jax.jit(jax.grad(lambda x, w: jnp.sum(
        causal_conv(x, w) ** 2), (0, 1)))(x, taps)
    want = jax.jit(jax.grad(lambda x, w: sum(jnp.sum(reference._short_conv(
        lambda a: a, row, w) ** 2) for row in x), (0, 1)))(x, taps)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_the_bias_enters_the_choice_and_not_the_weights():
    logits = 1.5 * jax.random.normal(jax.random.PRNGKey(2), (512, 16))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    scores = jax.nn.sigmoid(logits)
    ids, weights = sigmoid_top_k(bias)(logits, 4)
    plain_ids, plain_weights = sigmoid_top_k(0.0 * bias)(logits, 4)
    # The chosen set is the 4 largest of s + b ...
    np.testing.assert_array_equal(
        np.sort(ids, -1), np.sort(jax.lax.top_k(scores + bias, 4)[1], -1))
    changed = np.any(np.sort(ids, -1) != np.sort(plain_ids, -1), axis=-1)
    assert 0.2 < changed.mean() < 0.95
    # ... and the weights the chosen experts' own scores over their sum
    # plus 1e-6: they add up to 1 / (1 + 1e-6 / sum).
    own = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
    total = own.sum(-1, keepdims=True)
    np.testing.assert_allclose(weights, own / (total + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1),
                               1.0 / (1.0 + 1e-6 / total[:, 0]), rtol=1e-6)
    # Where the bias changed nothing in the choice, it changed nothing
    # (expert by expert: the order inside a set is the choice's).
    def by_expert(ids, weights):
        dense = np.zeros(logits.shape, np.float32)
        np.put_along_axis(dense, np.asarray(ids), np.asarray(weights), -1)
        return dense

    np.testing.assert_allclose(
        by_expert(ids, weights)[~changed],
        by_expert(plain_ids, plain_weights)[~changed], rtol=1e-6)

    def through(logits, bias):
        return jnp.sum(sigmoid_top_k(bias)(logits, 4)[1] ** 2)

    d_logits, d_bias = jax.grad(through, (0, 1))(logits, bias)
    assert not np.any(np.asarray(d_bias)) and np.any(np.asarray(d_logits))
    # The softmax rule is the one the held layer had inside it.
    top_logits, top_ids = jax.lax.top_k(logits, 4)
    old_ids, old_weights = softmax_top_k(logits, 4)
    np.testing.assert_array_equal(old_ids, top_ids)
    np.testing.assert_array_equal(old_weights,
                                  jax.nn.softmax(top_logits, axis=-1))


def test_the_tied_matrix_gradient_is_the_sum_of_its_two_paths(seeded):
    """The step the benchmark runs (each block recomputed, the loss in
    chunks with the embedding transposed as the head) against the plain
    model: one function; and the embedding's gradient is the lookup's
    plus the head's."""
    ids, params = seeded
    model = Lfm2LM(_config(remat=True))

    def chunked(p, head):
        hidden, _ = model.apply({"params": p}, ids, return_hidden=True)
        return chunked_causal_lm_loss(hidden, head.T, ids, num_chunks=4)

    def plain(p):
        return causal_lm_loss(
            Lfm2LM(_config()).apply({"params": p}, ids)[0], ids)

    table = params["tok_embeddings"]["embedding"]
    value, (by_lookup, by_head) = jax.jit(jax.value_and_grad(
        chunked, (0, 1)))(params, table)
    want_value, want = jax.jit(jax.value_and_grad(plain))(params)
    np.testing.assert_allclose(value, want_value, rtol=1e-5)
    lookup = by_lookup["tok_embeddings"]["embedding"]
    tied = want["tok_embeddings"]["embedding"]
    scale = float(np.max(np.abs(tied)))
    for part in (lookup, by_head):
        assert float(np.max(np.abs(part))) > 0.01 * scale
    np.testing.assert_allclose(lookup + by_head, tied, rtol=0,
                               atol=2e-5 * scale)
    assert "lm_head" not in params


def test_routed_parts_of_the_eight_shares_add_up_to_the_whole_layer(
        seeded, reference):
    """A sparse layer: a share's output is ``h + (its experts' part)``,
    so the routed parts of the eight disjoint shares (one expert each),
    with the mixer and the residual counted once, are the uncut
    reference's layer."""
    cfg = _config()
    # Alike on every chip: the mixer and the residual.
    assert_shares_add_up(
        lambda held: Lfm2Block(_config(held), kind=CONV, sparse=True),
        seeded[1]["layer_2"], lambda p, rows, rcfg: reference._layer(
            lambda a: a, p, rows, rcfg, CONV, True), _reference_config(cfg),
        [(expert,) for expert in range(cfg.num_experts)], cfg, SEQ)
