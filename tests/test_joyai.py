"""JoyAI-LLM-Flash (``models/joyai.py``) and what it forced of the
chunked loss, at a tiny size on seeded weights: the model against the
benchmark's plain float32 reference through three AdamW steps (loss,
first gradient of every leaf, the parameters after), with the
multi-token-prediction term and without it; rotating halves after the
de-interleaving against rotating the interleaved pairs; the chunked loss
two ahead against the full logits; the step the benchmark runs against
the plain model, the head's gradient from its two passes; the routed
parts of the eight disjoint shares add up to the uncut reference's
layer."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import (JoyAILM, chunked_causal_lm_loss,
                                joyai_lm_loss)
from horovod_tpu.models.decoder import rotary_embedding
from horovod_tpu.models.joyai import JoyAIBlock, deinterleave
from horovod_tpu.models.lfm2 import decay_mask
from horovod_tpu.models.losses import token_nll
from horovod_tpu.ops.attention import make_attention_fn
from decoder_helpers import (assert_shares_add_up,
                             assert_three_adamw_steps_match, share)
from joyai_helpers import (SEQ, _config, _reference_config,  # noqa: F401
                           reference, seeded)

OPTIMIZER = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.1)


def _full_loss(model, p, ids, mtp_weight):
    """``L_main + mtp_weight x L_MTP`` from the full logits."""
    logits, mtp_logits, _ = model.apply({"params": p}, ids)
    return token_nll(logits[:, :-1], ids[:, 1:]).mean() \
        + mtp_weight * token_nll(mtp_logits[:, :-2], ids[:, 2:]).mean()


@pytest.mark.parametrize("mtp_weight", [0.3, 0.0],
                         ids=["with-mtp", "without-mtp"])
def test_three_adamw_steps_match_the_plain_reference(mtp_weight, seeded,
                                                     reference):
    """Each step's loss, the first gradient and the parameters after
    three steps, leaf by leaf, with a share of the experts held, the
    dense layer, the shared expert, the bias and the module on. The
    leaves only the module's loss reaches (its projection, its norms, its
    block) are compared like any other: with the term they move by their
    gradient, without it their gradient is zero on both sides. The bias
    comes out bit for bit as it went in."""
    ids, params = seeded
    held = (0, 5, 7)
    cfg = _config(held)
    params = share(params, held)
    model = JoyAILM(cfg)

    def only_the_modules_loss_reaches(name, r):
        if "'mtp'" in name:
            assert bool(np.any(r)) is bool(mtp_weight), name

    # By norms: AdamW moves an entry whose gradient is all but zero by
    # its sign, which float32 does not settle; a leaf has a few.
    assert_three_adamw_steps_match(
        lambda p: _full_loss(model, p, ids, mtp_weight), params, ids,
        reference, _reference_config(cfg, mtp_weight, **OPTIMIZER),
        optax.adamw(mask=decay_mask, **OPTIMIZER), size=np.linalg.norm,
        check=only_the_modules_loss_reaches)


def test_rotating_halves_after_deinterleaving_gives_the_pairs_scores(
        reference):
    """What the program does to the rotary columns against what the
    published model does: the rotated vectors are each other's
    permutation, so every score ``q . k`` is the same."""
    theta, width = 3.2e7, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 3, width))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 1, width))
    ours = jax.jit(lambda x: rotary_embedding(deinterleave(x), theta))
    pairs = jax.jit(lambda x: reference.rotate_pairs(x, theta))
    ours_q, ours_k = ours(q), ours(k)
    pairs_q, pairs_k = pairs(q[0]), pairs(k[0, :, 0])      # no head axis
    np.testing.assert_allclose(ours_q[0], deinterleave(pairs_q), atol=1e-5)
    np.testing.assert_allclose(ours_k[0, :, 0], deinterleave(pairs_k),
                               atol=1e-5)
    np.testing.assert_allclose(
        jnp.einsum("qhd,kd->hqk", ours_q[0], ours_k[0, :, 0]),
        jnp.einsum("qhd,kd->hqk", pairs_q, pairs_k), atol=1e-4)
    # Rotating halves WITHOUT the de-interleaving is another function.
    halves = jax.jit(lambda x: rotary_embedding(x, theta))
    wrong = jnp.einsum("qhd,kd->hqk", halves(q)[0], halves(k)[0, :, 0])
    assert float(np.max(np.abs(wrong - jnp.einsum(
        "qhd,kd->hqk", pairs_q, pairs_k)))) > 0.1
    # The permutation: evens, then odds.
    np.testing.assert_array_equal(deinterleave(jnp.arange(8)),
                                  [0, 2, 4, 6, 1, 3, 5, 7])


@pytest.mark.parametrize("ahead", [1, 2, 3])
def test_the_chunked_loss_ahead_matches_the_full_logits(ahead):
    """The target ``ahead`` tokens on, the mean over the ``S - ahead``
    positions that have one: value and both gradients."""
    b, s, d, v = 2, 32, 16, 50
    hidden = jax.random.normal(jax.random.PRNGKey(0), (b, s, d))
    head = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (d, v))
    ids = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, v)

    def chunked(h, w):
        return chunked_causal_lm_loss(h, w, ids, num_chunks=4, ahead=ahead)

    def full(h, w):
        return token_nll((h @ w)[:, :-ahead], ids[:, ahead:]).mean()

    got, grads = jax.jit(jax.value_and_grad(chunked, (0, 1)))(hidden, head)
    want, want_grads = jax.jit(jax.value_and_grad(full, (0, 1)))(hidden, head)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(jax.jit(chunked)(hidden, head), want,
                               rtol=1e-6)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-6)
    # The last ``ahead`` positions have no target and take no gradient.
    assert not np.any(np.asarray(grads[0][:, -ahead:]))
    assert np.all(np.any(np.asarray(grads[0][:, :-ahead]), axis=-1))
    with pytest.raises(ValueError, match="ahead"):
        chunked_causal_lm_loss(hidden, head, ids, num_chunks=4, ahead=0)


def test_the_benchmarks_step_is_the_plain_model_and_the_head_has_two_sources(
        seeded):
    """Each block recomputed, both losses in chunks through the one head
    (``joyai_lm_loss``) against the plain model's full logits: one
    function; the head's gradient is the main pass's plus the module's,
    and the embedding's has the module's lookup in it."""
    ids, params = seeded
    weight = 0.3
    model = JoyAILM(_config(remat=True))

    def chunked(p, main_head, mtp_head):
        hidden, mtp_hidden, _ = model.apply({"params": p}, ids,
                                            return_hidden=True)
        return chunked_causal_lm_loss(hidden, main_head, ids, num_chunks=4) \
            + weight * chunked_causal_lm_loss(mtp_hidden, mtp_head, ids,
                                              num_chunks=4, ahead=2)

    def one_head(p):
        hidden, mtp_hidden, _ = model.apply({"params": p}, ids,
                                            return_hidden=True)
        return joyai_lm_loss(hidden, mtp_hidden, p["lm_head"]["kernel"],
                             ids, num_chunks=4, mtp_weight=weight)

    head = params["lm_head"]["kernel"]
    value, (_, by_main, by_mtp) = jax.jit(jax.value_and_grad(
        chunked, (0, 1, 2)))(params, head, head)
    got_value, got = jax.jit(jax.value_and_grad(one_head))(params)
    want_value, want = jax.jit(jax.value_and_grad(
        lambda p: _full_loss(JoyAILM(_config()), p, ids, weight)))(params)
    np.testing.assert_allclose(value, want_value, rtol=1e-5)
    np.testing.assert_allclose(got_value, want_value, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        scale = float(np.max(np.abs(b))) + 1e-12
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    both = want["lm_head"]["kernel"]
    scale = float(np.max(np.abs(both)))
    for part in (by_main, by_mtp):
        assert float(np.max(np.abs(part))) > 0.01 * scale
    np.testing.assert_allclose(by_main + by_mtp, both, rtol=0,
                               atol=2e-5 * scale)
    # Without the module the loss is the main one, and the module's
    # leaves are not asked for.
    hidden, _, _ = jax.jit(lambda p: model.apply(
        {"params": p}, ids, return_hidden=True))(params)
    np.testing.assert_allclose(
        joyai_lm_loss(hidden, None, head, ids, num_chunks=4),
        chunked_causal_lm_loss(hidden, head, ids, num_chunks=4))


def test_routed_parts_of_the_eight_shares_add_up_to_the_whole_layer(
        seeded, reference):
    """A sparse layer: a share's output is ``h + shared + 2.5 x (its
    experts' part)``, so the routed parts of the eight disjoint shares
    (one expert each; thirty-two shares of eight at the published sizes),
    with attention, the shared expert and the residual counted once, are
    the uncut reference's layer."""
    cfg = _config()
    attention_fn = make_attention_fn(causal=True, use_flash=False)
    # Alike on every chip: attention, the shared expert and the residual.
    assert_shares_add_up(
        lambda held: JoyAIBlock(_config(held), sparse=True,
                                attention_fn=attention_fn),
        seeded[1]["layer_1"], lambda p, rows, rcfg: reference._layer(
            lambda a: a, p, rows, rcfg, True), _reference_config(cfg),
        [(expert,) for expert in range(cfg.num_experts)], cfg, SEQ)
