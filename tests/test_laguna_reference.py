"""Laguna (``models/laguna.py``) against the benchmark's plain float32
reference (``benchmarks/reference/laguna-xs.2.py``: no flax, no kernel, no
grouped product, the shared expert and every held expert applied densely,
YaRN's frequencies written out), whole and with a share of the routed
experts: logits, loss and every gradient, with both layer types, the
dense layer, the gate and YaRN past its original context on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LagunaLM
from decoder_helpers import assert_matches_the_plain_reference, share
from laguna_helpers import (SEQ, _config, _reference_config,  # noqa: F401
                            reference, seeded)


@pytest.mark.parametrize("held", [None, (0, 5, 7)],
                         ids=["all", "share-0-5-7"])
def test_logits_loss_and_gradients_match_the_plain_reference(held, seeded,
                                                             reference):
    ids, params = seeded
    cfg = _config(held)
    assert SEQ > cfg.sliding_window and \
        SEQ > cfg.full_rotary.original_positions
    assert {"full_attention", "sliding_attention"} == set(cfg.layer_types)
    params = share(params, held)
    model = LagunaLM(cfg)
    rcfg = _reference_config(cfg)

    logits, theirs = jax.jit(lambda p: (
        model.apply({"params": p}, ids)[0][0],
        reference.sequence_hidden(p, ids[0], lambda a: a, rcfg)
        @ p["lm_head"]["kernel"]))(params)
    np.testing.assert_allclose(logits, theirs, rtol=0, atol=1e-3 * float(
        np.max(np.abs(theirs))))
    # float32 through three layers of weights scaled up: the loss agrees
    # to 1e-5, a gradient to a part in a thousand of its leaf.
    assert_matches_the_plain_reference(model, params, ids, reference,
                                       rcfg, 3e-3)


@pytest.mark.parametrize("window", [None, 48])
def test_reference_attention_in_blocks_is_the_masked_softmax(
        window, reference, monkeypatch):
    """The reference's attention, a block of queries at a time and on a
    sliding layer against a slice of the keys (here blocks of 64 against
    112 of 256 keys), is the masked softmax over all keys."""
    from horovod_tpu.ops.attention import reference_attention

    monkeypatch.setattr(reference, "QUERY_BLOCK", 64)
    q = jax.random.normal(jax.random.PRNGKey(0), (256, 8, 16))
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (256, 2, 16))
            for i in (1, 2))
    def in_blocks(q, k, v):
        out = reference._attention(lambda a: a, q, k, v, window)
        return jnp.sum(out ** 2), out

    def masked(q, k, v):
        out = reference_attention(q[None], k[None], v[None], causal=True,
                                  window=window)[0]
        return jnp.sum(out ** 2), out

    (_, ours), grads = jax.jit(jax.value_and_grad(
        in_blocks, (0, 1, 2), has_aux=True))(q, k, v)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        masked, (0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(ours, want, rtol=0, atol=2e-6)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-5)
