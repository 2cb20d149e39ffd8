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

from horovod_tpu.models import LagunaLM, causal_lm_loss
from decoder_helpers import share
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
    params = params if held is None else share(params, held)
    model = LagunaLM(cfg)
    rcfg = _reference_config(cfg)

    logits = model.apply({"params": params}, ids)[0]
    hidden = reference.sequence_hidden(params, ids[0], lambda a: a, rcfg)
    theirs = hidden @ params["lm_head"]["kernel"]
    np.testing.assert_allclose(logits[0], theirs, rtol=0, atol=1e-3 * float(
        jnp.max(jnp.abs(theirs))))

    def loss(p):
        return causal_lm_loss(model.apply({"params": p}, ids)[0], ids)

    ours, grads = jax.jit(jax.value_and_grad(loss))(params)

    def reference_loss(p):
        total = sum(reference.sequence_nll_sum(
            p, row, rnd=lambda a: a, config=rcfg) for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    theirs, reference_grads = jax.jit(
        jax.value_and_grad(reference_loss))(params)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(reference_grads)):
        # float32 through five layers of weights scaled up: the loss
        # agrees to 1e-5, a gradient to a part in a thousand of its leaf.
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 3e-3 * scale, path


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
    ours = reference._attention(lambda a: a, q, k, v, window)
    want = reference_attention(q[None], k[None], v[None], causal=True,
                               window=window)[0]
    np.testing.assert_allclose(ours, want, rtol=0, atol=2e-6)
    grads = jax.grad(lambda *a: jnp.sum(
        reference._attention(lambda x: x, *a, window) ** 2), (0, 1, 2))(
        q, k, v)
    want_grads = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
        q[None], k[None], v[None], causal=True, window=window) ** 2),
        (0, 1, 2))(q, k, v)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-5)
