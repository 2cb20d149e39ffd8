"""Olmo-Hybrid (``models/olmo_hybrid.py``) against the benchmark's plain
float32 reference (``benchmarks/reference/olmo-hybrid-7b.py``: no flax, no
kernel, no chunk algebra, the recurrence token by token), whole and with a
share of the heads: loss and every gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import OlmoHybridLM, causal_lm_loss
from olmo_hybrid_helpers import (_config, _reference_config, _share,  # noqa: F401
                                 reference, seeded)


def _reference_loss(reference, cfg, ids):
    def loss(p):
        total = sum(reference.sequence_nll_sum(
            p, row, rnd=lambda a: a, config=_reference_config(cfg))
            for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    return loss


@pytest.mark.parametrize("held", [None, (0, 1), (1, 3)],
                         ids=["all", "share-0-1", "share-1-3"])
def test_loss_and_gradients_match_the_plain_reference(held, seeded,
                                                      reference):
    """With ``heads_axis=None`` a share is the reference given that
    share: the partial sum goes into the norm, the q/k norm is over the
    held columns."""
    ids, params = seeded
    ids = ids[:1]
    cfg = _config(held)
    params = params if held is None else _share(params, held, cfg)
    model = OlmoHybridLM(cfg)

    def loss(p):
        return causal_lm_loss(model.apply({"params": p}, ids)[0], ids)

    ours, grads = jax.jit(jax.value_and_grad(loss))(params)
    theirs, reference_grads = jax.jit(jax.value_and_grad(
        _reference_loss(reference, cfg, ids)))(params)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(reference_grads)):
        # float32 through four layers, the chunked form against the
        # token-by-token one: a part in a thousand of the leaf's largest.
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-3 * scale, path
