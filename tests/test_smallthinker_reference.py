"""SmallThinker (``models/smallthinker.py``) against the benchmark's plain
float32 reference (``benchmarks/reference/smallthinker-21b-a3b.py``: no
flax, no kernel, no grouped product, every held expert applied densely),
whole and with a share of the experts: loss and every gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import SmallThinkerLM, causal_lm_loss
from decoder_helpers import share
from smallthinker_helpers import (_config, _reference_config,  # noqa: F401
                                  reference, seeded)


@pytest.mark.parametrize("held", [None, (2, 3), (0, 5, 7)],
                         ids=["all", "share-2-3", "share-0-5-7"])
def test_loss_and_gradients_match_the_plain_reference(held, seeded,
                                                      reference):
    ids, params = seeded
    cfg = _config(held)
    params = params if held is None else share(params, held)
    model = SmallThinkerLM(cfg)

    def loss(p):
        return causal_lm_loss(model.apply({"params": p}, ids)[0], ids)

    ours, grads = jax.jit(jax.value_and_grad(loss))(params)

    def reference_loss(p):
        total = sum(reference.sequence_nll_sum(
            p, row, rnd=lambda a: a, config=_reference_config(cfg))
            for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    theirs, reference_grads = jax.jit(
        jax.value_and_grad(reference_loss))(params)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(reference_grads)):
        # float32 through eight layers of weights scaled up: the loss
        # agrees to 1e-5, a gradient to a part in a thousand of its leaf.
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 3e-3 * scale, path
