"""A decoder block's checkpoint (PR 49: it keeps the flash kernel's output
and row statistics, ``test_decoder_checkpoint.py``) changes no gradient:
every decoder family with ``remat`` against the same model without, in
float32 through the interpreted streamed kernels, one jitted program a
side."""

import jax
import numpy as np
import pytest

from decoder_checkpoint_helpers import (FAMILIES, STREAMED, ids_of, loss_of,
                                        model_of)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_checkpoint_changes_no_gradient(family):
    plain = model_of(family, False, **STREAMED)
    kept = model_of(family, True, **STREAMED)
    ids = ids_of(plain)
    params = jax.jit(plain.init)(jax.random.PRNGKey(3), ids)["params"]
    want_value, want = jax.jit(jax.value_and_grad(loss_of(plain, ids)))(params)
    value, got = jax.jit(jax.value_and_grad(loss_of(kept, ids)))(params)
    np.testing.assert_allclose(value, want_value, rtol=1e-5, atol=1e-7)
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(got))
    for (path, g), w in zip(flat, jax.tree.leaves(jax.device_get(want))):
        name = jax.tree_util.keystr(path)
        scale = float(np.max(np.abs(w)))
        # The loss reads no logits, and a routing bias takes no gradient.
        assert scale > 0 or "lm_head" in name or "expert_bias" in name, name
        assert float(np.max(np.abs(g - w))) <= 1e-4 * scale, name
