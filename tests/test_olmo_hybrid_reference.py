"""Olmo-Hybrid (``models/olmo_hybrid.py``) against the benchmark's plain
float32 reference (``benchmarks/reference/olmo-hybrid-7b.py``: no flax, no
kernel, no chunk algebra, the recurrence token by token), whole and with a
share of the heads: loss and every gradient."""

import jax
import pytest

from horovod_tpu.models import OlmoHybridLM
from decoder_helpers import assert_matches_the_plain_reference
from olmo_hybrid_helpers import (_config, _reference_config, _share,  # noqa: F401
                                 reference, seeded)


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
    params = params if held is None else jax.jit(
        lambda p: _share(p, held, cfg))(params)
    model = OlmoHybridLM(cfg)

    # float32 through four layers, the chunked form against the
    # token-by-token one: a part in a thousand of the leaf's largest.
    assert_matches_the_plain_reference(model, params, ids, reference,
                                       _reference_config(cfg), 1e-3)
