"""SmallThinker (``models/smallthinker.py``) against the benchmark's plain
float32 reference (``benchmarks/reference/smallthinker-21b-a3b.py``: no
flax, no kernel, no grouped product, every held expert applied densely),
whole and with a share of the experts: loss and every gradient."""

import pytest

from horovod_tpu.models import SmallThinkerLM
from decoder_helpers import assert_matches_the_plain_reference, share
from smallthinker_helpers import (_config, _reference_config,  # noqa: F401
                                  reference, seeded)


@pytest.mark.parametrize("held", [None, (2, 3), (0, 5, 7)],
                         ids=["all", "share-2-3", "share-0-5-7"])
def test_loss_and_gradients_match_the_plain_reference(held, seeded,
                                                      reference):
    ids, params = seeded
    cfg = _config(held)
    params = share(params, held)
    model = SmallThinkerLM(cfg)

    # float32 through four layers of weights scaled up: the loss agrees
    # to 1e-5, a gradient to a part in a thousand of its leaf.
    assert_matches_the_plain_reference(model, params, ids, reference,
                                       _reference_config(cfg), 3e-3)
