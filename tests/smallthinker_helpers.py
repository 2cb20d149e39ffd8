"""What the SmallThinker test files share: the tiny configuration, the
benchmark's plain reference loaded by path, and seeded weights at scales
where every path matters."""

import dataclasses

import jax.numpy as jnp
import pytest

from horovod_tpu.models import SMALLTHINKER_TINY, SmallThinkerLM
from decoder_helpers import reference_fixture, seeded_ids_and_params

SEQ = 128       # the tiny window is 48: shorter than the sequence


reference = reference_fixture("smallthinker-21b-a3b")


def _config(held=None, **over):
    over.setdefault("num_layers", 4)        # one period
    return dataclasses.replace(SMALLTHINKER_TINY, dtype=jnp.float32,
                               experts_held=held, **over)


def _reference_config(cfg):
    """The model's sizes under the keys the configuration file has."""
    return {
        "num_layers": cfg.num_layers, "rms_norm_eps": cfg.norm_eps,
        "moe_num_active_primary_experts": cfg.num_selected,
        "sliding_window_size": cfg.sliding_window,
        "sliding_window_layout": list(cfg.window_layout),
        "rope_layout": list(cfg.rope_layout), "rope_theta": cfg.rope_theta,
        "deployment": {"experts_held": list(cfg.held())},
    }


@pytest.fixture(scope="module")
def seeded():
    # Scales at which every path matters: a router that decides, experts
    # and attention of the residual's own size.
    def scaled(path, x):
        return x * (25.0 if "router" in str(path) else 3.0) \
            if x.ndim > 1 else x

    return seeded_ids_and_params(SmallThinkerLM(_config()), SEQ, scaled)
