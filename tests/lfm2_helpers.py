"""What the LFM2 test files share: the tiny configuration, the
benchmark's plain reference loaded by path, and seeded weights at scales
where every path matters."""

import dataclasses

import jax.numpy as jnp
import pytest

from horovod_tpu.models import LFM2_TINY, Lfm2LM
from horovod_tpu.models.lfm2 import CONV, FULL
from decoder_helpers import reference_fixture, seeded_ids_and_params

SEQ = 96


reference = reference_fixture("lfm2-24b-a2b")


def _config(held=None, **over):
    """``LFM2_TINY`` as deep as a test needs: the dense convolution
    layer, a sparse attention layer and a sparse convolution one."""
    return dataclasses.replace(
        LFM2_TINY, dtype=jnp.float32, experts_held=held, num_layers=3,
        layer_types=(CONV, FULL, CONV), **over)


def _reference_config(cfg, **optimizer):
    """The model's sizes under the keys the configuration file has; the
    layers run are the first ``num_layers`` of ``layer_types``."""
    return {
        "num_layers": cfg.num_layers, "norm_eps": cfg.norm_eps,
        "num_dense_layers": cfg.num_dense_layers,
        "layer_types": list(cfg.layer_types),
        "num_experts_per_tok": cfg.num_selected,
        "routed_scaling_factor": 1,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "deployment": {"experts_held": list(cfg.held()),
                       "layers_run": list(range(cfg.num_layers))},
        "optimizer": optimizer,
    }


@pytest.fixture(scope="module")
def seeded():
    # Scales at which every path matters: a router that decides, a bias
    # that moves the choice for some tokens and not for all, mixers and
    # experts of the residual's own size (a convolution mixer is cubic in
    # its in-projection, which flax draws at 1/sqrt(width): left as
    # drawn).
    def scaled(path, x):
        names = {str(getattr(k, "key", k)) for k in path}
        if "router" in names:
            return x * 25.0
        if "expert_bias" in names:
            return x * 10.0
        if names & {"in_proj", "out_proj"}:
            return x
        return x * 3.0 if x.ndim > 1 and "taps" not in names else x

    return seeded_ids_and_params(Lfm2LM(_config()), SEQ, scaled)
