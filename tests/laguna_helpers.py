"""What the Laguna test files share: the tiny configuration, the
benchmark's plain reference loaded by path, and seeded weights at scales
where every path matters."""

import dataclasses

import jax.numpy as jnp
import pytest

from horovod_tpu.models import LAGUNA_TINY, LagunaLM
from horovod_tpu.models.laguna import FULL, SLIDING
from decoder_helpers import reference_fixture, seeded_ids_and_params

# The tiny window is 48 and YaRN's original context 32: both shorter than
# the sequence.
SEQ = 128


reference = reference_fixture("laguna-xs.2")


def _config(held=None, **over):
    """``LAGUNA_TINY`` as deep as a test needs: the dense full layer, a
    sparse sliding layer and a sparse full one (the MLP kinds are the
    constant's first three)."""
    return dataclasses.replace(
        LAGUNA_TINY, dtype=jnp.float32, experts_held=held, num_layers=3,
        layer_types=(FULL, SLIDING, FULL), heads_per_layer=(6, 8, 6), **over)


def _rope_parameters(spec):
    """A ``RotarySpec`` under the keys the configuration file has."""
    group = {"rope_theta": spec.theta,
             "partial_rotary_factor": spec.fraction,
             "rope_type": "default" if spec.yarn_factor is None else "yarn"}
    if spec.yarn_factor is not None:
        group.update(
            factor=spec.yarn_factor, beta_fast=spec.beta_fast,
            beta_slow=spec.beta_slow,
            original_max_position_embeddings=spec.original_positions,
            attention_factor=spec.attention_factor)
    return group


def _reference_config(cfg):
    """The model's sizes under the keys the configuration file has."""
    return {
        "num_layers": cfg.num_layers, "rms_norm_eps": cfg.norm_eps,
        "head_dim": cfg.head_dim,
        "layer_types": list(cfg.layer_types),
        "mlp_layer_types": list(cfg.mlp_layer_types),
        "sliding_window": cfg.sliding_window,
        "num_experts_per_tok": cfg.num_selected,
        "moe_routed_scaling_factor": cfg.routed_scale,
        "rope_parameters": {
            "full_attention": _rope_parameters(cfg.full_rotary),
            "sliding_attention": _rope_parameters(cfg.sliding_rotary)},
        "deployment": {"experts_held": list(cfg.held())},
    }


@pytest.fixture(scope="module")
def seeded():
    # Scales at which every path matters: a router that decides, a gate
    # that is not one half everywhere, experts and attention of the
    # residual's own size.
    def scaled(path, x):
        return x * (25.0 if "router" in str(path) or "wg" in str(path)
                    else 3.0) if x.ndim > 1 else x

    return seeded_ids_and_params(LagunaLM(_config()), SEQ, scaled)
