"""What the JoyAI test files share: the tiny configuration, the
benchmark's plain reference loaded by path, and seeded weights at scales
where every path matters."""

import dataclasses

import jax.numpy as jnp
import pytest

from horovod_tpu.models import JOYAI_TINY, JoyAILM
from decoder_helpers import reference_fixture, seeded_ids_and_params

SEQ = 96


reference = reference_fixture("joyai-llm-flash")


def _config(held=None, **over):
    return dataclasses.replace(JOYAI_TINY, dtype=jnp.float32,
                               experts_held=held, **over)


def _reference_config(cfg, mtp_weight=0.0, **optimizer):
    """The model's sizes under the keys the configuration file has."""
    return {
        "num_layers": cfg.num_layers, "rms_norm_eps": cfg.norm_eps,
        "first_k_dense_replace": cfg.num_dense_layers,
        "num_nextn_predict_layers": cfg.mtp_layers,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.num_selected,
        "routed_scaling_factor": cfg.routed_scale,
        "mtp_loss_weight": mtp_weight,
        "deployment": {"experts_held": list(cfg.held())},
        "optimizer": optimizer,
    }


@pytest.fixture(scope="module")
def seeded():
    # Scales at which every path matters: a router that decides, a bias
    # that moves the choice for some tokens and not for all, mixers and
    # experts of the residual's own size.
    def scaled(path, x):
        names = {str(getattr(k, "key", k)) for k in path}
        if "router" in names:
            return x * 25.0
        if "expert_bias" in names:
            return x * 10.0
        return x * 3.0 if x.ndim > 1 else x

    return seeded_ids_and_params(JoyAILM(_config()), SEQ, scaled)
