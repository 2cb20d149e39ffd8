"""What the Olmo-Hybrid test files share: the tiny configuration, the
benchmark's plain reference loaded by path, seeded weights, and the cut of
a whole model's weights to a share of its heads."""

import dataclasses

import jax.numpy as jnp
import pytest

from horovod_tpu.models import OLMO_HYBRID_TINY, OlmoHybridLM
from decoder_helpers import reference_fixture, seeded_ids_and_params

SEQ = 160       # three chunks of 64, the last one padded


reference = reference_fixture("olmo-hybrid-7b")


def _config(held=None, **over):
    over.setdefault("num_layers", 4)        # one period
    return dataclasses.replace(OLMO_HYBRID_TINY, dtype=jnp.float32,
                               heads_held=held, **over)


def _reference_config(cfg):
    """The model's sizes under the keys the configuration file has."""
    return {
        "num_layers": cfg.num_layers, "layer_types": list(cfg.layer_types),
        "rms_norm_eps": cfg.norm_eps, "head_dim": cfg.head_dim,
        "linear_key_head_dim": cfg.linear_key_dim,
        "linear_value_head_dim": cfg.linear_value_dim,
    }


def _share_mixer(mixer, held, cfg):
    """One mixer's weights cut to the heads ``held``: the columns of the
    projections in, the convolutions' channels, the per-head vectors, and
    the rows of the projection out."""
    def columns(width):
        return jnp.concatenate([jnp.arange(h * width, (h + 1) * width)
                                for h in held])

    heads = jnp.array(held)
    if "A_log" in mixer:
        d_k, d_v = cfg.linear_key_dim, cfg.linear_value_dim
        widths = (("wq", d_k), ("wk", d_k), ("wv", d_v), ("wg", d_v),
                  ("conv_q", d_k), ("conv_k", d_k), ("conv_v", d_v))
        out = {name: {"kernel": mixer[name]["kernel"][:, columns(width)]}
               for name, width in widths}
        out.update({name: {"kernel": mixer[name]["kernel"][:, heads]}
                    for name in ("wa", "wb")})
        out.update(A_log=mixer["A_log"][heads],
                   dt_bias=mixer["dt_bias"][heads], o_norm=mixer["o_norm"],
                   wo={"kernel": mixer["wo"]["kernel"][columns(d_v)]})
        return out
    cols = columns(cfg.head_dim)
    out = {name: {"kernel": mixer[name]["kernel"][:, cols]}
           for name in ("wq", "wk", "wv")}
    out.update({name: {"scale": mixer[name]["scale"][cols]}
                for name in ("q_norm", "k_norm")})
    out["wo"] = {"kernel": mixer["wo"]["kernel"][cols]}
    return out


def _share(params, held, cfg):
    """``params`` of the model that holds every head, cut to ``held``."""
    out = dict(params)
    for name in (n for n in params if n.startswith("layer_")):
        out[name] = dict(params[name], mixer=_share_mixer(
            params[name]["mixer"], held, cfg))
    return out


@pytest.fixture(scope="module")
def seeded():
    # Scales at which every path matters: norm scales that differ by
    # column (a q/k norm over the wrong columns shows), decays that keep a
    # state alive over chunks (flax's own scale on ``wa`` forgets it within
    # a token by the third layer).
    def leaf(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        if names[-1] == "scale":
            return 1.0 + 0.3 * jnp.sin(jnp.arange(x.shape[0], dtype=x.dtype))
        return x * 0.2 if "wa" in names else x

    return seeded_ids_and_params(OlmoHybridLM(_config()), SEQ, leaf)
