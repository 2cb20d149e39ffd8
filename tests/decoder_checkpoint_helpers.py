"""What the two files on a decoder block's checkpoint share
(``test_decoder_checkpoint.py``: what a step lowers to;
``test_decoder_checkpoint_gradients.py``: what it computes): every decoder
family at its ``*_TINY`` widths, cut to a layer of each kind, with the
flash kernels forced, and a loss that reaches every
parameter but the head."""

import dataclasses

import jax
import jax.numpy as jnp

from horovod_tpu import models
from horovod_tpu.ops.attention import make_attention_fn

SEQ = 256
STREAMED = {"block_q": 128, "block_k": 128}    # two blocks a side
# family: (model, its preset cut to a layer of each kind, attention calls
# a step: layers that attend, times the passes, plus JoyAI's MTP block).
FAMILIES = {
    "llama": (models.LlamaLM, models.LLAMA_TINY, 2),
    "smallthinker": (models.SmallThinkerLM, dataclasses.replace(
        models.SMALLTHINKER_TINY, num_layers=2), 2),    # global, windowed
    "olmo_hybrid": (models.OlmoHybridLM, dataclasses.replace(
        models.OLMO_HYBRID_TINY, num_layers=4), 1),     # 3 linear, 1 full
    "laguna": (models.LagunaLM, dataclasses.replace(
        models.LAGUNA_TINY, num_layers=2), 2),          # full, sliding
    "lfm2": (models.Lfm2LM, dataclasses.replace(
        models.LFM2_TINY, num_layers=3), 1),            # conv, full, conv
    "joyai": (models.JoyAILM, models.JOYAI_TINY, 3),
    "ouro": (models.OuroLM, dataclasses.replace(
        models.OURO_TINY, total_ut_steps=2), 4),        # 2 layers x 2
}


def model_of(family, remat, **blocks):
    """The family's model in float32 with the flash kernels forced;
    ``blocks`` are ``make_attention_fn``'s (``block_q``, ``block_k``): at
    ``SEQ`` the defaults are one tile, 128 a side streams."""
    cls, cfg, _ = FAMILIES[family]
    cfg = dataclasses.replace(cfg, remat=remat, dtype=jnp.float32)
    fns = {"attention_fn": make_attention_fn(
        causal=True, use_flash=True, **blocks)}
    if "window_attention_fn" in cls.__dataclass_fields__:
        fns["window_attention_fn"] = make_attention_fn(
            causal=True, use_flash=True, window=cfg.sliding_window, **blocks)
    return cls(cfg, **fns)


def loss_of(model, ids):
    """Everything the model returns with ``return_hidden`` (states, an
    MTP module's, exit gates) against a fixed random direction: every
    parameter but the head is under it, and no norm makes it a
    constant."""
    def loss(p):
        out = model.apply({"params": p}, ids, return_hidden=True)
        leaves = [x.astype(jnp.float32) for x in jax.tree.leaves(out)
                  if jnp.issubdtype(x.dtype, jnp.floating)]
        keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
        return sum(jnp.mean(x * jax.random.normal(key, x.shape))
                   for key, x in zip(keys, leaves))
    return loss


def ids_of(model):
    return jax.random.randint(jax.random.PRNGKey(7), (1, SEQ), 0,
                              model.config.vocab_size)
