"""The seam of ``horovod_tpu/models/`` (PR 43), held by code: no model
file imports another (``decoder`` and ``losses`` are what they share),
each decoder LM's parameter tree is the one written out here (a
checkpoint's contract, and what the benchmark's references and builders
read by name), and the held sparse layer's two functions, called from a
module of two lines, are the layer a block has. Nothing here compiles a
model: ``jax.eval_shape`` traces, and the one jitted program is a single
tiny block."""

import ast
import dataclasses
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import models
from horovod_tpu.models.decoder import (RMSNorm, held_experts,
                                        router_logits)
from horovod_tpu.models.smallthinker import (SmallThinkerAttention,
                                             SmallThinkerBlock)
from horovod_tpu.ops.attention import make_attention_fn
from horovod_tpu.parallel.moe import grouped_gated_mlp, softmax_top_k

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "horovod_tpu", "models")
# What a model file may import from its own package: the shared parts,
# and the file it extends by design: ``moe_lm`` is ``llama`` with a routed
# FFN (ROADMAP R1), ``vit`` takes the encoder's ``SelfAttention`` from
# ``bert``.
SHARED = {"decoder", "losses"}
EXTENDS = {"moe_lm": {"llama"}, "vit": {"bert"}}


def test_no_model_file_imports_another():
    files = sorted(f[:-3] for f in os.listdir(MODELS) if f.endswith(".py"))
    assert {"decoder", "losses", "llama", "joyai", "ouro"} <= set(files)
    found = {}
    for name in files:
        if name == "__init__":
            continue
        tree = ast.parse(open(os.path.join(MODELS, name + ".py")).read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.ImportFrom) or node.level != 1:
                continue
            # ``from .x import y`` names x; ``from . import x, y`` both.
            siblings = {node.module.split(".")[0]} if node.module \
                else {alias.name for alias in node.names}
            siblings -= SHARED | EXTENDS.get(name, set())
            if siblings:
                found[name] = sorted(siblings | set(found.get(name, ())))
    assert not found, f"model files that import a sibling model: {found}"


# ---- the parameter trees, at the *_TINY presets (hidden width 64) -------
# Every leaf is float32. A layer's leaves are written once for the layers
# that share them.

TIED = {"tok_embeddings/embedding": (512, 64), "final_norm/scale": (64,)}
TOP = {**TIED, "lm_head/kernel": (64, 512)}


def scales(*names, width=64):
    return {f"{name}/scale": (width,) for name in names}


def attention(heads, kv_heads, head_dim):
    return {"attention/wq/kernel": (64, heads, head_dim),
            "attention/wk/kernel": (64, kv_heads, head_dim),
            "attention/wv/kernel": (64, kv_heads, head_dim),
            "attention/wo/kernel": (heads, head_dim, 64)}


def gated(hidden, under=""):
    return {f"{under}w_gate/kernel": (64, hidden),
            f"{under}w_up/kernel": (64, hidden),
            f"{under}w_down/kernel": (hidden, 64)}


def under(prefix, leaves):
    return {f"{prefix}/{name}": leaves[name] for name in sorted(leaves)}


# The held sparse layer: 8 experts 48 wide, all of them held.
HELD = {"router/kernel": (64, 8), "w_gate/kernel": (8, 64, 48),
        "w_up/kernel": (8, 64, 48), "w_down/kernel": (8, 48, 64)}
BIAS = {"expert_bias/kernel": (8,)}

LLAMA_LAYER = {**attention(4, 2, 16), **gated(128),
               **scales("attention_norm", "ffn_norm")}
LAGUNA_SPARSE = {**HELD, **gated(48, "shared/"),
                 **scales("attention_norm", "ffn_norm")}
LFM2_CONV = {"conv/in_proj/kernel": (64, 192), "conv/taps/kernel": (3, 64),
             "conv/out_proj/kernel": (64, 64),
             **scales("operator_norm", "ffn_norm")}
LATENT = {"attention/wq_a/kernel": (64, 48),
          "attention/q_a_norm/scale": (48,),
          "attention/wq_b/kernel": (48, 2, 48),
          "attention/wkv_a/kernel": (64, 40),
          "attention/kv_a_norm/scale": (24,),
          "attention/wkv_b/kernel": (24, 2, 64),
          "attention/wo/kernel": (2, 32, 64),
          **scales("attention_norm", "ffn_norm")}
JOYAI_SPARSE = {**LATENT, **HELD, **BIAS, **gated(48, "shared/")}
OLMO_MLP = {**gated(96), **scales("mixer_norm", "mlp_norm")}
# A norm before and after each sublayer, named as the public code names
# them; the MLP's kernels in the block's own scope.
OURO_LAYER = {**attention(2, 2, 32), **gated(160),
              **scales("input_layernorm", "input_layernorm_2",
                       "post_attention_layernorm",
                       "post_attention_layernorm_2")}

TREES = {
    "LlamaLM": (models.LlamaLM, models.LLAMA_TINY, TOP,
                {(0, 1): LLAMA_LAYER}),
    "MoeLM": (models.MoeLM, models.MOE_TINY, TOP, {
        (0,): LLAMA_LAYER,
        (1,): {**attention(4, 2, 16),
               **scales("attention_norm", "ffn_norm"),
               "moe_ffn/gate": (64, 4), "moe_ffn/wi": (4, 64, 128),
               "moe_ffn/wo": (4, 128, 64)}}),
    "SmallThinkerLM": (
        models.SmallThinkerLM, models.SMALLTHINKER_TINY, TOP,
        {tuple(range(8)): {**attention(4, 2, 32), **HELD,
                           **scales("attention_norm", "ffn_norm")}}),
    "OlmoHybridLM": (models.OlmoHybridLM, models.OLMO_HYBRID_TINY, TOP, {
        (0, 1, 2, 4, 5): {
            **OLMO_MLP, "mixer/wq/kernel": (64, 64),
            "mixer/wk/kernel": (64, 64), "mixer/wv/kernel": (64, 128),
            "mixer/wg/kernel": (64, 128), "mixer/wa/kernel": (64, 4),
            "mixer/wb/kernel": (64, 4), "mixer/conv_q/kernel": (4, 64),
            "mixer/conv_k/kernel": (4, 64), "mixer/conv_v/kernel": (4, 128),
            "mixer/A_log": (4,), "mixer/dt_bias": (4,),
            "mixer/o_norm/scale": (32,), "mixer/wo/kernel": (128, 64)},
        (3,): {**OLMO_MLP, "mixer/wq/kernel": (64, 64),
               "mixer/wk/kernel": (64, 64), "mixer/wv/kernel": (64, 64),
               "mixer/wo/kernel": (64, 64),
               **scales("mixer/q_norm", "mixer/k_norm")}}),
    "LagunaLM": (models.LagunaLM, models.LAGUNA_TINY, TOP, {
        (0,): {**attention(6, 2, 32), "attention/wg/kernel": (64, 6),
               **gated(160, "mlp/"),
               **scales("attention_norm", "ffn_norm")},
        (1, 2, 3): {**attention(8, 2, 32), "attention/wg/kernel": (64, 8),
                    **LAGUNA_SPARSE},
        (4,): {**attention(6, 2, 32), "attention/wg/kernel": (64, 6),
               **LAGUNA_SPARSE}}),
    # The head is the embedding: no ``lm_head``.
    "Lfm2LM": (models.Lfm2LM, models.LFM2_TINY, TIED, {
        (0,): {**LFM2_CONV, **gated(160, "mlp/")},
        (1,): {**attention(2, 1, 32), **HELD, **BIAS,
               **scales("attention/q_norm", "attention/k_norm", width=32),
               **scales("operator_norm", "ffn_norm")},
        (2, 3, 4): {**LFM2_CONV, **HELD, **BIAS}}),
    "JoyAILM": (models.JoyAILM, models.JOYAI_TINY, {
        **TOP, "mtp/eh_proj/kernel": (128, 64),
        **scales("mtp/enorm", "mtp/hnorm", "mtp/norm"),
        **under("mtp/block", JOYAI_SPARSE)}, {
            (0,): {**LATENT, **gated(160, "mlp/")},
            (1,): JOYAI_SPARSE}),
    # Two layers run four times: one ``layer_i`` a layer, not one a pass,
    # and one gate for every pass.
    "OuroLM": (models.OuroLM, models.OURO_TINY, {
        **TOP, "early_exit_gate/kernel": (64, 1),
        "early_exit_gate/bias": (1,)}, {(0, 1): OURO_LAYER}),
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_parameter_tree_is_the_one_written_out(name):
    model, cfg, top, layers = TREES[name]
    expected = dict(top)
    for indices in sorted(layers):
        for i in indices:
            expected.update(under(f"layer_{i}", layers[indices]))
    assert len({i for indices in layers for i in indices}) == cfg.num_layers
    # With remat, as the cells run it: the tree is the same either way.
    lm = model(dataclasses.replace(cfg, remat=True))
    shapes = jax.eval_shape(lambda: lm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    found = {"/".join(k.key for k in path): leaf for path, leaf
             in jax.tree_util.tree_leaves_with_path(shapes)}
    assert {k: tuple(found[k].shape) for k in sorted(found)} == expected
    assert {str(found[k].dtype) for k in sorted(found)} == {"float32"}


# ---- the held sparse layer from a module of two lines ------------------

class TwoLines(nn.Module):
    held: tuple

    @nn.compact
    def __call__(self, routed_on, rows):
        logits = router_logits(routed_on, 8)
        return held_experts(grouped_gated_mlp, rows, logits, self.held, 48,
                            2, route=softmax_top_k)


@pytest.mark.parametrize("held", [None, (1, 4, 6)])
def test_two_lines_are_the_layer_a_block_has(held):
    cfg = dataclasses.replace(models.SMALLTHINKER_TINY, dtype=jnp.float32,
                              experts_held=held)
    attention_fn = make_attention_fn(causal=True, use_flash=False)
    block = SmallThinkerBlock(cfg, rope=True, attention_fn=attention_fn)
    layer = TwoLines(cfg.held())
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64), jnp.float32)

    @jax.jit
    def both(key):
        params = block.init(key, x)["params"]
        # Weights at which the router decides and the experts matter.
        params = jax.tree.map(lambda p: p * 20.0 if p.ndim > 1 else p,
                              params)
        out, load = block.apply({"params": params}, x)
        a = x + SmallThinkerAttention(cfg, True, attention_fn).apply(
            {"params": params["attention"]},
            RMSNorm(cfg.norm_eps, cfg.dtype).apply(
                {"params": params["attention_norm"]}, x))
        h = RMSNorm(cfg.norm_eps, cfg.dtype).apply(
            {"params": params["ffn_norm"]}, a)
        mine = {k: params[k] for k in ("router", "w_gate", "w_up", "w_down")}
        y, my_load = layer.apply({"params": mine}, x.reshape(-1, 64),
                                 h.reshape(-1, 64))
        return out, a, load, a + y.reshape(x.shape), my_load

    out, a, load, mine, my_load = both(jax.random.PRNGKey(2))
    assert float(jnp.abs(out - a).max()) > 0.1      # the routed part
    np.testing.assert_allclose(mine, out, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(my_load, load)
    assert int(load.sum()) > 0 and load.shape == (len(cfg.held()),)
    # A function adds no level: the module's own leaves are the block's.
    names = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), x.reshape(-1, 64), x.reshape(-1, 64)))
    assert {"/".join(k.key for k in path) for path, _
            in jax.tree_util.tree_leaves_with_path(names["params"])} == {
        "router/kernel", "w_gate/kernel", "w_up/kernel", "w_down/kernel"}
