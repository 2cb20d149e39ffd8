"""Olmo-Hybrid (``models/olmo_hybrid.py``) at a tiny size on seeded
weights (the model against the benchmark's plain float32 reference is
``test_olmo_hybrid_reference.py``): ``layer_types`` drives the mixers,
the step the benchmark runs is the plain model, and the two shares of a
layer, joined over ``heads_axis``, are the reference's whole layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import OlmoHybridLM
from horovod_tpu.models.olmo_hybrid import FULL, LINEAR, OlmoHybridBlock
from horovod_tpu.ops.attention import make_attention_fn
from decoder_helpers import assert_the_benchmarks_step_is_the_plain_model
from olmo_hybrid_helpers import (SEQ, _config, _reference_config,  # noqa: F401
                                 _share, reference, seeded)


def test_layer_types_drive_the_mixers(seeded):
    ids, _ = seeded
    cfg = _config(layer_types=(FULL, LINEAR, LINEAR, FULL), num_layers=4)
    params = jax.eval_shape(lambda: OlmoHybridLM(cfg).init(
        jax.random.PRNGKey(0), ids))["params"]
    kinds = ["A_log" in params[f"layer_{i}"]["mixer"] for i in range(4)]
    assert kinds == [False, True, True, False]
    assert "q_norm" in params["layer_0"]["mixer"]
    logits, stats = jax.eval_shape(
        lambda p: OlmoHybridLM(cfg).apply({"params": p}, ids), params)
    assert logits.shape == ids.shape + (cfg.vocab_size,)
    assert stats.shape == (2, 3)            # one row a linear layer
    with pytest.raises(ValueError, match="neither"):
        OlmoHybridLM(_config(layer_types=("sliding",) * 4)).init(
            jax.random.PRNGKey(0), ids)
    with pytest.raises(ValueError, match="an entry for each"):
        OlmoHybridLM(_config(layer_types=(LINEAR,), num_layers=4)).init(
            jax.random.PRNGKey(0), ids)


def test_stats_are_the_log_decays_and_the_largest_state(seeded):
    ids, params = seeded
    _, stats = jax.jit(lambda p: OlmoHybridLM(_config()).apply(
        {"params": p}, ids))(params)
    assert stats.shape == (3, 3)
    lowest, mean, norm = stats.T
    assert bool(jnp.all((lowest < mean) & (mean < 0)))
    assert bool(jnp.all((norm > 0) & jnp.isfinite(norm)))


def test_flash_kernels_remat_and_chunked_loss_change_nothing(seeded):
    """The step the benchmark runs (flash attention on the full layers,
    each block recomputed, the loss in chunks) against the plain model:
    one function."""
    ids, params = seeded
    ids = jnp.concatenate([ids, ids[:, :48]], axis=1)    # 128: two blocks
    plain = OlmoHybridLM(_config())
    fast = OlmoHybridLM(_config(remat=True),
                        attention_fn=make_attention_fn(
                            causal=True, use_flash=True, block_q=64,
                            block_k=64))
    assert_the_benchmarks_step_is_the_plain_model(plain, fast, params, ids)


@pytest.mark.parametrize("layer,kind", [("layer_1", LINEAR),
                                        ("layer_3", FULL)])
def test_the_two_shares_joined_are_the_whole_layer(layer, kind, seeded,
                                                   reference):
    """Under ``jax.vmap(..., axis_name="heads")`` over the two shares each
    device's block is the uncut reference's whole layer: the mixers' parts
    meet in the two psums, the MLP, the norms and the residual are
    computed alike and counted once."""
    _, params = seeded
    cfg = _config()
    x = jax.random.normal(jax.random.PRNGKey(11), (1, SEQ, cfg.dim))
    whole = jax.jit(lambda p: reference._layer(
        lambda a: a, p, x[0], _reference_config(cfg), kind))(params[layer])
    shares = [(0, 2), (1, 3)]
    stacked = jax.jit(lambda p: jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *(_share({layer: p}, held, cfg)[layer] for held in shares)))(
        params[layer])
    block = OlmoHybridBlock(_config(shares[0], heads_axis="heads"), kind,
                            make_attention_fn(causal=True, use_flash=False))
    out, _ = jax.jit(jax.vmap(lambda p: block.apply({"params": p}, x),
                              axis_name="heads"))(stacked)
    for device in range(2):
        np.testing.assert_allclose(
            out[device, 0], whole, rtol=0,
            atol=2e-5 * float(np.max(np.abs(whole))))
    # One share alone, with nothing joined, is another function.
    attention_fn = make_attention_fn(causal=True, use_flash=False)
    alone, _ = jax.jit(lambda p: OlmoHybridBlock(
        _config(shares[0]), kind, attention_fn).apply({"params": p}, x))(
        jax.tree.map(lambda a: a[0], stacked))
    assert float(np.max(np.abs(alone[0] - whole))) > 1e-2
    # The same from the layer that holds all four.
    full, _ = jax.jit(lambda p: OlmoHybridBlock(
        cfg, kind, attention_fn).apply({"params": p}, x))(params[layer])
    np.testing.assert_allclose(full[0], whole, rtol=0,
                               atol=2e-5 * float(np.max(np.abs(whole))))
