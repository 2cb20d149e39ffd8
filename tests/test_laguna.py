"""Laguna (``models/laguna.py``) and what it forced of
``rotary_embedding``, at a tiny size on seeded weights (the model against
the benchmark's plain float32 reference is ``test_laguna_reference.py``):
the routed parts of all the shares add up to the whole layer with
attention, residual and shared expert counted once; the step the
benchmark runs is the plain model's function; the rotary embedding's
defaults are the old program and its new arguments the reference's
written-out YaRN."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import LAGUNA_TINY, LAGUNA_XS2, LagunaLM
from horovod_tpu.models.decoder import rotary_embedding
from horovod_tpu.models.laguna import (FULL, SLIDING, SPARSE, LagunaBlock,
                                       rotary_arguments)
from horovod_tpu.ops.attention import make_attention_fn
from decoder_helpers import (assert_shares_add_up,
                             assert_the_benchmarks_step_is_the_plain_model)
from model_helpers import jit_apply
from laguna_helpers import (SEQ, _config, _reference_config,  # noqa: F401
                            reference, seeded)


def test_flash_kernels_remat_and_chunked_loss_change_nothing(seeded):
    """The step the benchmark runs (flash attention by the program's own
    rule, each block recomputed, the loss in chunks) against the plain
    model: one function."""
    ids, params = seeded
    ids = jnp.concatenate([ids] * 4, axis=1)      # 512: four blocks a side
    plain = LagunaLM(_config())
    fast = LagunaLM(
        _config(remat=True),
        attention_fn=make_attention_fn(causal=True, use_flash=True,
                                       block_q=128, block_k=128),
        window_attention_fn=make_attention_fn(
            causal=True, use_flash=True, block_q=128, block_k=128,
            window=LAGUNA_TINY.sliding_window))
    assert_the_benchmarks_step_is_the_plain_model(plain, fast, params, ids)


@pytest.mark.parametrize("layer,kind,heads", [
    ("layer_1", SLIDING, 8), ("layer_2", FULL, 6)])
def test_routed_parts_of_all_the_shares_add_up_to_the_whole_layer(
        layer, kind, heads, seeded, reference):
    """One sparse layer of each attention type: a share's output is ``a +
    shared expert + 2.5 x (its experts' part)``, so the routed parts of
    the four disjoint shares, with attention, residual and shared expert
    counted once, are the uncut reference's layer."""
    cfg = _config()
    attention_fn = make_attention_fn(
        causal=True, use_flash=False,
        window=cfg.sliding_window if kind == SLIDING else None)
    # Alike on every chip: attention, the residual and the shared expert.
    assert_shares_add_up(
        lambda held: LagunaBlock(_config(held), kind=kind, heads=heads,
                                 mlp_kind=SPARSE, attention_fn=attention_fn),
        seeded[1][layer], lambda p, rows, rcfg: reference._layer(
            lambda a: a, p, rows, rcfg, kind, True), _reference_config(cfg),
        [(0, 1), (2, 3), (4, 5), (6, 7)], cfg, SEQ)


def test_the_dense_layer_has_no_router_and_no_load(seeded):
    ids, params = seeded
    assert "router" not in params["layer_0"] and "mlp" in params["layer_0"]
    assert all("router" in params[f"layer_{i}"] and "shared" in
               params[f"layer_{i}"] for i in range(1, 3))
    _, load = jit_apply(LagunaLM(_config()))({"params": params}, ids)
    assert load.shape == (2, 8)         # the two sparse layers
    assert load.sum(axis=1).tolist() == [2 * SEQ * 2] * 2


def _old_rotary_embedding(x, theta, positions=None):
    """``rotary_embedding`` as it was before it took a rotary width,
    frequencies and a scale (PR 31's ``models/llama.py``)."""
    b, s, h, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., :, None] * freqs
    if angles.ndim == 2:
        cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
        sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    else:
        cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
        sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@pytest.mark.parametrize("positions", ["none", "row", "batch"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rotary_embedding_defaults_are_the_old_program(positions, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 32),
                          jnp.dtype(dtype))
    at = {"none": None, "row": jnp.arange(24) + 7,
          "batch": jnp.stack([jnp.arange(24), jnp.arange(24) + 40])}[
        positions]
    old = lambda x: _old_rotary_embedding(x, 1.5e6, at)  # noqa: E731
    new = lambda x: rotary_embedding(x, 1.5e6, at)  # noqa: E731
    np.testing.assert_array_equal(np.asarray(old(x), np.float32),
                                  np.asarray(new(x), np.float32))
    # The same program, instruction for instruction.
    assert str(jax.make_jaxpr(old)(x)) == str(jax.make_jaxpr(new)(x))


@pytest.mark.parametrize("config,kind,seq", [
    (LAGUNA_XS2, FULL, 6000), (LAGUNA_XS2, SLIDING, 600),
    (LAGUNA_TINY, FULL, 128), (LAGUNA_TINY, SLIDING, 128)],
    ids=["published-full", "published-sliding", "tiny-full",
         "tiny-sliding"])
def test_rotary_arguments_are_the_references_written_out_yarn(
        config, kind, seq, reference):
    """Partial width, YaRN's blended frequencies and its scale through
    ``rotary_embedding``'s new arguments against the reference's own
    angles, past the original context."""
    spec = config.full_rotary if kind == FULL else config.sliding_rotary
    group = _reference_config(config)["rope_parameters"][kind]
    if spec.yarn_factor is not None:
        assert seq > spec.original_positions
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, seq, 2, config.head_dim))
    ours = rotary_embedding(x, **rotary_arguments(spec, config.head_dim))
    width, cos, sin = reference.rotary_angles(group, config.head_dim, seq)
    assert width == int(config.head_dim * spec.fraction)
    np.testing.assert_allclose(
        ours[0], reference._rotate(x[0], width, cos, sin), rtol=0,
        atol=1e-4)   # float32 angles at position 600: 6e-5 of a radian
    # What passes through is untouched, and what is rotated is not.
    np.testing.assert_array_equal(ours[..., width:], x[..., width:])
    assert not np.allclose(ours[0, 1:, :, :width], x[0, 1:, :, :width])


def test_published_yarn_ramp_by_hand():
    """c(64) = 5.66 and c(1) = 15.80 at rotary width 64, theta 500000,
    original context 4096: frequencies 0-5 extrapolated, 16-31 divided
    by 64, a linear ramp between."""
    from horovod_tpu.models.laguna import yarn_inv_freq

    got = yarn_inv_freq(500000.0, 64, 64.0, 4096, 64.0, 1.0)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], plain[16:] / 64.0, rtol=1e-12)
    np.testing.assert_allclose(
        got[10], plain[10] * ((5 / 11) / 64.0 + 6 / 11), rtol=1e-12)
