"""The streamed flash kernels at the head layouts of the benchmark's
cells (``test_flash_layouts.py`` has every layout on both paths): at a
tiny sequence against the XLA reference under a causal band, a window, a
key mask and with ``sq != sk``, forward and all three gradients; and
compiled by Mosaic for a described v5e under a key mask."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_helpers import (CELLS, KERNELS, PATHS, _rand,
                               assert_matches_reference)
from horovod_tpu.ops.attention import (_one_tile_path, flash_attention,
                                       reference_attention)

S = 32

# The head layouts of the cells' streamed calls at a tiny sequence: name:
# (query heads, K/V heads, q/k width, v width).
STREAMED_HEADS = {
    "joyai_h32_192_over_128": (32, 32, 192, 128),
    "lfm2_gqa32_8_d64": (32, 8, 64, 64),
    "smallthinker_gqa28_4_d128": (28, 4, 128, 128),
}


# One row of keys, the first never masked.
KEY_MASK = jnp.asarray((np.random.RandomState(5).rand(2, S) > 0.3)[:1]
                       ).at[:, 0].set(True)


@pytest.mark.parametrize("how", ["causal", "window", "key_mask",
                                 "causal_sq_ne_sk"])
@pytest.mark.parametrize("heads", sorted(STREAMED_HEADS))
def test_streamed_cell_head_layouts_forward_and_grad(heads, how):
    h, hkv, d, dv = STREAMED_HEADS[heads]
    sq, sk, kw = {
        "causal": (S, S, dict(causal=True)),
        "window": (S, S, dict(causal=True, window=12)),
        "key_mask": (S, S, dict(causal=True, key_mask=KEY_MASK)),
        "causal_sq_ne_sk": (16, S, dict(causal=True)),       # decode rows
    }[how]
    q, k, v = (_rand((1, sq, h, d), 20), _rand((1, sk, hkv, d), 21),
               _rand((1, sk, hkv, dv), 22))
    assert_matches_reference(
        functools.partial(flash_attention, **kw, **PATHS["streamed"]),
        functools.partial(reference_attention, **kw), q, k, v,
        cot=_rand((1, sq, h, dv), 23))


@pytest.fixture(scope="module")
def one_chip():
    """A described TPU v5e's first device, to compile for and not to run
    on; described inside the fixture, never at import (on-chip-measurement
    guide, section 2)."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # What is compiled for a described chip cannot be read back without
    # one: the persistent cache stays off around these compiles.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case,sq,window", [
    ("joyai-192-over-128", 8192, None),     # a padded batch
    ("lfm2-head-64", 8192, None),
    ("smallthinker-window-4096", 2048, 4096),   # a ring shard: sq != sk
])
def test_streamed_gradient_compiles_for_v5e_under_a_key_mask(
        case, sq, window, one_chip, monkeypatch):
    """Mosaic, not the interpreter: the transposed tile takes the key
    mask's lane row as a column (``_allowed_mask``), which PR 29 met as a
    limit of Mosaic's on booleans. Nothing runs."""
    import horovod_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)
    b, sk, h, hkv, d, dv, _ = CELLS[case]
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = shape((b, sq, h, d), jnp.bfloat16)
    k = shape((b, sk, hkv, d), jnp.bfloat16)
    v = shape((b, sk, hkv, dv), jnp.bfloat16)
    assert _one_tile_path(q, k, 512, 1024, v) == 0
    grad = jax.grad(lambda q, k, v, m: flash_attention(
        q, k, v, key_mask=m, causal=True, window=window).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(
        q, k, v, shape((b, sk), jnp.bool_)).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3
    assert all(any(name in line for line in calls) for name in KERNELS)
