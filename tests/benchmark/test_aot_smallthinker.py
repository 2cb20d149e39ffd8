"""What the SmallThinker cell (PR 26) brought to the chip, compiled for a
described TPU v5e at real widths: the streamed flash kernels under a
causal band with and without a window (sequence 8192, 28 query heads over
4, head width 128), where each now holds two bodies, and the expert layer
with a share, whose grouped products XLA expands into Mosaic calls.
Nothing runs; nothing here is a measurement. The fixtures are
``test_aot_v5e.py``'s (the topology is described inside a fixture, never
at import: on-chip-measurement guide, section 2). The whole step compiles
in ``test_aot_v5e.py``'s way in half a minute and is left to the chip
run."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_aot_one_tile import _kernel_calls
from test_aot_v5e import no_compile_cache, topo  # noqa: F401


@pytest.mark.parametrize("window", [None, 4096])
def test_streamed_banded_kernels_compile(window, topo,  # noqa: F811
                                         no_compile_cache,  # noqa: F811
                                         monkeypatch):
    import horovod_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, 8192, 28, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16,
                              sharding=one_chip)
    assert attention._one_tile_path(q, kv, 512, 1024) == 0     # streams
    grad = jax.grad(lambda q, k, v: attention.flash_attention(
        q, k, v, causal=True, window=window).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    text = jax.jit(grad).lower(q, kv, kv).compile().as_text()
    assert _kernel_calls(text) == {
        "hvd_flash_fwd": 1, "hvd_flash_bwd_dq": 1, "hvd_flash_bwd_dkv": 1}


def test_expert_layer_with_a_share_compiles(topo,  # noqa: F811
                                            no_compile_cache):  # noqa: F811
    from horovod_tpu.parallel.moe import grouped_gated_mlp, moe_apply_held

    one_chip = SingleDeviceSharding(topo.devices[0])
    tokens, hidden, width, held = 16384, 2560, 768, tuple(range(16))

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = {"w_gate": shape(16, hidden, width, dtype=jnp.float32),
              "w_up": shape(16, hidden, width, dtype=jnp.float32),
              "w_down": shape(16, width, hidden, dtype=jnp.float32)}

    def loss(params, x, logits):
        y, load = moe_apply_held(grouped_gated_mlp, params, x, logits,
                                 held, 6)
        return y.astype(jnp.float32).sum(), load

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        params, shape(tokens, hidden),
        shape(tokens, 64, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    # Three grouped products forward, five or six backward, each a Mosaic call
    # XLA names itself; the scopes of the layer are in the program.
    assert text.count('op_name="ragged-dot-none"') >= 8
    for scope in ("hvd.moe.route", "hvd.moe.dispatch", "hvd.moe.experts",
                  "hvd.moe.combine"):
        assert scope in text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 6e9
