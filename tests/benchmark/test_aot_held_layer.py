"""The held expert layer (PR 27) compiled for a described TPU v5e at the
cell's widths with a share of the experts, 16 of 64: one path over all
98,304 sorted rows, no branch and no loop, under all four scopes, in no
more temporary memory than the parent's layer took. Nothing runs; nothing
here is a measurement. The fixtures are ``test_aot_v5e.py``'s."""

import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from harness import scope_time
from test_aot_v5e import no_compile_cache, topo  # noqa: F401

MOE_SCOPES = ("hvd.moe.route", "hvd.moe.dispatch", "hvd.moe.experts",
              "hvd.moe.combine")
# ``temp_size_in_bytes`` of the same compile on the parent (PR 26), whose
# layer kept a ``[98304, 2560]`` select on either side of the experts and
# a ``[16384, 6, 2560]`` copy of the rows: the compiler's count for a
# described chip, here on the CPU host.
PARENT_TEMP_BYTES = 2_264_000_000


def test_the_share_compiles_to_one_path_with_every_scope(
        topo, no_compile_cache, capsys):  # noqa: F811
    from horovod_tpu.parallel.moe import grouped_gated_mlp, moe_apply_held

    one_chip = SingleDeviceSharding(topo.devices[0])
    tokens, hidden, width, held = 16384, 2560, 768, tuple(range(16))

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = {"w_gate": shape(16, hidden, width, dtype=jnp.float32),
              "w_up": shape(16, hidden, width, dtype=jnp.float32),
              "w_down": shape(16, width, hidden, dtype=jnp.float32)}

    @jax.checkpoint
    def layer(params, x, logits):
        return moe_apply_held(grouped_gated_mlp, params, x, logits, held, 6)

    def loss(params, x, logits):
        y, load = layer(params, x, logits)
        return y.astype(jnp.float32).sum(), (y, load)

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)).lower(
        params, shape(tokens, hidden),
        shape(tokens, 64, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    with capsys.disabled():
        print(f"\n[aot] expert layer with a share, temporaries: parent "
              f"{PARENT_TEMP_BYTES / 1e9:.3f} GB, now {temp / 1e9:.3f} GB")
    assert temp <= PARENT_TEMP_BYTES
    # Three grouped products forward and nine on the way back (three
    # recomputed, two gradients of each).
    assert text.count('op_name="ragged-dot-none"') == 12
    for scope in MOE_SCOPES:
        assert scope_time.names_under(text, (scope,)), scope
    # Nothing spans several phases: a ``conditional`` or a ``while`` under
    # one of the four scopes would have its whole span counted for it.
    assert not re.search(r"\s(while|conditional)\(", text)
