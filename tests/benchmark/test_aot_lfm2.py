"""The LFM2 cell's step (PR 38) lowered for a described TPU v5e at its
real size (four sequences of 8,192 tokens, hidden 2048, one dense layer
and one period [attention, convolution x3] of sparse layers, 8 of 64
routed experts held, the head tied, AdamW on 469M parameters), and the
streamed flash kernels compiled by Mosaic at its head width 64, where
they had never run: what the chip's compiler would refuse of a kernel
costs no chip time here. The whole step is lowered and not compiled: its
compilation takes two minutes of one worker here; the chip's own is in
``PERF.md``. Nothing runs; nothing here is a measurement.

And the functions this PR changed underneath the cells that were there
lower to the text they lowered to on the parent commit: SmallThinker's
and Laguna's held layer (``moe_apply_held`` now calls a routing rule the
model gives), Olmo-Hybrid's ``causal_conv_silu`` (now a caller of
``causal_conv``). The fixtures are ``test_aot_v5e.py``'s (the topology is
described inside a fixture, never at import: on-chip-measurement guide,
section 2)."""

import collections
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from harness import manifest
from test_aot_one_tile import _kernel_calls
from test_aot_v5e import no_compile_cache, topo  # noqa: F401

CELL = "lfm2-24b-a2b-s8k-ep8share"
# The first 16 hexadecimal digits of the SHA-256 of the lowered text (no
# debug information), and the count of its operations, recorded on the
# parent commit ae30c7a by the same three functions below.
PARENT = {
    "smallthinker-held-layer": ("eb1633c03d7ab6c5", 689),
    "laguna-held-layer": ("454ee609729eb716", 781),
    "olmo-hybrid-causal-conv-silu": ("2eaf7c4842c3bbaf", 164),
}


@pytest.fixture(scope="module")
def lowered(topo):  # noqa: F811
    import horovod_tpu.ops.attention as attention

    # On the CPU backend the program would interpret its kernels; the step
    # is lowered for the chip, so steer it to the Mosaic branch here.
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)
    try:
        cell = manifest.Cell(CELL)
        builder = manifest.load_module("builders", cell.config["builder"])
        mesh = Mesh(np.array(topo.devices[:cell.chips]), ("data",))
        bench = builder.build(cell.config, cell.traffic, mesh)
        yield bench.step.lower(*bench.arg_shapes()).as_text(debug_info=True)
    finally:
        monkeypatch.undo()


def test_the_step_lowers_with_its_kernels_products_and_scopes(lowered):
    # One attention layer: forward, its recomputation, dq and dk/dv.
    calls = [line for line in lowered.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 4
    named = collections.Counter(
        re.findall(r'hvd_flash_\w+(?=/pallas_call)', lowered))
    assert set(named) == {"hvd_flash_fwd", "hvd_flash_bwd_dq",
                          "hvd_flash_bwd_dkv"}
    # Four sparse layers x (three grouped products forward, three
    # recomputed, two gradients of each).
    assert len(re.findall(r"= \"?(?:stablehlo|chlo)\.ragged_dot", lowered)) \
        == 4 * 12
    for scope in ("hvd.shortconv", "hvd.shortconv.pointwise",
                  "hvd.attn.full", "hvd.moe.route", "hvd.moe.dispatch",
                  "hvd.moe.experts", "hvd.moe.combine", "hvd.loss.head",
                  "hvd.update"):
        word = re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")
        found = [m.start() for m in word.finditer(lowered)]
        assert found, scope
    # The sigmoid and the bias's add are the route's; a kernel is
    # attention's.
    assert re.search(r'hvd\.moe\.route/logistic', lowered)
    assert re.search(r'layer_1/attention/hvd\.attn\.full/hvd_flash_fwd',
                     lowered)
    # The convolution mixers are layers 0, 2, 3, 4 and no other.
    layers = set(re.findall(r"layer_(\d)/conv/hvd\.shortconv/", lowered))
    assert layers == {"0", "2", "3", "4"}


def test_the_streamed_kernels_compile_at_head_width_64(
        topo, no_compile_cache, monkeypatch):  # noqa: F811
    import horovod_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)
    cell = manifest.Cell(CELL)
    config, traffic = cell.config, cell.traffic
    builder = manifest.load_module("builders", config["builder"])
    batch, seq = traffic["per_chip_batch"], traffic["sequence_length"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    width = builder.head_dim(config)
    assert (batch, seq, heads, kv, width) == (4, 8192, 32, 8, 64)
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((batch, seq, heads, width), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((batch, seq, kv, width), jnp.bfloat16,
                             sharding=one_chip)
    grad = jax.grad(lambda q, k, v: attention.flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(q, k, k).compile().as_text()
    assert _kernel_calls(text) == {
        "hvd_flash_fwd": 1, "hvd_flash_bwd_dq": 1, "hvd_flash_bwd_dkv": 1}
    # Past one tile: the streamed path, heads folded into batch and
    # blocks (1, block, 64).
    assert attention._one_tile_path(q, k, 512, 1024) == 0
    assert re.search(r"bf16\[128,8192,64\]", text)


def _shape(*dims, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(dims, dtype)


def _held_layer(tokens, hidden, width, router, chosen, activation):
    """The text a held expert layer of 16 experts lowers to, forward and
    backward under ``jax.checkpoint``, through the rule its model gives."""
    from horovod_tpu.parallel.moe import (grouped_gated_mlp, moe_apply_held,
                                          softmax_top_k)

    params = {name: _shape(16, *dims, dtype=jnp.float32)
              for name, dims in (("w_gate", (hidden, width)),
                                 ("w_up", (hidden, width)),
                                 ("w_down", (width, hidden)))}

    @jax.checkpoint
    def layer(params, x, logits):
        return moe_apply_held(
            functools.partial(grouped_gated_mlp, activation=activation),
            params, x, logits, tuple(range(16)), chosen, route=softmax_top_k)

    def loss(params, x, logits):
        y, load = layer(params, x, logits)
        return y.astype(jnp.float32).sum(), (y, load)

    return jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)).lower(
        params, _shape(tokens, hidden),
        _shape(tokens, router, dtype=jnp.float32)).as_text()


def _causal_conv_silu():
    from horovod_tpu.ops import linear_attention

    def loss(x, w):
        return linear_attention.causal_conv_silu(x, w).astype(
            jnp.float32).sum()

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        _shape(1, 8192, 1440), _shape(4, 1440, dtype=jnp.float32)).as_text()


@pytest.mark.parametrize("name,lower", [
    ("smallthinker-held-layer", functools.partial(
        _held_layer, 16384, 2560, 768, 64, 6, jax.nn.relu)),
    ("laguna-held-layer", functools.partial(
        _held_layer, 16384, 2048, 512, 256, 8, jax.nn.silu)),
    ("olmo-hybrid-causal-conv-silu", _causal_conv_silu),
])
def test_the_old_callers_lower_to_the_parents_text(name, lower):
    """Letter for letter, or where names shift to the same operations in
    the same numbers."""
    text = lower()
    digest, operations = PARENT[name]
    ops = re.findall(r"= \"?(stablehlo\.[\w.]+|func\.call|chlo\.[\w.]+)", text)
    assert len(ops) == operations
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
