"""The Ouro cell's step (PR 48) lowered for a described TPU v5e at its
real size (one sequence of 8,192 tokens, hidden 2048, six layers run four
times on one set of parameters, the four exits through the whole
49,152-row head in one sweep, AdamW on 509.7M parameters), and the
streamed flash kernels compiled by Mosaic at its heads (16 over 16 of
width 128, one sequence): what the chip's compiler would refuse of a
kernel costs no chip time here. The whole step is lowered and not
compiled: its compilation takes close to three minutes of one worker
here; the chip's own is in ``PERF.md``. Nothing runs; nothing here is a
measurement.

What the lowered text holds to: the passes are unrolled (24 applications
of a block are 96 flash calls and no ``while`` stands around a block: the
one ``while`` of the step is the head's sweep), the scopes the cell's
readers read are there, and the optimizer is handed one gradient a shared
parameter. The fixtures are ``test_aot_v5e.py``'s (the topology is
described inside a fixture, never at import: on-chip-measurement guide,
section 2)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from harness import manifest
from test_aot_one_tile import _kernel_calls
from test_aot_v5e import no_compile_cache, topo  # noqa: F401

CELL = "ouro-2.6b-s8k-loop4"
LEAVES = 6 * (4 + 3 + 4) + 5    # a layer: attention, MLP, four norms


@pytest.fixture(scope="module")
def lowered(topo):  # noqa: F811
    import horovod_tpu as hvd
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
        text = bench.step.lower(*bench.arg_shapes()).as_text(debug_info=True)
        yield text, bench, hvd.profiler.exchanges()[-1]
    finally:
        monkeypatch.undo()


def test_the_passes_are_unrolled_and_the_head_is_the_one_loop(lowered):
    text, _, _ = lowered
    # 6 layers x 4 passes, each: forward, its recomputation, dq, dk/dv.
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 24 * 4 == 96
    assert set(re.findall(r'hvd_flash_\w+(?=/pallas_call)', text)) == {
        "hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"}
    # No ``while`` around a block, a pass or the passes: the one loop of
    # the step is the sweep over the head's chunks, under its scope.
    loops = re.findall(r"stablehlo\.while", text)
    assert len(loops) == 1
    names = set(re.findall(r'#loc\d+ = loc\("([^"]*/while[^"]*)"', text))
    assert names and all("hvd.loss.head" in name for name in names)
    assert not any("hvd.loop.pass" in name for name in names)


def test_the_scopes_the_readers_read_are_in_the_step(lowered):
    text, _, _ = lowered
    for scope in ("hvd.loop.pass", "hvd.loop.exit", "hvd.attn.full",
                  "hvd.loss.head", "hvd.update"):
        word = re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")
        assert word.search(text), scope
    # A kernel is attention's, inside a pass; the gate's product and the
    # exit distribution are the exits'; the head is outside both.
    assert re.search(
        r"hvd\.loop\.pass/layer_5/attention/hvd\.attn\.full/hvd_flash_fwd",
        text)
    assert re.search(r"hvd\.loop\.pass/final_norm/", text)
    assert re.search(r"hvd\.loop\.exit/early_exit_gate/dot_general", text)
    assert re.search(r"jvp\(hvd\.loop\.exit\)/jit\(log_sigmoid\)", text)
    assert not re.search(r"hvd\.loop\.(pass|exit)/[^\"]*hvd\.loss\.head",
                         text)
    # One ``layer_i`` a layer, not one a pass.
    assert set(re.findall(r"/layer_(\d+)/", text)) == set("012345")


def test_the_optimizer_gets_one_gradient_a_shared_parameter(lowered):
    _, bench, exchange = lowered
    shapes = jax.tree.leaves(bench.weight_shapes)
    assert len(shapes) == LEAVES == 71
    assert sum(int(np.prod(s.shape)) for s in shapes) == 509_661_185
    # What the step hands to ``hvd.DistributedOptimizer``: as many leaves
    # and bytes as there are parameters, whatever the number of passes.
    assert exchange.leaves == LEAVES
    assert exchange.bytes_asked == 4 * 509_661_185
    assert bench.flops_per_step == pytest.approx(100.196e12, rel=1e-4)


def test_the_streamed_kernels_compile_at_the_cells_heads(
        topo, no_compile_cache, monkeypatch):  # noqa: F811
    import horovod_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)
    cell = manifest.Cell(CELL)
    config, traffic = cell.config, cell.traffic
    batch, seq = traffic["per_chip_batch"], traffic["sequence_length"]
    heads, width = config["num_attention_heads"], config["head_dim"]
    assert (batch, seq, heads, config["num_key_value_heads"], width) == (
        1, 8192, 16, 16, 128)
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((batch, seq, heads, width), jnp.bfloat16,
                             sharding=one_chip)
    grad = jax.grad(lambda q, k, v: attention.flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(q, q, q).compile().as_text()
    assert _kernel_calls(text) == {
        "hvd_flash_fwd": 1, "hvd_flash_bwd_dq": 1, "hvd_flash_bwd_dkv": 1}
    # Past one tile: the streamed path.
    assert attention._one_tile_path(q, q, 512, 1024) == 0
