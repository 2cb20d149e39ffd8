"""The Laguna cell's step (PR 32) compiled for a described TPU v5e at its
real size: two sequences of 8,192 tokens, hidden 2048, the leading dense
layer and one period [sliding x3, full] of sparse layers (16 of 256 routed
experts held beside a shared one), the streamed flash kernels at window
512, below their key block of 1024, with 64 query heads over 8, and
causal with 48, AdamW on 490M parameters. What the chip's compiler would
refuse costs no chip time here. Nothing runs; nothing here is a
measurement. The fixtures are ``test_aot_v5e.py``'s (the topology is
described inside a fixture, never at import: on-chip-measurement guide,
section 2); the step compiles once for the whole file, in under a
minute."""

import re

import pytest

from harness import manifest, scope_time, scopes
from test_aot_one_tile import _kernel_calls
from test_aot_v5e import (HBM_BYTES, _compile, _device_bytes,  # noqa: F401
                          no_compile_cache, topo)

CELL = "laguna-xs.2-s8k-ep16share"
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


@pytest.fixture(scope="module")
def compiled(topo, no_compile_cache):  # noqa: F811
    monkeypatch = pytest.MonkeyPatch()
    try:
        yield _compile(CELL, topo, monkeypatch)
    finally:
        monkeypatch.undo()


@pytest.fixture(scope="module")
def text(compiled):
    return compiled.as_text()


def test_step_fits_the_chip_with_the_harness_beside_it(compiled):
    # Parameters and AdamW's two moments, float32: 12 bytes of the 16 a
    # parameter (the gradients are temporaries), 490,297,344 of them.
    m = compiled.memory_analysis()
    assert 5.88e9 < m.argument_size_in_bytes < 5.89e9
    # The harness keeps the first gradient beside the state through the
    # checked steps (4 bytes a parameter): the step must leave that room.
    assert _device_bytes(compiled) + 4 * 490.3e6 < HBM_BYTES


def test_the_streamed_flash_kernels_are_in_the_step_by_layer_type(text):
    # Five layers: forward, its recomputation, and the two backward
    # kernels each; the other Mosaic calls are the grouped products XLA
    # names itself (4 sparse layers x (12 products + 3 tile schedules)).
    assert _kernel_calls(text) == {
        "hvd_flash_fwd": 10, "hvd_flash_bwd_dq": 5, "hvd_flash_bwd_dkv": 5,
        "(unnamed)": 60}
    calls = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        found = re.search(r"/layer_(\d)/attention/(hvd\.attn\.\w+)/"
                          r"(hvd_flash_\w+)/pallas_call", line)
        if found:
            layer, scope, kernel = found.groups()
            calls.setdefault((scope, int(layer)), []).append(kernel)
    # Every kernel call is attributable to one scope or the other: the
    # full layers 0 and 4, the sliding layers 1-3.
    assert sorted(calls) == [("hvd.attn.full", 0), ("hvd.attn.full", 4),
                             ("hvd.attn.window", 1), ("hvd.attn.window", 2),
                             ("hvd.attn.window", 3)]
    assert all(sorted(calls[key]) == sorted(KERNELS + KERNELS[:1])
               for key in sorted(calls))


@pytest.mark.parametrize("scope,layers", [
    ("hvd.attn.pointwise", range(5)), ("hvd.attn.full", (0, 4)),
    ("hvd.attn.window", (1, 2, 3)), ("hvd.moe.shared", range(1, 5)),
    ("hvd.moe.dispatch", range(1, 5)), ("hvd.moe.experts", range(1, 5))])
def test_the_scopes_are_in_the_step_forward_and_backward(scope, layers,
                                                         text):
    names = scope_time.names_under(text, (scope,))
    assert names
    ops = {op for name in names for op in scopes.op_names(text)[name]
           if scope in op}
    for layer in range(5):
        here = [op for op in ops if f"/layer_{layer}/" in op]
        if layer not in layers:
            assert not here, (scope, layer)
            continue
        assert any("transpose(" in op for op in here), (scope, layer)
        assert any("transpose(" not in op for op in here), (scope, layer)


def test_pointwise_passes_fused_into_a_product_are_told_apart(text):
    """XLA fuses some of the rotary and gate passes into the projections'
    products; the reader counts the fusions that hold no product."""
    reader = manifest.load_module("layer_metrics", "attn_pointwise_ms")
    under = scope_time.names_under(text, (reader.SCOPE,))
    holders = reader.product_holders(text)
    assert under & holders and under - holders
    # A product of the step itself is a holder; so is the fusion around it.
    assert any(name.startswith("convolution") for name in holders)
    assert any(name.startswith("fusion") for name in holders)


def test_no_conditional_in_the_step(text):
    assert not re.search(r"=\s.*\sconditional\(", text)
