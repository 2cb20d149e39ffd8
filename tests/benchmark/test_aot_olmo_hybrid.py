"""The Olmo-Hybrid cell's step (PR 30) compiled for a described TPU v5e at
its real size: one sequence of 8,192 tokens, hidden 3840, three gated
delta-rule layers (15 heads, keys 96, values 192, chunks of 64) and one
full-attention layer (15 heads of width 128, the streamed flash kernels),
AdamW on 766M parameters. What the chip's compiler would refuse costs no
chip time here. Nothing runs; nothing here is a measurement. The fixtures
are ``test_aot_v5e.py``'s (the topology is described inside a fixture,
never at import: on-chip-measurement guide, section 2); the step compiles
once for the whole file, in about a minute."""

import re

import pytest

from harness import scope_time, scopes
from test_aot_one_tile import _kernel_calls
from test_aot_v5e import (HBM_BYTES, _compile, _device_bytes,  # noqa: F401
                          no_compile_cache, topo)

CELL = "olmo-hybrid-7b-s8k-tp2share"
SCOPES = ("hvd.linattn.conv", "hvd.linattn.scan", "hvd.linattn.gate")


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


def test_step_fits_the_chip(compiled):
    # Parameters and AdamW's two moments, float32: 12 bytes of the 16 a
    # parameter (the gradients are temporaries).
    m = compiled.memory_analysis()
    assert 9.19e9 < m.argument_size_in_bytes < 9.20e9
    assert _device_bytes(compiled) < HBM_BYTES


def test_the_three_streamed_flash_kernels_are_in_the_step(text):
    # The full layer's forward, its recomputation, and the two backward
    # kernels; none for the linear layers.
    assert _kernel_calls(text) == {
        "hvd_flash_fwd": 2, "hvd_flash_bwd_dq": 1, "hvd_flash_bwd_dkv": 1}


@pytest.mark.parametrize("scope", SCOPES)
def test_the_linear_layers_scopes_are_in_the_step(scope, text):
    names = scope_time.names_under(text, (scope,))
    assert names
    # Forward, recomputed and backward alike, on each of the three layers.
    ops = {op for name in names for op in scopes.op_names(text)[name]
           if scope in op}
    for layer in range(3):
        here = [op for op in ops if f"/layer_{layer}/" in op]
        assert any("transpose(" in op for op in here), (scope, layer)
        assert any("transpose(" not in op for op in here), (scope, layer)
    assert not any("/layer_3/" in op for op in ops)


def test_the_scan_over_chunks_is_a_loop_under_its_scope(text):
    # Three linear layers x (forward, recomputed forward, backward): nine
    # loops, each carrying the scope in its own op_name, so the reader
    # counts a loop whole.
    loops = [line for line in text.splitlines()
             if re.search(r"=\s.*\swhile\(", line)
             and "hvd.linattn.scan" in line]
    assert len(loops) == 9
    under = scope_time.names_under(text, ("hvd.linattn.scan",))
    for line in loops:
        assert re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+)", line).group(1) \
            in under


def test_no_conditional_in_the_step(text):
    assert not re.search(r"=\s.*\sconditional\(", text)
