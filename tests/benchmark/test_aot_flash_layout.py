"""The step of ``bert-base-s512-dp1`` compiled for a described TPU v5e
holds no relayout copy around its flash kernels (PR 29). Until then
``ops/attention.py`` folded heads into batch on both paths, (B, S, H, D)
-> (B * H, S, D), and XLA lowered eight copies a layer of
``bf16[64,12,512,64]`` around the 36 Mosaic calls (q, k, v in; o out; do
in; dq, dk, dv out: 96 a step), each into a form whose 64-wide minor axis
fills half of every 128-lane tile. The one-tile kernels now take
(B, H * D, S), which is how XLA keeps these arrays anyway (it writes the
projections sequence-minor), so the program's transposes compile to
bitcasts. Nothing runs; nothing here is a measurement. The fixtures and
the compile are ``test_aot_v5e.py``'s (the topology is described inside a
fixture, never at import: on-chip-measurement guide, section 2)."""

import re

from test_aot_one_tile import _kernel_calls
from test_aot_v5e import _compile, no_compile_cache, topo  # noqa: F401

# q, k, v, o and their gradients: 64 x 512 x 12 x 64 elements each.
ATTENTION_ELEMENTS = 64 * 512 * 12 * 64


def _attention_copies(text):
    """The ``copy`` operations under an attention module whose result is
    as large as one of attention's operands, whatever its rank."""
    found = []
    for line in text.splitlines():
        shape = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)
        if not shape or "/SelfAttention_0/" not in line:
            continue
        elements = 1
        for n in shape.group(1).split(","):
            elements *= int(n)
        if elements >= ATTENTION_ELEMENTS:
            found.append(line.strip()[:160])
    return found


def test_bert_s512_step_copies_nothing_around_the_flash_kernels(
        topo, no_compile_cache, monkeypatch):  # noqa: F811
    text = _compile("bert-base-s512-dp1", topo, monkeypatch).as_text()
    assert _kernel_calls(text) == {
        "hvd_flash_fwd": 12, "hvd_flash_bwd_dq": 12, "hvd_flash_bwd_dkv": 12}
    assert _attention_copies(text) == []
