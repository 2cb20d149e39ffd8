"""The JoyAI-LLM-Flash cell's step (PR 40) compiled for a described TPU
v5e at its real size: two sequences of 8,192 tokens, hidden 2048, the
leading dense layer, four sparse ones and the multi-token-prediction
module (six blocks of multi-head latent attention with q and k 192 wide
over v 128, 8 of 256 routed experts held beside a shared one, two passes
of the head), AdamW on 491.7M parameters. The streamed flash kernels are
compiled by Mosaic at the two widths, where they had never run, with no
padded copy of q, k or v in the step: what the chip's compiler would
refuse costs no chip time here. Nothing runs; nothing here is a
measurement.

And the functions this PR changed underneath the cells that were there
lower to the text they lowered to on the parent commit 1742278: the
attention calls of every guard cell (both kernel families now read v's
width from v), the chunked loss one ahead (it now takes how far ahead its
target lies) and LFM2's held layer (``sigmoid_top_k`` now takes the
model's eps). The fixtures are ``test_aot_v5e.py``'s (the topology is
described inside a fixture, never at import: on-chip-measurement guide,
section 2); the step compiles once for the whole file."""

import base64
import functools
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from harness import manifest, scope_time, scopes
from test_aot_one_tile import _kernel_calls
from test_aot_v5e import (HBM_BYTES, _compile, _device_bytes,  # noqa: F401
                          no_compile_cache, topo)

CELL = "joyai-llm-flash-s8k-ep32share"
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
# The first 16 hexadecimal digits of the SHA-256 of the lowered text (no
# debug information; a kernel's Mosaic module as MLIR without its source
# locations), and the count of its operations, recorded on the parent
# commit 1742278 by the functions below.
PARENT = {
    "smallthinker-attention-global": ("9495a8df390f6061", 47),
    "smallthinker-attention-window-4096": ("08c4ec488ef0ceae", 47),
    "olmo-hybrid-attention": ("9eb31756dfb6eee1", 47),
    "laguna-attention-full": ("55ae0ed0d92d4243", 47),
    "laguna-attention-window-512": ("a713e7eae559771d", 47),
    "lfm2-attention-head-64": ("91469deb63870c53", 47),
    "bert-attention-one-tile": ("077510303d2b0629", 64),
    "smallthinker-chunked-loss-one-ahead": ("745af7186242b6e3", 135),
    "lfm2-held-layer": ("ed3e05855a582f38", 827),
}


def _shape(*dims, dtype=jnp.bfloat16, sharding=None):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


def _without_locations(text):
    """A lowered text with every kernel's Mosaic module printed as MLIR
    without its source locations: the serialized module a
    ``tpu_custom_call`` carries holds the file names and line numbers of
    ``ops/attention.py`` and of whoever called it, so a line added
    anywhere above a kernel would read as another program."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    tpu.register_dialect(context)
    context.allow_unregistered_dialects = True

    def module(found):
        config = json.loads(found.group(1).replace("\\22", '"'))
        body = base64.b64decode(config["custom_call_config"]["body"])
        return ir.Module.parse(body).operation.get_asm(
            enable_debug_info=False)

    with context:
        return re.sub(r'backend_config = "((?:[^"\\]|\\.)*)"', module,
                      text)


def digest(text):
    """``(first 16 hexadecimal digits of the SHA-256, operations)`` of a
    lowered text, its kernels without their source locations."""
    text = _without_locations(text)
    ops = re.findall(r"= \"?(stablehlo\.[\w.]+|func\.call|chlo\.[\w.]+)", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(ops)


def _attention(batch, seq, heads, kv_heads, width, window=None, causal=True,
               masked=False, topo=None):
    """The text an attention call of a guard cell's shape lowers to for
    the chip, forward and the three gradients, the kernels' Mosaic
    modules inside it."""
    import horovod_tpu.ops.attention as attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    q = _shape(batch, seq, heads, width, sharding=one_chip)
    k = _shape(batch, seq, kv_heads, width, sharding=one_chip)
    mask = _shape(batch, seq, dtype=jnp.bool_, sharding=one_chip)

    def loss(q, k, v, m):
        return attention.flash_attention(
            q, k, v, key_mask=m if masked else None, causal=causal,
            window=window).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k, mask).as_text()


def _chunked_loss(topo=None):
    """SmallThinker's head: hidden (2, 8192, 2560) x (2560, 37984), eight
    chunks, the target one ahead."""
    from horovod_tpu.models import chunked_causal_lm_loss

    return jax.jit(jax.value_and_grad(
        lambda h, w, ids: chunked_causal_lm_loss(h, w, ids, num_chunks=8),
        argnums=(0, 1))).lower(
        _shape(2, 8192, 2560), _shape(2560, 37984, dtype=jnp.float32),
        _shape(2, 8192, dtype=jnp.int32)).as_text()


def _lfm2_held_layer(topo=None):
    """LFM2's held layer: 8 of 64 experts 1536 wide, 4 chosen by the
    sigmoid rule with its bias at the default eps, 32,768 tokens."""
    from horovod_tpu.parallel.moe import (grouped_gated_mlp, moe_apply_held,
                                          sigmoid_top_k)

    params = {name: _shape(8, *dims, dtype=jnp.float32)
              for name, dims in (("w_gate", (2048, 1536)),
                                 ("w_up", (2048, 1536)),
                                 ("w_down", (1536, 2048)))}

    @jax.checkpoint
    def layer(params, bias, x, logits):
        return moe_apply_held(
            functools.partial(grouped_gated_mlp, activation=jax.nn.silu),
            params, x, logits, tuple(range(8)), 4,
            route=sigmoid_top_k(bias))

    def loss(params, bias, x, logits):
        y, load = layer(params, bias, x, logits)
        return y.astype(jnp.float32).sum(), (y, load)

    return jax.jit(jax.value_and_grad(
        loss, argnums=(0, 2, 3), has_aux=True)).lower(
        params, _shape(64, dtype=jnp.float32), _shape(32768, 2048),
        _shape(32768, 64, dtype=jnp.float32)).as_text()


OLD_CALLERS = {
    "smallthinker-attention-global": functools.partial(
        _attention, 2, 8192, 28, 4, 128),
    "smallthinker-attention-window-4096": functools.partial(
        _attention, 2, 8192, 28, 4, 128, window=4096),
    "olmo-hybrid-attention": functools.partial(
        _attention, 1, 8192, 15, 15, 128),
    "laguna-attention-full": functools.partial(
        _attention, 2, 8192, 48, 8, 128),
    "laguna-attention-window-512": functools.partial(
        _attention, 2, 8192, 64, 8, 128, window=512),
    "lfm2-attention-head-64": functools.partial(
        _attention, 4, 8192, 32, 8, 64),
    "bert-attention-one-tile": functools.partial(
        _attention, 64, 512, 12, 12, 64, causal=False, masked=True),
    "smallthinker-chunked-loss-one-ahead": _chunked_loss,
    "lfm2-held-layer": _lfm2_held_layer,
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_old_callers_lower_to_the_parents_text(
        name, topo, monkeypatch):  # noqa: F811
    """Letter for letter, the kernels' source locations apart."""
    import horovod_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)
    assert digest(OLD_CALLERS[name](topo=topo)) == PARENT[name]


# ------------------------------------------------------------- the step

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
    # parameter (the gradients are temporaries), 491,697,408 of them.
    m = compiled.memory_analysis()
    assert 5.90e9 < m.argument_size_in_bytes < 5.91e9
    # The harness keeps the first gradient beside the state through the
    # checked steps (4 bytes a parameter): the step must leave that room.
    assert _device_bytes(compiled) + 4 * 491.7e6 < HBM_BYTES


def test_the_kernels_run_at_the_two_widths_under_their_scope(text):
    # Six blocks attend: forward, its recomputation, and the two backward
    # kernels each; the other Mosaic calls are the grouped products XLA
    # names itself (5 sparse blocks x (12 products + 3 tile schedules)).
    assert _kernel_calls(text) == {
        "hvd_flash_fwd": 12, "hvd_flash_bwd_dq": 6, "hvd_flash_bwd_dkv": 6,
        "(unnamed)": 75}
    calls = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        found = re.search(r"/(layer_\d|block)/attention/"
                          r"(hvd\.attn\.[\w.]+)/(hvd_flash_\w+)/pallas_call",
                          line)
        if not found:
            continue
        block, scope, kernel = found.groups()
        calls.setdefault(block, []).append(kernel)
        assert scope == "hvd.attn.latent"
        # Heads folded into batch: q and k 192 wide, v, o and do 128.
        shapes = re.findall(r"bf16\[64,8192,(\d+)\]", line)
        widths = {"hvd_flash_fwd": ["128", "192", "192", "128"],
                  "hvd_flash_bwd_dq": ["192", "192", "192", "128", "128"],
                  "hvd_flash_bwd_dkv": ["192", "128", "192", "192", "128",
                                        "128"]}[kernel]
        assert shapes == widths, (kernel, shapes)
    # The module's block is ``mtp/block`` forward and, recomputed,
    # ``mtp/checkpoint/[rematted_computation/]block``.
    assert sorted(calls) == ["block"] + [f"layer_{i}" for i in range(5)]
    assert all(sorted(calls[block]) == sorted(KERNELS + KERNELS[:1])
               for block in sorted(calls))


def test_no_padded_copy_of_q_k_or_v_is_in_the_step(text):
    """The kernels' operands, heads folded into batch, are 192 and 128
    wide and nothing else; no array of tokens by heads is wider than its
    logical width (the widest is ``[k_n | v]``, 256, before its split);
    and what XLA pads is a part into its whole (a concatenation written
    as pads and adds, a split's cotangent): never a 192-wide head to the
    next whole tile of 256. (In HBM a 192-wide minor axis lies in (8,
    128) tiles, which is XLA's layout of any such array, not a copy.)"""
    assert set(re.findall(r"bf16\[64,8192,(\d+)\]", text)) == {"192", "128"}
    widths = set(re.findall(r"bf16\[2,8192,32,(\d+)\]", text))
    assert {"192", "128"} <= widths <= {"32", "64", "128", "192", "256"}
    pads = re.findall(
        r"= bf16\[(?:2,8192,32|64,8192),(\d+)\]\S* pad\(.*?"
        r"padding=(?:0_0x)+(\d+)_(\d+)", text)
    assert pads
    for width, low, high in pads:
        part = int(width) - int(low) - int(high)
        if width == "256":
            assert part == 128, (width, low, high)      # k_n or v
        else:
            assert width in ("64", "192") and part in (32, 64, 128)


@pytest.mark.parametrize("scope,blocks", [
    ("hvd.attn.latent", range(6)), ("hvd.attn.latent.proj", range(6)),
    ("hvd.mtp", (5,)), ("hvd.moe.shared", range(1, 6)),
    ("hvd.moe.route", range(1, 6)), ("hvd.moe.experts", range(1, 6))])
def test_the_scopes_are_in_the_step_forward_and_backward(scope, blocks, text):
    names = scope_time.names_under(text, (scope,))
    assert names
    ops = {op for name in names for op in scopes.op_names(text)[name]
           if scope_time._word(scope).search(op)}
    where = [f"/layer_{i}/" for i in range(5)] + ["/block/"]
    for i, part in enumerate(where):
        here = [op for op in ops if part in op]
        if i not in blocks:
            assert not here, (scope, part)
            continue
        assert any("transpose(" in op for op in here), (scope, part)
        assert any("transpose(" not in op for op in here), (scope, part)
    if scope == "hvd.mtp":
        # The module's second pass through the head is under it too.
        assert any("hvd.loss.head" in op for op in ops)
        assert any("hvd.attn.latent/" in op for op in ops)


def test_one_head_two_sweeps_and_no_conditional(text):
    loops = [line for line in text.splitlines()
             if re.search(r"=\s.*\swhile\(", line)
             and "hvd.loss.head" in line]
    assert len(loops) == 2
    assert sum("hvd.mtp" in line for line in loops) == 1
    assert not re.search(r"=\s.*\sconditional\(", text)
