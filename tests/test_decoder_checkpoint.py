"""What a decoder block's checkpoint keeps (PR 49): ``ops/attention.py``
names the flash forward kernel's two results, ``models/decoder.py``
``rematerialised`` saves those names, and the backward pass of a
checkpointed block does not call ``hvd_flash_fwd`` again. Held here for
every decoder family at its ``*_TINY`` widths:

* lowered for the chip (cross-platform lowering: the kernels become
  Mosaic custom calls, nothing is compiled and no TPU library is loaded),
  a checkpointed ``value_and_grad`` holds one ``hvd_flash_fwd`` beside
  each ``hvd_flash_bwd_dq``; with the names taken away, which is the
  forward rule as it was before, it holds two;
* the checkpoint changes no gradient
  (``test_decoder_checkpoint_gradients.py``);
* outside a checkpoint a name is nothing: without ``remat`` a model's
  step, a block's and ``flash_attention`` alone lower to the operations,
  in the numbers, that the rule without names lowers to.

``utils.comm_accounting.mosaic_calls_by_kernel`` is the reader."""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

import horovod_tpu.ops.attention as attention
from horovod_tpu import models
from horovod_tpu.common import profiler
from horovod_tpu.models import decoder
from horovod_tpu.models.llama import LlamaBlock
from horovod_tpu.ops.attention import (FLASH_LSE_NAME, FLASH_OUT_NAME,
                                       FLASH_RESIDUAL_NAMES,
                                       flash_attention, make_attention_fn)
from horovod_tpu.utils.comm_accounting import mosaic_calls_by_kernel
from decoder_checkpoint_helpers import (FAMILIES, SEQ, STREAMED, ids_of,
                                        loss_of, model_of)

FLASH = (profiler.KERNEL_FLASH_FWD, profiler.KERNEL_FLASH_BWD_DQ,
         profiler.KERNEL_FLASH_BWD_DKV)


def _for_the_chip(f, *shapes):
    """``f`` lowered for a TPU from here: StableHLO in which each Pallas
    kernel is a ``tpu_custom_call``."""
    return jax.jit(f).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


def _step_for_the_chip(family, remat):
    model = model_of(family, remat, **STREAMED)
    ids = ids_of(model)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    return _for_the_chip(jax.value_and_grad(loss_of(model, ids)), params)


def _operations(text):
    """``{operation: how many}`` of a StableHLO module, payloads, types
    and locations left out."""
    return collections.Counter(re.findall(
        r"^\s*(?:%[\w:#, %]+ = )?\"?((?:stablehlo|func|chlo)\.\w+)",
        text, re.M))


@pytest.fixture
def mosaic(monkeypatch):
    """On the CPU backend the program would interpret its kernels: steer
    it to the Mosaic branch, as the benchmark's AOT tests do."""
    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)


@pytest.fixture
def without_names(monkeypatch):
    """Calling it takes the names out of the forward rule for the rest of
    the test: the rule every PR before 49 had."""
    return lambda: monkeypatch.setattr(attention, "checkpoint_name",
                                       lambda x, name: x)


def _flash_calls(text):
    found = mosaic_calls_by_kernel(text)
    return tuple(found[kernel] for kernel in FLASH)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_checkpointed_step_calls_the_forward_kernel_once(
        family, mosaic, without_names):
    calls = FAMILIES[family][2]
    assert _flash_calls(_step_for_the_chip(family, True)) == (
        calls, calls, calls)
    # The policy with nothing to save is the plain checkpoint: the whole
    # block, its forward kernel included, is computed again.
    without_names()
    assert _flash_calls(_step_for_the_chip(family, True)) == (
        2 * calls, calls, calls)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_without_a_checkpoint_the_step_is_the_one_without_names(
        family, mosaic, without_names):
    calls = FAMILIES[family][2]
    text = _step_for_the_chip(family, False)
    assert _flash_calls(text) == (calls, calls, calls)
    assert FLASH_OUT_NAME not in text and FLASH_LSE_NAME not in text
    without_names()
    assert _operations(text) == _operations(_step_for_the_chip(family, False))


def test_both_results_have_to_be_kept(mosaic, monkeypatch):
    """With the output alone saved the recomputation still needs the row
    statistics, and the call stays."""
    monkeypatch.setattr(decoder, "FLASH_RESIDUAL_NAMES", (FLASH_OUT_NAME,))
    assert _flash_calls(_step_for_the_chip("llama", True)) == (4, 2, 2)


# ---- a name outside a checkpoint is nothing ------------------------------

def _attention_alone(window=None, **blocks):
    shape = jax.ShapeDtypeStruct((1, SEQ, 4, 32), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, SEQ, 2, 32), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               **blocks).astype(jnp.float32).sum()

    return _for_the_chip(jax.value_and_grad(loss, (0, 1, 2)), shape, kv, kv)


def _block_alone():
    cfg = dataclasses.replace(models.LLAMA_TINY, remat=False)
    block_cls = decoder.rematerialised(cfg, LlamaBlock)
    assert block_cls is LlamaBlock
    block = block_cls(cfg, attention_fn=make_attention_fn(
        causal=True, use_flash=True, **STREAMED))
    x = jax.ShapeDtypeStruct((1, SEQ, cfg.dim), jnp.bfloat16)
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)["params"]
    return _for_the_chip(jax.value_and_grad(
        lambda p, x: block.apply({"params": p}, x)[0].astype(
            jnp.float32).sum()), params, x)


@pytest.mark.parametrize("lowered", [
    pytest.param(_attention_alone, id="one-tile"),
    pytest.param(lambda: _attention_alone(**STREAMED), id="streamed"),
    pytest.param(lambda: _attention_alone(48, **STREAMED),
                 id="streamed-window"),
    pytest.param(_block_alone, id="block-without-remat"),
])
def test_a_name_outside_a_checkpoint_is_nothing(lowered, mosaic,
                                                without_names):
    text = lowered()
    assert _flash_calls(text) == (1, 1, 1)
    assert not any(name in text for name in FLASH_RESIDUAL_NAMES)
    # By kind and number: MLIR's suffix on a private function's symbol
    # (``@_where_11`` / ``@_where_12`` in the one-tile text) is no operation.
    assert _operations(text)["stablehlo.custom_call"] == 3
    without_names()
    assert _operations(text) == _operations(lowered())


# ---- the reader ----------------------------------------------------------

COMPILED = '''
  %jvp_hvd_flash_fwd_.1 = (bf16[1,256,2048]{2,1,0}, f32[2,1,2048]{2,1,0}) custom-call(%bitcast.27), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(LlamaLM)/layer_0/attention/hvd.attn.full/hvd_flash_fwd/pallas_call" stack_frame_id=11}
  %transpose_jvp_hvd_flash_bwd_dq__.1 = bf16[1,256,2048]{2,1,0} custom-call(%bitcast.28), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(hvd_flash_bwd_dq))/pallas_call"}
  %transpose_jvp_hvd_flash_bwd_dkv__.1 = bf16[1,256,2048]{2,1,0} custom-call(%bitcast.29), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(hvd_flash_bwd_dkv))/pallas_call"}
  %ragged-dot-none.3 = bf16[512,64]{1,0} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer_1/hvd.moe.experts/ragged_dot"}
  %fusion.hvd_flash_fwd = f32[8]{0} fusion(%p.2), kind=kLoop, metadata={op_name="jit(step)/hvd_flash_fwd/mul"}
'''


def test_the_reader_counts_mosaic_calls_by_kernel_name():
    found = mosaic_calls_by_kernel(COMPILED)
    assert sorted(found) == sorted(profiler.KERNELS + ("(unnamed)",))
    assert [(kernel, found[kernel]) for kernel in sorted(found)
            if found[kernel]] == [
        ("(unnamed)", 1), ("hvd_flash_bwd_dkv", 1), ("hvd_flash_bwd_dq", 1),
        ("hvd_flash_fwd", 1)]

    class Program:
        def as_text(self):
            return COMPILED

    assert mosaic_calls_by_kernel(Program()) == found
    assert mosaic_calls_by_kernel("ENTRY %main () -> f32[]") == dict.fromkeys(
        found, 0)
