"""``correct`` has to be able to come out false in the Laguna cell (PR
32), at the rehearsal's sizes on the CPU. The control: the plain
reference put in the program's place one precision below bf16, in int8,
reads not correct (at bf16 it stays inside every limit; fp8 read not
correct on all 8 seeds the limits were set from). And the program with
one mechanism of the block broken underneath reads not correct: the
window ignored, the whole head rotated on a full layer, the gate dropped,
the shared expert dropped, the routed part's 2.5 dropped. Each broken
case compiles the step anew, half a minute of one worker: three run with
the suite and two are marked slow (all five read not correct here and on
the chip, PR 32). ``BROKEN`` is also what the builder's scratch script
patches in on the chip at the cell's own sizes (PERF.md, section 2)."""

import contextlib
import copy
import dataclasses

import jax
import pytest

from builders import training
from harness import compare, manifest

CELL = "laguna-xs.2-s8k-ep16share"
SEED = 2147483693


@contextlib.contextmanager
def _patched(module, attribute, wrap):
    real = getattr(module, attribute)
    setattr(module, attribute, wrap(real))
    try:
        yield
    finally:
        setattr(module, attribute, real)


def _window_ignored(cell):
    from horovod_tpu.ops import attention

    return _patched(attention, "make_attention_fn", lambda make: (
        lambda **kw: make(**{**kw, "window": None})))


def _whole_head_rotated(cell):
    from horovod_tpu.models import laguna

    return _patched(laguna, "rotary_arguments", lambda real: (
        lambda spec, width: real(
            dataclasses.replace(spec, fraction=1.0), width)))


def _gate_dropped(cell):
    from horovod_tpu.models import laguna

    return _patched(laguna, "head_gate", lambda _: (
        lambda ctx, gate_logits: ctx))


def _shared_expert_dropped(cell):
    from horovod_tpu.models import laguna

    return _patched(laguna.GatedMLP, "__call__", lambda real: (
        lambda self, h: real(self, h) * (0.0 if self.name == "shared"
                                         else 1.0)))


@contextlib.contextmanager
def _routed_scale_dropped(cell):
    cell.config["moe_routed_scaling_factor"] = 1.0
    yield


# name -> a context manager around building the program from ``cell`` (a
# copy the program alone is built from; the reference keeps the cell's).
BROKEN = {
    "window-ignored": _window_ignored,
    "whole-head-rotated-on-full-layers": _whole_head_rotated,
    "gate-dropped": _gate_dropped,
    "shared-expert-dropped": _shared_expert_dropped,
    "routed-scale-dropped": _routed_scale_dropped,
}


def program_numbers(cell, devices, seed, name=None):
    """``(program, host_batch, key, numbers)`` of the checked steps, with
    the mechanism ``name`` broken where one is named."""
    built_from = copy.copy(cell)
    built_from.config = copy.deepcopy(cell.config)
    builder = manifest.load_module("builders", cell.config["builder"])
    with BROKEN[name](built_from) if name else contextlib.nullcontext():
        program = training.compile_program(built_from, devices,
                                           builder.build, {})
    key, state, host_batch, batch = training.seeded_inputs(program, seed)
    _, numbers = training.checked_steps(
        program, state, batch, key, cell.traffic["checked_steps"])
    return program, host_batch, key, numbers


@pytest.fixture(scope="module")
def sound():
    """The sound program's checked steps and the reference's, once."""
    import horovod_tpu as hvd

    hvd.init()
    cell = manifest.Cell(CELL, rehearsal=True)
    program, host_batch, key, ours = program_numbers(
        cell, jax.devices()[:1], SEED)
    reference = training.reference_numbers(
        cell, program, host_batch, key, cell.traffic["checked_steps"])
    module = manifest.load_module("reference", cell.config["reference"])
    assert module.CONTROL == "int8"
    return (cell, program, host_batch, key, ours, reference,
            module.REHEARSAL_LIMITS)


def test_sound_program_reads_correct(sound):
    *_, ours, reference, limits = sound
    assert compare.judge(training.gaps(ours, reference), limits)


@pytest.mark.parametrize("precision,correct", [
    ("bf16", True), ("int8", False)])
def test_lower_precision_controls_read_not_correct(precision, correct,
                                                   sound):
    cell, program, host_batch, key, _, reference, limits = sound
    lower = training.reference_numbers(
        cell, program, host_batch, key, cell.traffic["checked_steps"],
        precision=precision)
    assert compare.judge(training.gaps(lower, reference), limits) is correct


# Slow: each case is another compilation of the rehearsal's step; the
# three that run with the suite cover attention's band, the gate and the
# shared expert.
SLOW = ("routed-scale-dropped", "whole-head-rotated-on-full-layers")


@pytest.mark.parametrize("name", [
    pytest.param(name, marks=pytest.mark.slow) if name in SLOW else name
    for name in sorted(BROKEN)])
def test_a_broken_mechanism_reads_not_correct(name, sound, capsys):
    cell, *_, reference, limits = sound
    *_, ours = program_numbers(cell, jax.devices()[:1], SEED, name)
    assert not compare.judge(training.gaps(ours, reference), limits)
    failed = [line for line in capsys.readouterr().out.splitlines()
              if line.endswith("NOT CORRECT")]
    print(name, "fails", len(failed), "limits:", *failed, sep="\n  ")
