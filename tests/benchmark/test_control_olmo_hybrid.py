"""``correct`` has to be able to come out false in the Olmo-Hybrid cell
(PR 30), at the rehearsal's sizes on the CPU. The control: the plain
reference put in the program's place one precision below bf16, int8 and
fp8, reads not correct (at bf16 it stays inside every limit). And the
program with one step of the linear layer broken underneath reads not
correct: the decay left out, ``beta`` not doubled, the convolution shifted
by one token, the state reset at every chunk boundary. ``BROKEN`` is also
what the builder's scratch script patches in on the chip at the cell's
own sizes (PERF.md, section 2)."""

import contextlib

import jax
import jax.numpy as jnp
import pytest

from builders import training
from harness import compare, manifest

CELL = "olmo-hybrid-7b-s8k-tp2share"
SEED = 2147483693


def _no_decay(rule):
    return lambda q, k, v, g, beta, **kw: rule(q, k, v, 0.0 * g, beta, **kw)


def _beta_not_doubled(rule):
    return lambda q, k, v, g, beta, **kw: rule(q, k, v, g, 0.5 * beta, **kw)


def _state_reset_each_chunk(rule):
    def reset(q, k, v, g, beta, chunk=64, output_final_state=False):
        b, s = q.shape[:2]
        apart = lambda x: x.reshape((b * s // chunk, chunk)  # noqa: E731
                                    + x.shape[2:])
        o, state = rule(*map(apart, (q, k, v, g, beta)), chunk=chunk,
                        output_final_state=True)
        o = o.reshape((b, s) + o.shape[2:])
        state = state.reshape((b, s // chunk) + state.shape[1:])[:, -1]
        return (o, state) if output_final_state else o

    return reset


def _convolution_shifted(conv):
    return lambda x, w: conv(jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1], w)


# name -> (the function of ops.linear_attention the layer calls, its
# broken form)
BROKEN = {
    "decay-left-out": ("gated_delta_rule", _no_decay),
    "beta-not-doubled": ("gated_delta_rule", _beta_not_doubled),
    "state-reset-each-chunk": ("gated_delta_rule", _state_reset_each_chunk),
    "convolution-shifted": ("causal_conv_silu", _convolution_shifted),
}


@contextlib.contextmanager
def broken(name):
    """The program's linear layer with one step broken underneath, for
    whatever is traced inside the block."""
    from horovod_tpu.ops import linear_attention

    attribute, wrap = BROKEN[name]
    real = getattr(linear_attention, attribute)
    setattr(linear_attention, attribute, wrap(real))
    try:
        yield
    finally:
        setattr(linear_attention, attribute, real)


def program_numbers(cell, devices, seed, name=None):
    """``(program, host_batch, key, numbers)`` of the checked steps, with
    the step ``name`` broken where one is named."""
    builder = manifest.load_module("builders", cell.config["builder"])
    with broken(name) if name else contextlib.nullcontext():
        program = training.compile_program(cell, devices, builder.build, {})
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
    limits = manifest.load_module(
        "reference", cell.config["reference"]).REHEARSAL_LIMITS
    return cell, program, host_batch, key, ours, reference, limits


def test_sound_program_reads_correct(sound):
    *_, ours, reference, limits = sound
    assert compare.judge(training.gaps(ours, reference), limits)


@pytest.mark.parametrize("precision,correct", [
    ("bf16", True), ("int8", False), ("fp8", False)])
def test_lower_precision_controls_read_not_correct(precision, correct,
                                                   sound):
    cell, program, host_batch, key, _, reference, limits = sound
    lower = training.reference_numbers(
        cell, program, host_batch, key, cell.traffic["checked_steps"],
        precision=precision)
    assert compare.judge(training.gaps(lower, reference), limits) is correct


@pytest.mark.parametrize("name", sorted(BROKEN))
def test_a_broken_step_reads_not_correct(name, sound, capsys):
    cell, *_, reference, limits = sound
    *_, ours = program_numbers(cell, jax.devices()[:1], SEED, name)
    assert not compare.judge(training.gaps(ours, reference), limits)
    failed = [line for line in capsys.readouterr().out.splitlines()
              if line.endswith("NOT CORRECT")]
    print(name, "fails", len(failed), "limits:", *failed, sep="\n  ")
