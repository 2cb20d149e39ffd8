"""``correct`` has to be able to come out false in the LFM2 cell (PR 38),
at the rehearsal's sizes on the CPU: the program with one mechanism of
the block broken underneath reads not correct at the rehearsal's limits.
The six faults: the bias left out of the choice, the bias added to the
weights, a softmax in place of the sigmoid, the convolution shifted by a
token, one gate dropped, the QK norm dropped. A fault is read by its
first step alone (the first loss and the first gradient, at their
limits): not correct there is not correct. The control, the plain
reference one precision below bf16, is read on the chip at the cell's
sizes and on the CPU by the scratch script the rehearsal's limits were
set with (``benchmarks/reference/lfm2-24b-a2b.py``); it is no test here,
where it would be another quarter of a minute of one worker.

One compiled step serves every case. Each mechanism is patched by a form
that computes both its sound and its broken result and selects by a
number the host holds (``FAULT``, read through a callback with no
argument, so that no gradient rule meets it): with 0 the step is the
builder's own, value for value, and reads correct; a compilation a fault
would be a minute and a half of one worker."""

import contextlib
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from builders import training
from harness import compare, manifest

CELL = "lfm2-24b-a2b-s8k-ep8share"
SEED = 2147483693
FAULTS = ("sound", "bias-left-out-of-the-choice",
          "bias-added-to-the-weights", "softmax-in-place-of-the-sigmoid",
          "convolution-shifted-by-a-token", "one-gate-dropped",
          "qk-norm-dropped")
FAULT = np.zeros((), np.int32)      # the host's: which fault is on


def _on(name):
    """Whether the fault ``name`` is on, read from the host when the step
    runs."""
    now = jax.pure_callback(lambda: np.asarray(FAULT, np.int32),
                            jax.ShapeDtypeStruct((), jnp.int32))
    return now == FAULTS.index(name)


def _switched_rule(real):
    """``sigmoid_top_k`` with the three faults of the routing rule."""
    def make(bias):
        sound, no_bias = real(bias), real(jnp.zeros_like(bias))

        def weigh(values):
            return values / (jnp.sum(values, -1, keepdims=True) + 1e-6)

        def rule(logits, k):
            ids, weights = sound(logits, k)
            plain_ids, plain_weights = no_bias(logits, k)
            # The bias in the weights too: the chosen s + b, normalised.
            lifted, _ = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, k)
            # Softmax scores, the bias still in the choice only.
            soft = jax.nn.softmax(logits, axis=-1)
            _, soft_ids = jax.lax.top_k(soft + bias, k)
            soft_weights = weigh(jnp.take_along_axis(soft, soft_ids, -1))
            for name, i, w in ((FAULTS[1], plain_ids, plain_weights),
                               (FAULTS[2], ids, weigh(lifted)),
                               (FAULTS[3], soft_ids, soft_weights)):
                ids = jnp.where(_on(name), i, ids)
                weights = jnp.where(_on(name), w, weights)
            return ids, weights

        return rule

    return make


def _switched_conv(real):
    def conv(x, w, activation=None):
        shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        return real(jnp.where(_on(FAULTS[4]), shifted, x), w, activation)

    return conv


def _switched_gates(real):
    def gated(b_gate, c_gate, u, taps):
        return real(b_gate, jnp.where(_on(FAULTS[5]), 1.0, c_gate).astype(
            c_gate.dtype), u, taps)

    return gated


def _switched_norm(real):
    def norm(self, x):
        normed = real(self, x)
        if self.name not in ("q_norm", "k_norm"):
            return normed
        return jnp.where(_on(FAULTS[6]), x.astype(normed.dtype), normed)

    return norm


@contextlib.contextmanager
def switches():
    """The program's block with every fault built in and off, for
    whatever is traced inside."""
    from horovod_tpu.models import lfm2

    patches = [(lfm2, "sigmoid_top_k", _switched_rule),
               (lfm2, "causal_conv", _switched_conv),
               (lfm2, "gated_short_conv", _switched_gates),
               (lfm2.RMSNorm, "__call__", _switched_norm)]
    real = [getattr(owner, name) for owner, name, _ in patches]
    for (owner, name, wrap), function in zip(patches, real):
        setattr(owner, name, wrap(function))
    try:
        yield
    finally:
        for (owner, name, _), function in zip(patches, real):
            setattr(owner, name, function)


@pytest.fixture(scope="module")
def rehearsal():
    """The switched program compiled once, and the reference's numbers."""
    import horovod_tpu as hvd

    hvd.init()
    cell = manifest.Cell(CELL, rehearsal=True)
    builder = manifest.load_module("builders", cell.config["builder"])
    built_from = copy.copy(cell)
    built_from.config = copy.deepcopy(cell.config)
    with switches():
        program = training.compile_program(
            built_from, jax.devices()[:1], builder.build, {})
    key, _, host_batch, _ = training.seeded_inputs(program, SEED)
    reference = training.reference_numbers(
        cell, program, host_batch, key, cell.traffic["checked_steps"])
    module = manifest.load_module("reference", cell.config["reference"])
    assert module.CONTROL == "int8"
    return cell, program, reference, module.REHEARSAL_LIMITS


@pytest.mark.parametrize("name", FAULTS)
def test_a_broken_mechanism_reads_not_correct(name, rehearsal, capsys):
    cell, program, reference, limits = rehearsal
    key, state, _, batch = training.seeded_inputs(program, SEED)
    sound = name == "sound"
    steps = cell.traffic["checked_steps"] if sound else 1
    FAULT[...] = FAULTS.index(name)
    try:
        _, ours = training.checked_steps(program, state, batch, key, steps)
    finally:
        FAULT[...] = 0
    if not sound:
        # The parameters' change is the reference's after three steps.
        ours["change"] = reference["change"]
        limits = {name: limits[name] for name in (
            "loss_step1", "first_gradient_worst_matrix",
            "first_gradient_global")}
    assert compare.judge(training.gaps(ours, reference), limits) is sound
    failed = [line for line in capsys.readouterr().out.splitlines()
              if line.endswith("NOT CORRECT")]
    print(name, "fails", len(failed), "limits:", *failed, sep="\n  ")
