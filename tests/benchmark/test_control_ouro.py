"""``correct`` has to be able to come out false in the Ouro cell (PR 48),
at the rehearsal's sizes on the CPU: the program with one mechanism of
the looped model broken underneath reads not correct at the rehearsal's
limits. The five faults: the norm after a sublayer left out, the final
norm's output not fed to the next pass (the exits still read it), uniform
weights in the gate's place, the entropy's sign turned, three passes for
four (the fourth pass runs and no exit reads it). A fault is read by its
first step alone (the first loss and the first gradient, by its norms and
by the norm of its difference from the reference's, at their limits): not correct there is not correct. The control, the plain
reference one precision below bf16, is read on the chip at the cell's
sizes and on the CPU by ``benchmarks/tools/read_gaps.py --rehearse-cpu``,
which the rehearsal's limits were set with
(``benchmarks/reference/ouro-2.6b.py``); it is no test here, where it
would be another quarter of a minute of one worker.

One compiled step serves every case. Each mechanism is patched by a form
that computes both its sound and its broken result and selects by a
number the host holds (``FAULT``, read through a callback with no
argument, so that no gradient rule meets it): with 0 the step is the
builder's own, value for value, and reads correct."""

import contextlib
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from builders import training
from harness import compare, manifest

CELL = "ouro-2.6b-s8k-loop4"
SEED = 2147483693
FAULTS = ("sound", "norm-after-a-sublayer-left-out",
          "final-norm-not-fed-to-the-next-pass",
          "uniform-weights-in-the-gates-place", "entropys-sign-turned",
          "three-passes-for-four")
FAULT = np.zeros((), np.int32)      # the host's: which fault is on


def _on(name):
    """Whether the fault ``name`` is on, read from the host when the step
    runs."""
    now = jax.pure_callback(lambda: np.asarray(FAULT, np.int32),
                            jax.ShapeDtypeStruct((), jnp.int32))
    return now == FAULTS.index(name)


def _switched_norm(real, unnormed):
    def norm(self, x):
        normed = real(self, x)
        if self.name == "final_norm":
            unnormed.append(x)      # what the next pass must not read
        if not self.name.endswith("layernorm_2"):
            return normed
        return jnp.where(_on(FAULTS[1]), x.astype(normed.dtype), normed)

    return norm


def _switched_block(real, unnormed):
    def block(self, x, *args):
        if self.name == "layer_0" and unnormed:
            # A pass after the first: the stream as the pass before left
            # it, before its final norm.
            x = jnp.where(_on(FAULTS[2]), unnormed[-1].astype(x.dtype), x)
        return real(self, x, *args)

    return block


def _switched_embedding(real, unnormed):
    def embedding(cfg):
        unnormed.clear()    # a new call of the model: no pass behind it
        return real(cfg)

    return embedding


def _switched_distribution(real):
    def log_p(gate_logits):
        sound = real(gate_logits)
        uniform = jnp.full_like(sound, -np.log(sound.shape[0]))
        # The exits of the passes before the last as a distribution of
        # their own; the last pass's exit weighs nothing.
        short = jnp.concatenate([real(gate_logits[:-1]),
                                 jnp.full_like(sound[:1], -1e30)], axis=0)
        return jnp.where(_on(FAULTS[3]), uniform,
                         jnp.where(_on(FAULTS[5]), short, sound))

    return log_p


def _switched_entropy(real):
    def entropy(log_p):
        sound = real(log_p)
        return jnp.where(_on(FAULTS[4]), -sound, sound)

    return entropy


@contextlib.contextmanager
def switches():
    """The program's model with every fault built in and off, for
    whatever is traced inside."""
    from horovod_tpu.models import ouro

    unnormed = []
    patches = [
        (ouro.RMSNorm, "__call__", lambda f: _switched_norm(f, unnormed)),
        (ouro.OuroBlock, "__call__", lambda f: _switched_block(f, unnormed)),
        (ouro, "token_embedding",
         lambda f: _switched_embedding(f, unnormed)),
        (ouro, "exit_log_distribution", _switched_distribution),
        (ouro, "exit_entropy", _switched_entropy)]
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
        _, ours = training.checked_steps(program, state, batch, key, steps,
                                         keep_gradient=True)
    finally:
        FAULT[...] = 0
    if not sound:
        # The parameters' change is the reference's after three steps.
        ours["change"] = reference["change"]
        limits = {name: limits[name] for name in (
            "loss_step1", "first_gradient_worst_matrix",
            "first_gradient_global", "first_gradient_difference",
            "first_gradient_difference_worst_matrix")}
    assert compare.judge(training.gaps(ours, reference), limits) is sound
    failed = [line for line in capsys.readouterr().out.splitlines()
              if line.endswith("NOT CORRECT")]
    print(name, "fails", len(failed), "limits:", *failed, sep="\n  ")
