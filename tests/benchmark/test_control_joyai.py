"""``correct`` has to be able to come out false in the JoyAI-LLM-Flash
cell (PR 40), at the rehearsal's sizes on the CPU: the program with one
mechanism broken underneath reads not correct at the rehearsal's limits.
The seven faults: ``v`` read at q's width (its heads taken 48 columns
apart where they lie 32 apart), the rotary key a head of its own (every
head but the first reads another vector), the halves rotated with no
de-interleaving, the bias added to the weights, ``lambda`` 0, the
module's target one ahead, the shared expert dropped. A fault is read by
its first step alone (the first loss and the first gradient, at their
limits): not correct there is not correct. The control, the plain
reference one precision below bf16, is read on the chip at the cell's
sizes and on the CPU by the scratch script the rehearsal's limits were
set with (``benchmarks/reference/joyai-llm-flash.py``); it is no test
here, where it would be another half a minute of one worker.

One compiled step serves every case. Each mechanism is patched by a form
that computes both its sound and its broken result and selects by a
number the host holds (``FAULT``, read through a callback with no
argument, so that no gradient rule meets it): with 0 the step is the
builder's own, value for value, and reads correct; a compilation a fault
would be most of a minute of one worker."""

import contextlib
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from builders import training
from harness import compare, manifest

CELL = "joyai-llm-flash-s8k-ep32share"
SEED = 2147483693
BROKEN = ("sound", "v-read-at-q-width", "rotary-key-a-head-of-its-own",
          "halves-rotated-with-no-deinterleaving",
          "bias-added-to-the-weights", "lambda-zero",
          "module-target-one-ahead", "shared-expert-dropped")
FAULT = np.zeros((), np.int32)      # the host's: which fault is on


def _on(name):
    """Whether the fault ``name`` is on, read from the host when the step
    runs."""
    now = jax.pure_callback(lambda: np.asarray(FAULT, np.int32),
                            jax.ShapeDtypeStruct((), jnp.int32))
    return now == BROKEN.index(name)


def _switched_attention(real):
    """``make_attention_fn`` whose function reads its operands wrongly on
    demand: v's heads ``q``'s width apart, and the rotary columns of k
    another vector a head."""
    def make(*args, **kwargs):
        attend = real(*args, **kwargs)

        def fn(q, k, v, mask):
            b, s, heads, vo = v.shape
            qk = q.shape[-1]
            flat = v.reshape(b, s, heads * vo)
            at = (np.arange(heads)[:, None] * qk + np.arange(vo)) \
                % (heads * vo)
            v = jnp.where(_on(BROKEN[1]), flat[..., at], v)
            rope = qk - vo
            own = jnp.stack([
                jnp.roll(k[:, :, h, vo:], h * rope // heads, axis=-1)
                for h in range(heads)], axis=2)
            k = jnp.where(_on(BROKEN[2]), jnp.concatenate(
                [k[..., :vo], own], axis=-1), k)
            return attend(q, k, v, mask)

        return fn

    return make


def _switched_deinterleave(real):
    return lambda x: jnp.where(_on(BROKEN[3]), x, real(x))


def _switched_rule(real):
    """``sigmoid_top_k`` with the bias in the weights too: the chosen ``s
    + b``, normalised."""
    def make(bias, eps):
        sound = real(bias, eps=eps)

        def rule(logits, k):
            ids, weights = sound(logits, k)
            lifted, _ = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, k)
            lifted = lifted / (jnp.sum(lifted, -1, keepdims=True) + eps)
            return ids, jnp.where(_on(BROKEN[4]), lifted, weights)

        return rule

    return make


def _switched_loss(real):
    def loss(hidden, mtp_hidden, head, ids, num_chunks, mtp_weight):
        from horovod_tpu.models import chunked_causal_lm_loss

        sound = real(hidden, mtp_hidden, head, ids, num_chunks=num_chunks,
                     mtp_weight=mtp_weight)
        main = real(hidden, None, head, ids, num_chunks=num_chunks)
        near = main + mtp_weight * chunked_causal_lm_loss(
            mtp_hidden, head, ids, num_chunks=num_chunks, ahead=1)
        return jnp.where(_on(BROKEN[5]), main,
                         jnp.where(_on(BROKEN[6]), near, sound))

    return loss


def _switched_mlp(real):
    def mlp(self, h):
        out = real(self, h)
        if self.name != "shared":
            return out
        return jnp.where(_on(BROKEN[7]), jnp.zeros_like(out), out)

    return mlp


@contextlib.contextmanager
def switches():
    """The program's block with every fault built in and off, for
    whatever is traced inside."""
    import horovod_tpu.models as models
    from horovod_tpu.models import joyai
    from horovod_tpu.ops import attention

    patches = [(attention, "make_attention_fn", _switched_attention),
               (joyai, "deinterleave", _switched_deinterleave),
               (joyai, "sigmoid_top_k", _switched_rule),
               (models, "joyai_lm_loss", _switched_loss),
               (joyai.GatedMLP, "__call__", _switched_mlp)]
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


@pytest.mark.parametrize("name", BROKEN)
def test_a_broken_mechanism_reads_not_correct(name, rehearsal, capsys):
    cell, program, reference, limits = rehearsal
    key, state, _, batch = training.seeded_inputs(program, SEED)
    sound = name == "sound"
    steps = cell.traffic["checked_steps"] if sound else 1
    FAULT[...] = BROKEN.index(name)
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
