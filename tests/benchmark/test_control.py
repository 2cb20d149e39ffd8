"""``correct`` has to be able to come out false.

The control: the plain reference put in the program's place and computed
one precision below the configuration's bf16 reads not correct, at a size
a test run can hold (on the chip it was read at each cell's own size:
PERF.md, section 2). And a run with the timed path broken underneath (a
step that returns its state unchanged, a part of the batch left out, the
exchange between chips left out) reads ``correct`` false. These skip the
harness's look for a chip and drive the rest of a run."""

import time

import jax
import jax.numpy as jnp
import pytest

from builders import training
from harness import compare, manifest


def _cell(name):
    return manifest.Cell(name, rehearsal=True)


def _limits(cell):
    reference = manifest.load_module("reference", cell.config["reference"])
    return reference.REHEARSAL_LIMITS, reference.CONTROL


@pytest.mark.parametrize("name", ["resnet50-dp1", "bert-base-s512-dp1"])
def test_lower_precision_control_reads_not_correct(name):
    import horovod_tpu as hvd

    hvd.init()
    cell = _cell(name)
    limits, control = _limits(cell)
    builder = manifest.load_module("builders", cell.config["builder"])
    program = training.compile_program(cell, jax.devices()[:1],
                                       builder.build, {})
    steps = cell.traffic["checked_steps"]
    for seed in (2147483693, 11, 12):
        key, _, host_batch, _ = training.seeded_inputs(program, seed)
        ref = training.reference_numbers(cell, program, host_batch, key,
                                         steps)
        lower = training.reference_numbers(cell, program, host_batch, key,
                                           steps, precision=control)
        assert not compare.judge(training.gaps(lower, ref), limits)
        # The reference at the configuration's own precision is inside.
        same = training.reference_numbers(cell, program, host_batch, key,
                                          steps, precision="bf16")
        assert compare.judge(training.gaps(same, ref), limits)


def _run_broken(name, break_step, chips=1):
    import horovod_tpu as hvd

    hvd.init()
    cell = _cell(name)
    builder = manifest.load_module("builders", cell.config["builder"])

    def build(config, traffic, mesh):
        bench = builder.build(config, traffic, mesh)
        bench.step = break_step(bench.step)
        return bench

    return training.run({
        "cell": cell, "seed": 2147483693, "seconds": 0.3, "trace": False,
        "devices": jax.devices()[:chips], "spans": {},
        "t_start": time.perf_counter(), "out_dir": None}, build)


def test_sound_path_reads_correct():
    assert _run_broken("resnet50-dp1", lambda step: step)["correct"] is True


def test_step_that_returns_its_state_unchanged_reads_not_correct(capsys):
    def frozen(step):
        return jax.jit(lambda state, batch: (state, step(state, batch)[1]))

    assert _run_broken("resnet50-dp1", frozen)["correct"] is False
    out = capsys.readouterr().out
    assert "param_change_worst_matrix" in out and "NOT CORRECT" in out


def test_part_of_the_batch_left_out_reads_not_correct():
    def half(step):
        def twice(a):
            return jnp.concatenate([a[:a.shape[0] // 2]] * 2)

        return jax.jit(lambda state, batch: step(
            state, jax.tree.map(twice, batch)))

    assert _run_broken("bert-base-s128-dp1", half)["correct"] is False


def test_exchange_between_chips_left_out_reads_not_correct(monkeypatch,
                                                           capsys):
    import horovod_tpu as hvd

    # The optimizer handed back unwrapped: every replica keeps its own
    # gradient and nothing crosses the mesh.
    monkeypatch.setattr(hvd, "DistributedOptimizer",
                        lambda optimizer, **_: optimizer)
    assert _run_broken("resnet50-dp4", lambda step: step,
                       chips=4)["correct"] is False
    out = capsys.readouterr().out
    assert "no_all_reduce_over_all_replicas = 1" in out
    assert "replicas_not_bit_identical = 1" in out
