"""Example smoke runs (see ``test_examples.py``): the examples of the
parallel axes, each on a few virtual devices: sequence (ring attention),
experts, pipeline stages under both schedules. The decoder's own
(``--seq-parallel``, FSDP, FSDP x TP) are ``test_examples_fsdp.py``'s."""

import os
import sys

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


def test_ring_attention_example_smoke():
    out = _run([sys.executable,
                os.path.join(EX, "jax_long_context_ring_attention.py"),
                "--seq-len", "64", "--heads", "2", "--head-dim", "8"])
    assert "ring attention" in out


def test_moe_example_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_moe_training.py"),
                "--steps", "15", "--tokens-per-device", "128",
                "--d-model", "16", "--d-hidden", "32"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=4"})
    assert "tokens/sec through" in out


def test_pipeline_example_smoke():
    out = _run([sys.executable,
                os.path.join(EX, "jax_pipeline_parallel.py"),
                "--steps", "10", "--microbatches", "8",
                "--microbatch-size", "4", "--features", "32"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=4"})
    assert "samples/sec through" in out


def test_pipeline_example_1f1b_smoke():
    out = _run([sys.executable,
                os.path.join(EX, "jax_pipeline_parallel.py"),
                "--steps", "10", "--microbatches", "8",
                "--microbatch-size", "4", "--features", "32",
                "--schedule", "1f1b"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=4"})
    assert "samples/sec through" in out
