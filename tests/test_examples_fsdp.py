"""Example smoke runs (see ``test_examples.py``): the decoder trained
sharded on eight virtual devices: FSDP, FSDP x TP, and the sequence axis."""

import os
import sys

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


def test_llama_fsdp_smoke():
    out = _run([sys.executable, os.path.join(EX,
                                             "jax_llama_fsdp_training.py"),
                "--model", "tiny", "--seq-len", "64", "--num-iters", "2"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "tokens/sec" in out
    assert "param shard fraction=1/8" in out


def test_llama_fsdp_tp_hybrid_smoke():
    out = _run([sys.executable, os.path.join(EX,
                                             "jax_llama_fsdp_training.py"),
                "--model", "tiny", "--seq-len", "64", "--num-iters", "2",
                "--tensor-parallel", "2"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "dp=4 tp=2" in out


def test_llama_seq_parallel_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_llama_training.py"),
                "--model", "tiny", "--seq-len", "64", "--batch-size", "1",
                "--num-iters", "2", "--seq-parallel", "4"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=4"})
    assert "tokens/sec" in out
