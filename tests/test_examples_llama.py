"""Example smoke runs (see ``test_examples.py``): the decoder's training
scripts (generation and the decode profile: ``test_examples_decode.py``)."""

import os
import sys

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


def test_llama_remat_chunked_loss_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_llama_training.py"),
                "--model", "tiny", "--seq-len", "64", "--batch-size", "1",
                "--num-iters", "2", "--remat", "--chunked-loss", "4"])
    assert "tokens/sec" in out


def test_llama_chunked_loss_rejects_seq_parallel():
    err = _run([sys.executable, os.path.join(EX, "jax_llama_training.py"),
                "--model", "tiny", "--seq-len", "64", "--seq-parallel", "4",
                "--chunked-loss", "4"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=4"},
               expect_failure=True)
    assert "chunked-loss" in err


def test_jax_moe_lm_training_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_moe_lm_training.py"),
                "--model", "tiny", "--seq-len", "64", "--batch-size", "1",
                "--num-iters", "2"])
    assert "tokens/sec" in out


def test_llama_adafactor_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_llama_training.py"),
                "--model", "tiny", "--seq-len", "64", "--batch-size", "1",
                "--num-iters", "2", "--optimizer", "adafactor"])
    assert "tokens/sec" in out
