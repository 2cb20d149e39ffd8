"""Example smoke runs (see ``test_examples.py``): the encoders' training
scripts, BERT and ViT."""

import os
import sys

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


def test_bert_example_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_bert_pretraining.py"),
                "--model", "tiny", "--seq-len", "32", "--batch-size", "1",
                "--num-iters", "2"])
    assert "sequences/sec" in out


def test_vit_example_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_vit_training.py"),
                "--model", "tiny", "--batch-per-chip", "2", "--steps", "4",
                "--warmup-steps", "1"],
               extra_env={
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert "vit-tiny" in out and "img/sec" in out
