"""Example smoke runs (see ``test_examples.py``): the PyTorch examples,
two ranks under the launcher."""

import os
import sys

import pytest

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


def test_torch_mnist_two_ranks():
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, os.path.join(EX, "torch_mnist.py"),
                "--epochs", "1", "--batch-size", "128"])
    assert "epoch 0" in out


@pytest.mark.slow  # ~24 s (two launches); torch_mnist_two_ranks keeps
def test_torch_imagenet_resnet50_two_ranks_resume(tmp_path):  # torch 2-rank
    fmt = str(tmp_path / "checkpoint-{epoch}.pth.tar")
    script = os.path.join(EX, "torch_imagenet_resnet50.py")
    args = ["--steps-per-epoch", "2", "--batch-size", "2", "--image-size",
            "32", "--num-classes", "10", "--checkpoint-format", fmt]
    _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
          sys.executable, script, "--epochs", "1"] + args)
    assert os.path.exists(fmt.format(epoch=1))
    # Second run resumes past epoch 0 from the rank-0 checkpoint.
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, script, "--epochs", "2"] + args)
    assert "epoch 1" in out and "epoch 0:" not in out


def test_torch_synthetic_benchmark_two_ranks():
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable,
                os.path.join(EX, "torch_synthetic_benchmark.py"),
                "--num-iters", "2", "--num-warmup", "1",
                "--batch-size", "8", "--image-size", "32"])
    assert "total img/sec on 2 ranks" in out
