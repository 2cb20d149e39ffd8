"""Example smoke runs (see ``test_examples.py``): the MXNet examples (over
``tests/fake_mxnet.py``), two ranks under the launcher."""

import os
import sys

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


def test_mxnet_example_two_ranks():
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, os.path.join(EX, "mxnet_mnist.py"),
                "--epochs", "1"])
    assert "epoch 0" in out


def test_mxnet_imagenet_resnet50_two_ranks():
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable,
                os.path.join(EX, "mxnet_imagenet_resnet50.py"),
                "--epochs", "1", "--steps-per-epoch", "2",
                "--batch-size", "4", "--image-size", "16",
                "--num-classes", "10"])
    assert "epoch 0" in out
