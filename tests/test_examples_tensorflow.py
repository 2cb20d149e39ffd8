"""Example smoke runs (see ``test_examples.py``): the TensorFlow and Keras examples,
two ranks under the launcher."""

import os
import sys

import pytest

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


@pytest.mark.slow  # ~14 s; test_word2vec_example_smoke keeps the
def test_tensorflow_word2vec_two_ranks():  # word2vec path in tier-1
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, os.path.join(EX, "tensorflow_word2vec.py"),
                "--steps", "10", "--batch-size", "64",
                "--vocab-size", "500", "--embedding-dim", "16"])
    # The embedding gradient must ride the sparse IndexedSlices path while
    # the dense projection gradient rides the dense allreduce path.
    assert "embedding grad: IndexedSlices" in out
    assert "proj grad: EagerTensor" in out


@pytest.mark.slow  # ~11 s; spark coverage stays in test_spark{,_e2e}.py
def test_keras_spark_rossmann_fallback_path():
    # pyspark is absent in this image; the example's in-process path still
    # runs the full feature-engineering + entity-embedding pipeline.
    out = _run([sys.executable, os.path.join(EX, "keras_spark_rossmann.py"),
                "--epochs", "1", "--rows", "1024"])
    assert "final exp_rmspe=" in out


@pytest.mark.slow  # ~15 s; tensorflow_mnist_eager_two_ranks keeps the tf
def test_tensorflow_mnist_two_ranks():  # 2-rank mnist path in tier-1
    # The tf.function path: allreduce rides a py_function node inside the
    # traced step.
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, os.path.join(EX, "tensorflow_mnist.py"),
                "--epochs", "1", "--batch-size", "256"])
    assert "epoch 0" in out


def test_tensorflow_mnist_eager_two_ranks():
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, os.path.join(EX, "tensorflow_mnist_eager.py"),
                "--steps", "5", "--batch-size", "32"])
    assert "step 0" in out


def test_tensorflow_keras_mnist_two_ranks(tmp_path):
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, os.path.join(EX, "tensorflow_keras_mnist.py"),
                "--epochs", "1", "--batch-size", "256",
                "--model-dir", str(tmp_path)])
    assert "final: acc=" in out


@pytest.mark.slow  # ~14 s; tensorflow_keras_mnist_two_ranks keeps the
def test_keras_mnist_advanced_two_ranks():  # keras 2-rank path in tier-1
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, os.path.join(EX, "keras_mnist_advanced.py"),
                "--epochs", "2", "--batch-size", "256",
                "--warmup-epochs", "1"])
    assert "final: acc=" in out


@pytest.mark.slow  # ~65 s: 2-rank keras ResNet-50 train + resume
def test_keras_imagenet_resnet50_two_ranks(tmp_path):
    fmt = str(tmp_path / "ck-{epoch}.keras")
    base = [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
            sys.executable,
            os.path.join(EX, "keras_imagenet_resnet50.py"),
            "--steps-per-epoch", "2", "--batch-size", "2",
            "--image-size", "32", "--num-classes", "10",
            "--checkpoint-format", fmt]
    out = _run(base + ["--epochs", "1"])
    assert "final:" in out
    # Rank 0 wrote a FULL .keras checkpoint (optimizer state included).
    assert os.path.exists(fmt.format(epoch=1))
    # Second run resumes: rank 0 restores epoch 1 through hvd.load_model
    # (optimizer re-wrapped in DistributedOptimizer, reference
    # examples/keras_imagenet_resnet50.py:100-104) and only epoch 2 trains.
    out = _run(base + ["--epochs", "2"])
    assert "Epoch 2/2" in out
    assert "Epoch 1/2" not in out
    assert "final:" in out


@pytest.mark.slow  # ~22 s model build; torch_synthetic_benchmark keeps
def test_tensorflow_synthetic_benchmark_two_ranks():  # the bench path
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable,
                os.path.join(EX, "tensorflow_synthetic_benchmark.py"),
                "--model", "MobileNetV2", "--batch-size", "4",
                "--image-size", "32", "--num-classes", "10",
                "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
                "--num-iters", "2"])
    assert "Total img/sec on 2 worker(s):" in out
