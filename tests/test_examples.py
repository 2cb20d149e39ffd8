"""Example smoke runs — the reference CI does the same for its examples
(.buildkite/gen-pipeline.sh:101-133). This file: the JAX examples on the
data axis. Its neighbours: ``test_examples_encoders.py`` (BERT and ViT),
``test_examples_parallel.py`` (the other axes),
``test_examples_llama.py``, ``test_examples_decode.py`` and
``test_examples_fsdp.py`` (the decoder's scripts: training, decoding,
sharded), ``test_examples_{torch,tensorflow,mxnet}.py`` (the other
frameworks). ``--dist loadfile`` starts the files with the most
tests first and the suite's last long file has four: files of two to four
launches fill the other workers while it runs, a file of twenty would be a
sixth of the wall on its own (``ROADMAP.md`` D9)."""

import os
import sys

import pytest

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


def test_jax_mnist_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_mnist.py"),
                "--epochs", "1", "--batch-size", "256"])
    assert "epoch 0" in out


def test_word2vec_example_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_word2vec.py"),
                "--steps", "50", "--batch-size", "256",
                "--vocab-size", "2000", "--embedding-dim", "32"])
    assert "pairs/sec" in out


@pytest.mark.slow  # ~40 s: two full example launches (train + resume)
def test_imagenet_resnet50_checkpoint_resume(tmp_path):
    ck = str(tmp_path / "ckpts")
    script = os.path.join(EX, "jax_imagenet_resnet50.py")
    args = ["--image-size", "32", "--batch-per-chip", "1", "--warmup-steps",
            "2", "--checkpoint-dir", ck, "--checkpoint-every", "2"]
    # Small mesh + persistent compile cache keep the two ResNet-50 compiles
    # affordable on the 1-core CI box.
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla_cache")}
    _run([sys.executable, script, "--steps", "2"] + args, extra_env=env)
    out = _run([sys.executable, script, "--steps", "3"] + args,
               extra_env=env)
    assert "resumed" in out and "ckpt_2" in out


def test_flash_benchmark_smoke():
    out = _run([sys.executable,
                os.path.join(EX, "flash_attention_benchmark.py"),
                "--batch", "1", "--seq-len", "128", "--heads", "2",
                "--head-dim", "16", "--block-q", "64", "--block-k", "64",
                "--iters", "2"])
    assert '"metric": "flash_fwd_ms"' in out


@pytest.mark.slow  # ~30 s/family: large-model compiles on CPU
@pytest.mark.parametrize("model,size", [("vgg16", "64"), ("inception3", "96")])
def test_jax_synthetic_benchmark_model_families(model, size):
    out = _run([sys.executable, os.path.join(EX, "jax_synthetic_benchmark.py"),
                "--model", model, "--batch-size", "2", "--num-iters", "2",
                "--num-batches", "1", "--image-size", size])
    assert "Img/sec per chip" in out
