"""Example smoke runs (see ``test_examples.py``): the decoder's generation
and decode-profile scripts (its training scripts: ``test_examples_llama.py``)."""

import os
import sys

from mp_harness import REPO
from mp_harness import run_example as _run

EX = os.path.join(REPO, "examples")


def test_llama_generation_example_smoke():
    out = _run([sys.executable, os.path.join(EX, "jax_llama_generation.py"),
                "--model", "tiny", "--prompt-len", "8",
                "--max-new-tokens", "8", "--batch-size", "2"])
    assert "decode tokens/sec" in out


def test_tp_decode_profile_smoke():
    # The round-6 serving path proof: the harness must classify the TP
    # mesh as kernel_tp, find ONLY kernel_tp markers in the lowered
    # step, and match the single-device greedy tokens exactly (f32).
    out = _run([sys.executable, os.path.join(EX, "tp_decode_profile.py"),
                "--model", "tiny", "--tp", "2", "--batch-size", "4",
                "--prompt-len", "8", "--max-new-tokens", "8",
                "--force-host-devices", "4", "--f32"])
    assert '"path": "kernel_tp"' in out
    assert '"token_parity_mismatches": 0' in out
