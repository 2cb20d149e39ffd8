"""``attn_latent_copies_ms`` (PR 42) on ``test_rehearsal_joyai.py``'s hand
run: device time under ``hvd.attn.latent`` outside the three flash
kernels; nothing from a program without the scope, nothing without a
device trace, and listed by JoyAI's cell alone."""

import pytest

from harness import manifest
from test_rehearsal_joyai import CELL, HAND_TEXT, _hand_run, _read

NAME = "attn_latent_copies_ms"


def test_it_reads_what_is_under_the_scope_and_no_kernel():
    # ``fold.1``, 30 ns over two steps: the one operation under the scope
    # that is no Mosaic call; ``fusion.1`` and ``up.1`` are under
    # ``hvd.attn.latent.proj``, another word, and ``flash.4`` under none.
    assert _read(NAME, _hand_run()) == pytest.approx(30 / 2 / 1e6)
    # With the copy gone the scope holds kernels only: zero, not nothing.
    run = _hand_run()
    run["compiled_text"] = HAND_TEXT.replace(
        "hvd.attn.latent/transpose", "transpose")
    assert _read(NAME, run) == 0.0


def test_it_returns_nothing_where_there_is_nothing_to_read():
    run = _hand_run()
    run["compiled_text"] = HAND_TEXT.replace("hvd.attn.", "attn.")
    assert _read(NAME, run) is None
    assert _read(NAME, dict(_hand_run(), trace=None)) is None


def test_the_cell_lists_it_and_no_other_cell_does():
    entry, = [m for m in manifest.load_manifest()["per_layer"]
              if m["name"] == NAME]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "kernels: ops/attention.py"
    assert entry["moves"] == "train_samples_per_s_per_chip"
    assert NAME in {m["name"] for m in manifest.Cell(CELL).per_layer}
