"""Eager multi-process tier: spawn real rank processes over the TCP star.

This is the rebuild's analogue of the reference CI running every test under
``mpirun -np 2`` (SURVEY.md §4): true multi-process collectives on one host,
no accelerators required. This file: the ring data plane under the default
(native) engine. Its neighbours hold the other planes:
``test_multiprocess_frameworks.py`` (the torch, TensorFlow and MXNet
bindings), ``test_multiprocess_python.py`` (the star and the Python engine),
``test_multiprocess_hierarchical.py`` (two-level rings and /dev/shm) and
``test_multiprocess_stall.py`` (stalls and dead peers)."""

import pytest

from mp_harness import run_ring_ranks as run_ranks


@pytest.mark.parametrize("scenario", [
    "allreduce", "fusion", "allgather", "broadcast", "cache",
    "error_mismatch", "duplicate_name", "optimizer", "inplace", "grouped",
    "objects", "reducescatter_alltoall",
])
def test_two_ranks(scenario):
    run_ranks(scenario, size=2)


def test_three_ranks_allreduce():
    run_ranks("allreduce", size=3)


def test_three_ranks_reducescatter_alltoall():
    # 5 rows over 3 ranks: uneven array_split blocks [2, 2, 1]; alltoall
    # with three distinct per-rank block sizes.
    run_ranks("reducescatter_alltoall", size=3)


def test_allreduce_unpipelined_escape_hatch():
    """HOROVOD_RING_PIPELINE=0 restores exchange-then-reduce (the
    measurement escape hatch in allreduce_bandwidth_r4.json) — full dtype
    matrix must stay correct on both code paths."""
    run_ranks("allreduce", size=3,
              extra_env={"HOROVOD_RING_PIPELINE": "0"})


def test_copybench_inplace_not_slower():
    """Zero-copy micro-bench: the in-place path (0 staging copies) must at
    least match the value path (1 defensive copy) in bytes/sec; before the
    zero-copy engine the eager tier staged 4 host copies per tensor."""
    outs = run_ranks("copybench", size=2)
    ratios = []
    for out in outs:
        for line in out.splitlines():
            if line.startswith("copybench"):
                ratios.append(float(line.rsplit("ratio=", 1)[1]))
    assert len(ratios) == 2, outs
    # Shared-core CI box is noisy; require "not meaningfully slower" and
    # let the printed numbers document the typical win.
    assert min(ratios) > 0.85, ratios


def test_timeline_multiprocess(tmp_path):
    tl_file = tmp_path / "timeline.json"
    run_ranks("allreduce", size=2, extra_env={
        "HOROVOD_TIMELINE": str(tl_file),
        "HOROVOD_TIMELINE_MARK_CYCLES": "1",
    })
    content = tl_file.read_text()
    # Markers the reference timeline test asserts (test/test_timeline.py).
    assert "NEGOTIATE_ALLREDUCE" in content
    assert "ALLREDUCE" in content
    assert "CYCLE_START" in content


def test_three_ranks_broadcast_nonzero_root():
    run_ranks("broadcast", size=3)


def test_autotune_stays_correct(tmp_path):
    log = tmp_path / "autotune.csv"
    run_ranks("autotune", size=2, extra_env={
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_AUTOTUNE_LOG": str(log),
    })
    # Coordinator scored at least one configuration.
    assert log.exists() and log.read_text().strip()
