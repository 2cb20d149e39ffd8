"""Eager multi-process tier: what a job does when a rank is late or
dead. Stall warnings and the cooperative stall shutdown on both engines,
and a SIGKILLed peer surfacing as an error, never a hang. The harness:
``mp_harness.py``; the healthy runs: ``test_multiprocess.py``."""

import pytest

from mp_harness import finish, free_port, launch_rank, ring_env
from mp_harness import run_ring_ranks as run_ranks


def test_stall_warning():
    outs = run_ranks("stall", size=2, extra_env={
        "HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
        "HOROVOD_LOG_LEVEL": "warning",
    })
    # Coordinator (rank 0) logs the reference-style stall warning naming the
    # missing ranks (operations.cc:688-769).
    assert "waiting for remainder of ranks" in outs[0]
    assert "stall.t" in outs[0]


def test_stall_shutdown():
    run_ranks("stall_shutdown", size=2, timeout=60, extra_env={
        "HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
        "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "2",
    })


@pytest.mark.parametrize("engine", ["native", "python"])
def test_peer_death_surfaces_engine_error(engine):
    """Kill rank 1 (SIGKILL, no shutdown message) after a warm collective:
    rank 0's next op must error within the stall timeout — ring EOF or
    cooperative stall shutdown — never hang (round-3 verdict item #7)."""
    addr = f"127.0.0.1:{free_port()}"
    env = dict(ring_env(2), HOROVOD_ENGINE=engine,
               HOROVOD_STALL_CHECK_TIME_SECONDS="1",
               HOROVOD_STALL_SHUTDOWN_TIME_SECONDS="5")
    procs = [launch_rank("peer_death", rank, 2, addr, extra_env=env)
             for rank in range(2)]
    # Rank 1 dies by SIGKILL and in no other way; rank 0 exits 0.
    outputs = finish(procs, 90.0, f"peer_death[{engine}]",
                     allowed_exit={1: (-9,)})
    assert "peer-death error surfaced" in outputs[0], outputs[0]


def test_native_engine_timeline_stall_parity(tmp_path):
    # The native engine's C++ timeline writes the same vocabulary the Python
    # timeline test asserts (reference test/test_timeline.py markers).
    tl_file = tmp_path / "native_timeline.json"
    outs = run_ranks("stall", size=2, extra_env={
        "HOROVOD_ENGINE": "native",
        "HOROVOD_TIMELINE": str(tl_file),
        "HOROVOD_TIMELINE_MARK_CYCLES": "1",
        "HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
    })
    assert "waiting for remainder of ranks" in outs[0]
    content = tl_file.read_text()
    assert "NEGOTIATE_ALLREDUCE" in content
    assert "CYCLE_START" in content
