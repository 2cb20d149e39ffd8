"""Eager multi-process tier on a 2 x 2 layout (two simulated nodes of two
ranks, the launcher's ``-H`` grouping): the two-level data plane (local
ring + cross ring of local roots) on both engines, the /dev/shm local
plane, and the layouts that must fall back to the flat ring. The harness:
``mp_harness.py``; the flat ring: ``test_multiprocess.py``."""

import sys

import pytest

from mp_harness import WORKER, run_launcher


def _launch(scenario, np=4, **env):
    """``np`` ranks of an mp_worker scenario as two nodes of two, through
    the launcher; fails with its output unless it exits 0."""
    res = run_launcher(
        ["-np", str(np), "-H", "localhost:2,localhost:2",
         sys.executable, WORKER, scenario], extra_env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    return res.stdout


@pytest.mark.parametrize("engine", ["native", "python"])
def test_hierarchical_two_level(engine):
    # 4 ranks as 2 simulated nodes x 2 ranks via the launcher's -H grouping;
    # the reference's HOROVOD_HIERARCHICAL_* env vars flip on the two-level
    # data plane (local ring + cross ring of local roots) in both engines.
    out = _launch("hierarchical", HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                  HOROVOD_HIERARCHICAL_ALLGATHER="1", HOROVOD_ENGINE=engine)
    for r in range(4):
        assert f"worker rank={r} scenario=hierarchical: OK" in out


def test_timeline_names_shm_data_plane(tmp_path):
    """With the shm local plane active, timeline activities must say which
    plane moved the bytes (SHM_CROSS_RING_COLLECTIVE, docs/timeline.md)."""
    tl_file = tmp_path / "timeline.json"
    _launch("hierarchical", HOROVOD_HIERARCHICAL_ALLREDUCE="1",
            HOROVOD_ENGINE="native", HOROVOD_TIMELINE=str(tl_file))
    content = tl_file.read_text()
    assert "SHM_CROSS_RING_COLLECTIVE" in content
    assert "NEGOTIATE_ALLREDUCE" in content


def test_shm_allgather_multipass_uneven_counts():
    """Per-rank blocks larger than a tiny 4 KiB shm slot force the
    chunked multi-pass allgather/allreduce paths with uneven counts."""
    out = _launch("shmgather", HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                  HOROVOD_HIERARCHICAL_ALLGATHER="1",
                  HOROVOD_ENGINE="native", HOROVOD_SHM_SLOT_BYTES="4096")
    for r in range(4):
        assert f"worker rank={r} scenario=shmgather: OK" in out


def _run_shmbench(shm_disable):
    out = _launch("shmbench", HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                  HOROVOD_ENGINE="native",
                  HOROVOD_SHM_DISABLE="1" if shm_disable else None)
    # Launcher output is rank-prefixed ("[2]: shmbench rank=2 rate=...").
    rates = [float(line.rsplit("rate=", 1)[1].replace("MB/s", ""))
             for line in out.splitlines()
             if "shmbench rank=" in line and "rate=" in line]
    assert len(rates) == 4, out
    return min(rates)


@pytest.mark.slow  # ~14 s: best-of-two comparative bench, not a
def test_shm_local_plane_beats_loopback():  # correctness gate
    """The /dev/shm local data plane (MPI_Win_allocate_shared analogue)
    must clearly beat the TCP loopback local ring it replaces — same-host
    bytes move as memcpys through one shared mapping instead of crossing
    the kernel socket stack twice."""
    # Best-of-two per config: the timeshared CI core adds +-20% run noise
    # on the loopback denominator.
    shm_rate = max(_run_shmbench(shm_disable=False) for _ in range(2))
    tcp_rate = max(_run_shmbench(shm_disable=True) for _ in range(2))
    print(f"shm={shm_rate:.1f}MB/s loopback={tcp_rate:.1f}MB/s "
          f"ratio={shm_rate / tcp_rate:.2f}")
    # Observed ~1.3-1.9x end-to-end on the 1-core CI box. The local phase
    # alone is far beyond 2x; the measured number is diluted by the
    # cross-ring TCP phase both configs share and by 4 processes
    # timesharing one core across the shm barriers. Threshold sits well
    # under the observed floor so scheduler noise can't flake the build.
    assert shm_rate > 1.15 * tcp_rate, (shm_rate, tcp_rate)


def test_autotune_categorical_hierarchical_stays_correct():
    # Autotune on a 2x2-node layout (rings available, hierarchical flag OFF)
    # may flip the two-level path mid-run via the synced reply; results must
    # stay correct throughout.
    out = _launch("autotune", HOROVOD_AUTOTUNE="1", HOROVOD_ENGINE="python")
    for r in range(4):
        assert f"worker rank={r} scenario=autotune: OK" in out


def test_hierarchical_flags_heterogeneous_layout_falls_back():
    # 3 ranks over localhost:2,localhost:2 gives groups of 2 and 1: the
    # launcher must NOT export group rings (mixed sizes would diverge the
    # per-rank path choice) and the job must still produce correct results
    # on the flat data plane.
    out = _launch("allreduce", np=3, HOROVOD_HIERARCHICAL_ALLREDUCE="1")
    for r in range(3):
        assert f"worker rank={r} scenario=allreduce: OK" in out
