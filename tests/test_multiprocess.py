"""Eager multi-process tier: spawn real rank processes over the TCP star.

This is the rebuild's analogue of the reference CI running every test under
``mpirun -np 2`` (SURVEY.md §4): true multi-process collectives on one host,
no accelerators required."""

import os
import socket
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "mp_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launcher_env(**extra):
    """Env for tests that go through ``python -m horovod_tpu.run``: repo on
    PYTHONPATH, CPU-only ranks, fast cycle time. ``extra`` values override;
    a value of ``None`` unsets."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_CYCLE_TIME"] = "1"
    for key, value in extra.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return env


def run_ranks(scenario: str, size: int = 2, timeout: float = 120.0,
              extra_env=None, per_rank_env=None):
    addr = f"127.0.0.1:{_free_port()}"
    ring_addrs = ",".join(f"127.0.0.1:{_free_port()}" for _ in range(size))
    procs = []
    for rank in range(size):
        env = _launcher_env(
            HOROVOD_RANK=str(rank),
            HOROVOD_SIZE=str(size),
            HOROVOD_LOCAL_RANK=str(rank),
            HOROVOD_LOCAL_SIZE=str(size),
            HOROVOD_CONTROLLER_ADDR=addr,
            HOROVOD_RING_ADDRS=ring_addrs,
        )
        env.update(extra_env or {})
        env.update((per_rank_env or {}).get(rank, {}))
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, scenario],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    deadline = time.monotonic() + timeout
    outputs = []
    for rank, proc in enumerate(procs):
        remaining = max(1.0, deadline - time.monotonic())
        try:
            out, _ = proc.communicate(timeout=remaining)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise AssertionError(
                f"scenario {scenario}: rank {rank} timed out")
        outputs.append(out)
    for rank, (proc, out) in enumerate(zip(procs, outputs)):
        assert proc.returncode == 0, (
            f"scenario {scenario}: rank {rank} failed "
            f"(exit {proc.returncode}):\n{out}")
    return outputs


@pytest.mark.parametrize("scenario", [
    "allreduce", "fusion", "allgather", "broadcast", "cache",
    "error_mismatch", "duplicate_name", "optimizer", "torch", "tensorflow",
    "mxnet", "inplace", "grouped", "objects", "reducescatter_alltoall",
])
def test_two_ranks(scenario):
    run_ranks(scenario, size=2)


def test_three_ranks_allreduce():
    run_ranks("allreduce", size=3)


def test_three_ranks_reducescatter_alltoall():
    # 5 rows over 3 ranks: uneven array_split blocks [2, 2, 1]; alltoall
    # with three distinct per-rank block sizes.
    run_ranks("reducescatter_alltoall", size=3)


@pytest.mark.slow  # ~11 s edge variant; test_tf_custom_op_two_ranks
def test_tf_custom_op_mixed_availability_agrees_on_fallback():  # stays
    """One rank opts out of the custom-op path (the shape of a host whose
    op library can't build): the job-wide vote in ``_custom_ops`` must drop
    BOTH ranks to the py_function path — a mixed-path job would diverge
    anonymous collective names (trace-time vs per-execution autonaming)
    and stall negotiation."""
    from horovod_tpu.tensorflow import tf_ops

    # Pre-build in the parent: rank 0's availability probe inside the vote
    # would otherwise spend minutes compiling while rank 1 sits parked in
    # the agreement allreduce, racing the timeout on a cold cache.
    tf_ops.build()
    run_ranks("tensorflow", size=2, timeout=240.0,
              per_rank_env={1: {"HOROVOD_TENSORFLOW_CUSTOM_OP": "0"}})


def test_tf_custom_op_two_ranks():
    """TF custom-op data path (tensorflow/src/tf_ops.cc) across real ranks:
    graph-node collectives, gradients, validation errors. Building the op
    library against the TF headers takes minutes on one core, so the parent
    builds (or reuses the cached .so) before the ranks spawn."""
    from horovod_tpu.tensorflow import tf_ops

    tf_ops.build()
    run_ranks("tf_custom_op", size=2, timeout=240.0)


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
    outs = run_ranks("copybench", size=2, timeout=300)
    ratios = []
    for out in outs:
        for line in out.splitlines():
            if line.startswith("copybench"):
                ratios.append(float(line.rsplit("ratio=", 1)[1]))
    assert len(ratios) == 2, outs
    # Shared-core CI box is noisy; require "not meaningfully slower" and
    # let the printed numbers document the typical win.
    assert min(ratios) > 0.85, ratios


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
    size = 2
    addr = f"127.0.0.1:{_free_port()}"
    ring_addrs = ",".join(f"127.0.0.1:{_free_port()}" for _ in range(size))
    procs = []
    for rank in range(size):
        env = _launcher_env(
            HOROVOD_RANK=str(rank),
            HOROVOD_SIZE=str(size),
            HOROVOD_LOCAL_RANK=str(rank),
            HOROVOD_LOCAL_SIZE=str(size),
            HOROVOD_CONTROLLER_ADDR=addr,
            HOROVOD_RING_ADDRS=ring_addrs,
            HOROVOD_ENGINE=engine,
            HOROVOD_STALL_CHECK_TIME_SECONDS="1",
            HOROVOD_STALL_SHUTDOWN_TIME_SECONDS="5",
        )
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, "peer_death"], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    deadline = time.monotonic() + 90.0
    outputs = []
    for rank, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(
                timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise AssertionError(
                f"peer_death[{engine}]: rank {rank} hung after peer died")
        outputs.append(out)
    assert procs[1].returncode == -9, (
        f"rank 1 should have been SIGKILLed: {procs[1].returncode}\n"
        f"{outputs[1]}")
    assert procs[0].returncode == 0, (
        f"rank 0 failed (exit {procs[0].returncode}):\n{outputs[0]}")
    assert "peer-death error surfaced" in outputs[0], outputs[0]


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


@pytest.mark.parametrize("scenario", ["allreduce", "allgather", "broadcast"])
def test_star_data_plane(scenario):
    # Pure-Python fallback path (HOROVOD_CPU_OPS=star) stays correct.
    run_ranks(scenario, size=2, extra_env={"HOROVOD_CPU_OPS": "star"})


@pytest.mark.parametrize("scenario", [
    "allreduce", "fusion", "cache", "error_mismatch", "duplicate_name",
    "inplace", "objects", "reducescatter_alltoall",
    # grouped behind @slow on this engine (~15 s: torch+tf imports in one
    # worker); python-engine fusion grouping stays covered by [fusion]
    # and the native run of the full grouped scenario stays in tier-1.
    pytest.param("grouped", marks=pytest.mark.slow),
    # TF on the Python controller = the tf.py_function fallback path (the
    # native-engine run of this scenario rides the custom op instead).
    "tensorflow",
    # torch/mxnet re-run here so the Handle.tensor_sizes plumbing (one
    # collective per autograd allgather; metric gather split) is covered on
    # BOTH data planes, not just the native engine's slot accessors.
    "torch", "mxnet",
])
def test_python_engine(scenario):
    # The Python controller (TCP star control plane) remains selectable via
    # HOROVOD_ENGINE=python; the default above exercises the native C++
    # engine (engine.cc) whenever ring addresses are exported.
    run_ranks(scenario, size=2, extra_env={"HOROVOD_ENGINE": "python"})


@pytest.mark.parametrize("engine", ["native", "python"])
def test_hierarchical_two_level(engine):
    # 4 ranks as 2 simulated nodes x 2 ranks via the launcher's -H grouping;
    # the reference's HOROVOD_HIERARCHICAL_* env vars flip on the two-level
    # data plane (local ring + cross ring of local roots) in both engines.
    env = _launcher_env(HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                        HOROVOD_HIERARCHICAL_ALLGATHER="1",
                        HOROVOD_ENGINE=engine)
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "4",
         "-H", "localhost:2,localhost:2",
         sys.executable, WORKER, "hierarchical"],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(4):
        assert f"worker rank={r} scenario=hierarchical: OK" in res.stdout


def test_timeline_names_shm_data_plane(tmp_path):
    """With the shm local plane active, timeline activities must say which
    plane moved the bytes (SHM_CROSS_RING_COLLECTIVE, docs/timeline.md)."""
    tl_file = tmp_path / "timeline.json"
    env = _launcher_env(HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                        HOROVOD_ENGINE="native",
                        HOROVOD_TIMELINE=str(tl_file))
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "4",
         "-H", "localhost:2,localhost:2",
         sys.executable, WORKER, "hierarchical"],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    content = tl_file.read_text()
    assert "SHM_CROSS_RING_COLLECTIVE" in content
    assert "NEGOTIATE_ALLREDUCE" in content


def test_shm_allgather_multipass_uneven_counts():
    """Per-rank blocks larger than a tiny 4 KiB shm slot force the
    chunked multi-pass allgather/allreduce paths with uneven counts."""
    env = _launcher_env(HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                        HOROVOD_HIERARCHICAL_ALLGATHER="1",
                        HOROVOD_ENGINE="native",
                        HOROVOD_SHM_SLOT_BYTES="4096")
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "4",
         "-H", "localhost:2,localhost:2",
         sys.executable, WORKER, "shmgather"],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(4):
        assert f"worker rank={r} scenario=shmgather: OK" in res.stdout


def _run_shmbench(shm_disable):
    env = _launcher_env(HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                        HOROVOD_ENGINE="native",
                        HOROVOD_SHM_DISABLE="1" if shm_disable else None)
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "4",
         "-H", "localhost:2,localhost:2",
         sys.executable, WORKER, "shmbench"],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    # Launcher output is rank-prefixed ("[2]: shmbench rank=2 rate=...").
    rates = [float(line.rsplit("rate=", 1)[1].replace("MB/s", ""))
             for line in res.stdout.splitlines()
             if "shmbench rank=" in line and "rate=" in line]
    assert len(rates) == 4, res.stdout
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
    env = _launcher_env(HOROVOD_AUTOTUNE="1", HOROVOD_ENGINE="python")
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "4",
         "-H", "localhost:2,localhost:2",
         sys.executable, WORKER, "autotune"],
        env=env, capture_output=True, text=True, timeout=360, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(4):
        assert f"worker rank={r} scenario=autotune: OK" in res.stdout


def test_hierarchical_flags_heterogeneous_layout_falls_back():
    # 3 ranks over localhost:2,localhost:2 gives groups of 2 and 1: the
    # launcher must NOT export group rings (mixed sizes would diverge the
    # per-rank path choice) and the job must still produce correct results
    # on the flat data plane.
    env = _launcher_env(HOROVOD_HIERARCHICAL_ALLREDUCE="1")
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "3",
         "-H", "localhost:2,localhost:2",
         sys.executable, WORKER, "allreduce"],
        env=env, capture_output=True, text=True, timeout=180, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(3):
        assert f"worker rank={r} scenario=allreduce: OK" in res.stdout


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
