"""Shared parent-side harness for the multi-process eager-tier tests.

One copy of the "spawn N ranks of tests/mp_worker.py and collect their
output" machinery (previously triplicated across test_metrics /
test_trace / test_doctor): a fix to the launch env or the hang handling
lands once, for every chaos/acceptance test.

Every ``run_ranks`` job also runs under the wire-protocol conformance
monitor (``HOROVOD_PROTOCHECK=1``, analysis/protocol.py) and asserts
zero recorded violations at the end — so each chaos scenario (kill,
drop, delay, join, leave) doubles as a protocol conformance run for
free. Pass ``protocheck=False`` to opt a job out (e.g. a scenario that
deliberately sends off-spec frames).
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "mp_worker.py")


def protocheck_env(out_dir):
    """Env additions that put a job under the conformance monitor, with
    per-rank artifacts in ``out_dir``."""
    return {"HOROVOD_PROTOCHECK": "1",
            "HOROVOD_PROTOCHECK_OUTPUT":
                os.path.join(out_dir, "protocheck.json")}


def assert_protocheck_clean(out_dir, context="", require=0):
    """Every protocheck artifact a monitored job left in ``out_dir``
    must record zero violations. Ranks that died without running atexit
    (SIGKILL, ``os._exit``) leave no artifact — that's expected; the
    survivors' clean reports are the assertion. ``require`` guards
    against the check going VACUOUS (artifacts silently not written
    would otherwise pass every scenario forever): callers that know at
    least N ranks exited normally pass that N."""
    paths = sorted(p for p in os.listdir(out_dir)
                   if p.startswith("protocheck.json"))
    checked = 0
    for name in paths:
        with open(os.path.join(out_dir, name), encoding="utf-8") as f:
            report = json.load(f)
        assert report.get("ok"), (
            f"{context}: protocol violations recorded in {name}: "
            f"{report.get('violations')}")
        checked += 1
    assert checked >= require, (
        f"{context}: expected >= {require} protocheck artifact(s) in "
        f"{out_dir}, found {checked} — the conformance monitor is not "
        "writing reports (check HOROVOD_PROTOCHECK wiring)")
    return checked


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def counter_by_label(snap, name):
    """First-label -> value view of one labeled counter in a metrics
    snapshot (hvd.metrics.snapshot() shape). Shared by the mp elastic
    acceptance tests and their in-process simcluster siblings — both
    assert on the same membership counters, one from a printed rank-0
    snapshot, the other from the harness's final snapshot."""
    entry = snap.get(name) or {}
    return {tuple(labels)[0] if labels else "": value
            for labels, value in entry.get("values", [])}


def launch_rank(scenario, rank, size, addr, extra_env=None):
    """Spawn ONE mp_worker rank against an existing controller address.
    Building block for run_ranks and for elastic tests that add late
    joiners to a live job."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.update({
        "HOROVOD_RANK": str(rank),
        "HOROVOD_SIZE": str(size),
        "HOROVOD_LOCAL_RANK": str(rank),
        "HOROVOD_LOCAL_SIZE": str(size),
        "HOROVOD_CONTROLLER_ADDR": addr,
        "HOROVOD_ENGINE": "python",
        "HOROVOD_CYCLE_TIME": "1",
    })
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, WORKER, scenario], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def run_ranks(scenario, size=2, timeout=120.0, extra_env=None,
              per_rank_env=None, allowed_exit=None, protocheck=True):
    """Run ``size`` ranks of the given mp_worker scenario to completion;
    returns each rank's combined stdout/stderr. Any rank hanging past
    ``timeout`` kills the whole job; a rank exiting outside its allowed
    codes (default: only 0; chaos tests allow e.g. ``{2: (-9,)}`` for a
    SIGKILLed rank) fails with that rank's output. Unless
    ``protocheck=False``, the job runs under the wire-protocol
    conformance monitor and zero violations are asserted."""
    addr = f"127.0.0.1:{free_port()}"
    pc_dir = tempfile.mkdtemp(prefix="hvd-protocheck-") if protocheck \
        else None
    try:
        procs = []
        for rank in range(size):
            env = dict(protocheck_env(pc_dir)) if protocheck else {}
            env.update(extra_env or {})
            env.update((per_rank_env or {}).get(rank, {}))
            procs.append(launch_rank(scenario, rank, size, addr,
                                     extra_env=env))
        deadline = time.monotonic() + timeout
        outputs = []
        for rank, proc in enumerate(procs):
            try:
                out, _ = proc.communicate(
                    timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                raise AssertionError(
                    f"{scenario}: rank {rank} hung past the timeout")
            outputs.append(out)
        for rank, proc in enumerate(procs):
            ok = (allowed_exit or {}).get(rank, (0,))
            assert proc.returncode in ok, (
                f"{scenario}: rank {rank} failed (exit {proc.returncode}, "
                f"allowed {ok}):\n{outputs[rank]}")
        if protocheck:
            # At least ONE rank must have dumped an artifact — a chaos
            # rank may die without atexit (SIGKILL, os._exit leave), but
            # an empty directory means the monitor wiring broke.
            assert_protocheck_clean(pc_dir, context=scenario, require=1)
        return outputs
    finally:
        if pc_dir is not None:
            shutil.rmtree(pc_dir, ignore_errors=True)

