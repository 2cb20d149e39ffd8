"""Shared parent-side harness for every test that launches a process.

Two launchers, and no test file outside ``tests/benchmark/`` starts a
process any other way (``test_lint.py`` holds that):

* ``run_cmd`` runs one command to its end (a CLI, a ``python -c``);
  ``run_example`` and ``run_launcher`` are it for an example and for
  ``python -m horovod_tpu.run``; ``spawn`` + ``finish`` are its two
  halves for a test that needs the processes while they live;
* ``run_ranks`` runs N ranks of a ``tests/mp_worker.py`` scenario over
  the TCP star (``launch_rank`` for one rank of a live job);
  ``run_ring_ranks`` is it over the ring data plane (the native engine),
  ``run_script_ranks`` the same job shape for a test file's own
  ``__main__`` scenarios.

A launch's ``timeout`` is what a HANG costs, not a budget: about three
times what the launch takes under the suite's own load (six xdist workers
on eight cores), and never a literal above ``LAUNCH_LIMIT``. A launch
that needs longer is two tests or a ``slow`` one. The whole suite runs
under one ``timeout 1470``; a limit of 560 s is a third of it.

Every ``run_ranks`` job also runs under the wire-protocol conformance
monitor (``HOROVOD_PROTOCHECK=1``, analysis/protocol.py) and asserts
zero recorded violations at the end — so each chaos scenario (kill,
drop, delay, join, leave) doubles as a protocol conformance run for
free. Pass ``protocheck=False`` to opt a job out (e.g. a scenario that
deliberately sends off-spec frames).
"""

import itertools
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

LAUNCH_LIMIT = 180.0


def child_env(extra=None):
    """The environment of a child of the suite: this process's, with the
    repo importable and JAX held to the CPU. ``extra`` overrides; a value
    of ``None`` unsets."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    for key, value in sorted((extra or {}).items()):
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return env


def spawn(cmd, env=None, cwd=None, stderr=subprocess.STDOUT, **popen):
    """Start ``cmd`` with its output piped as text (stderr into stdout
    unless told otherwise); ``env`` defaults to ``child_env()``."""
    return subprocess.Popen(
        cmd, env=child_env() if env is None else env, cwd=cwd,
        stdout=subprocess.PIPE, stderr=stderr, text=True, **popen)


def finish(procs, timeout, what, allowed_exit=None):
    """Wait for every process of one job under ONE deadline and return
    each one's output. A process still running at the deadline kills the
    whole job and fails the test; one that exits outside its allowed codes
    (default: only 0; ``{rank: codes}`` to allow others, ``None`` as codes
    for any) fails with its output."""
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
                f"{what}: rank {rank} hung past {timeout:.0f} s")
        outputs.append(out)
    for rank, proc in enumerate(procs):
        ok = (allowed_exit or {}).get(rank, (0,))
        assert ok is None or proc.returncode in ok, (
            f"{what}: rank {rank} failed (exit {proc.returncode}, "
            f"allowed {ok}):\n{outputs[rank]}")
    return outputs


def run_cmd(cmd, timeout, env=None, cwd=None, **run):
    """Run ``cmd`` to its end with stdout and stderr captured apart;
    returns the ``CompletedProcess`` (the caller judges the exit code).
    ``env`` defaults to ``child_env()``."""
    return subprocess.run(
        cmd, env=child_env() if env is None else env, cwd=cwd,
        capture_output=True, text=True, timeout=timeout, **run)


def ring_env(size):
    """Fresh ring addresses for ``size`` ranks on this host: what puts a
    job on the ring data plane (and, with no engine named, on the native
    engine)."""
    return {"HOROVOD_RING_ADDRS": ",".join(
        f"127.0.0.1:{free_port()}" for _ in range(size))}


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


_PORT_STEP, _PORT_SLICE = 4, 1200
# Two pytest processes with the same worker name (two runs on one box)
# start at different places of the slice.
_next_port = itertools.count(os.getpid() * _PORT_STEP, _PORT_STEP)


def free_port():
    """A port (and the ``_PORT_STEP - 1`` after it, for a job that derives
    its ranks' ports from a base) that nothing else of the suite is
    handed: BELOW the kernel's ephemeral range, from a slice that is this
    xdist worker's own, probed by ``bind``. Bind-to-0-and-close hands out
    an ephemeral port that any connection of any of the six workers may
    take before the rank binds it ("Address already in use", and a ring
    joined by another job's rank: "left-neighbor authentication failed")."""
    worker = int((os.environ.get("PYTEST_XDIST_WORKER") or "gw0")[2:]) % 16
    for _ in range(_PORT_SLICE // _PORT_STEP):
        port = 10000 + worker * _PORT_SLICE + next(_next_port) % _PORT_SLICE
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
        return port
    raise RuntimeError("no free port in this worker's slice")


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
    """Spawn ONE mp_worker rank against an existing controller address,
    on the Python engine unless ``extra_env`` names another (``None``
    unsets, as in ``child_env``). Building block for run_ranks and for
    elastic tests that add late joiners to a live job."""
    env = {
        "HOROVOD_RANK": str(rank),
        "HOROVOD_SIZE": str(size),
        "HOROVOD_LOCAL_RANK": str(rank),
        "HOROVOD_LOCAL_SIZE": str(size),
        "HOROVOD_CONTROLLER_ADDR": addr,
        "HOROVOD_ENGINE": "python",
        "HOROVOD_CYCLE_TIME": "1",
    }
    env.update(extra_env or {})
    return spawn([sys.executable, WORKER, scenario], env=child_env(env))


def run_ranks(scenario, size=2, timeout=LAUNCH_LIMIT, extra_env=None,
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
        outputs = finish(procs, timeout, scenario, allowed_exit)
        if protocheck:
            # At least ONE rank must have dumped an artifact — a chaos
            # rank may die without atexit (SIGKILL, os._exit leave), but
            # an empty directory means the monitor wiring broke.
            assert_protocheck_clean(pc_dir, context=scenario, require=1)
        return outputs
    finally:
        if pc_dir is not None:
            shutil.rmtree(pc_dir, ignore_errors=True)


def run_ring_ranks(scenario, size=2, timeout=LAUNCH_LIMIT, extra_env=None,
                   per_rank_env=None):
    """``run_ranks`` over the ring data plane: ring addresses exported
    and no engine named, so the native C++ engine (engine.cc) runs unless
    ``extra_env`` names another. Not under the protocol monitor, which
    watches the Python controller."""
    env = {"HOROVOD_ENGINE": None, **ring_env(size)}
    env.update(extra_env or {})
    return run_ranks(scenario, size, timeout, extra_env=env,
                     per_rank_env=per_rank_env, protocheck=False)


def run_example(cmd, timeout=LAUNCH_LIMIT, extra_env=None,
                expect_failure=False):
    """Run an example's command from the repo's root and return its
    stdout; with ``expect_failure`` it must exit non-zero and its stderr
    comes back."""
    env = {"HOROVOD_CYCLE_TIME": "1", **(extra_env or {})}
    res = run_cmd(cmd, timeout, env=child_env(env), cwd=REPO)
    if expect_failure:
        assert res.returncode != 0, res.stdout + res.stderr
        return res.stderr
    assert res.returncode == 0, res.stdout + res.stderr
    return res.stdout


def run_launcher(args, timeout=LAUNCH_LIMIT, extra_env=None):
    """``python -m horovod_tpu.run <args>`` from the repo's root to its
    end; returns the ``CompletedProcess``."""
    env = {"HOROVOD_CYCLE_TIME": "1", **(extra_env or {})}
    return run_cmd([sys.executable, "-m", "horovod_tpu.run"] + list(args),
                   timeout, env=child_env(env), cwd=REPO)


def run_script_ranks(script, scenario, size, timeout=LAUNCH_LIMIT,
                     extra_env=None, per_rank_env=None):
    """``size`` ranks of a test file's own ``__main__`` scenarios over a
    real TCP ring (``script scenario rank size addrs``, the addresses in
    ``HOROVOD_RING_ADDRS`` too); returns the json each rank printed on its
    last ``RESULT`` line."""
    ring = ring_env(size)
    addrs = ring["HOROVOD_RING_ADDRS"]
    procs = []
    for rank in range(size):
        env = {**ring, "HOROVOD_CYCLE_TIME": "1", **(extra_env or {})}
        env.update((per_rank_env or {}).get(rank, {}))
        procs.append(spawn(
            [sys.executable, script, scenario, str(rank), str(size), addrs],
            env=child_env(env)))
    results = []
    for out in finish(procs, timeout, scenario):
        lines = [line for line in out.splitlines()
                 if line.startswith("RESULT ")]
        assert lines, f"{scenario}: no RESULT in:\n{out}"
        results.append(json.loads(lines[-1][len("RESULT "):]))
    return results
