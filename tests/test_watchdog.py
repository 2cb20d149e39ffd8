"""Parent-death watchdog (run/watchdog.py): an orphaned launcher-spawned
rank reaps itself (reference ``spark/task/mpirun_exec_fn.py:25-35``)."""

import os
import signal
import sys
import time

from mp_harness import child_env, run_cmd, spawn


_PARENT = r"""
import subprocess, sys, time
prctl_ok = sys.argv[1] == "prctl"
body = '''
import horovod_tpu.run.watchdog as w
if not %r:
    w._set_pdeathsig = lambda s: False  # poll-thread-only path
assert w.install(poll_interval=0.2, grace=1.0)
print("armed", flush=True)
import time
time.sleep(120)
''' % prctl_ok
# stderr/stdout piped to THIS (soon dead) parent: the watchdog's
# diagnostic write hits a broken pipe and must still reap the child.
child = subprocess.Popen([sys.executable, "-c", body],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
# The order of events under test: the watchdog is armed against THIS
# parent, and then the parent dies. A child still importing when its
# parent is killed arms against whoever adopted it and watches that
# (the flap of a loaded box: CHANGES.md, PR 39), so the pid goes out only
# once the child has said it is armed.
assert child.stdout.readline().strip() == b"armed"
print(child.pid, flush=True)
time.sleep(120)
"""


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False


import pytest


@pytest.mark.parametrize("layer", ["prctl", "poll"])
def test_orphaned_child_reaps_itself(layer):
    parent = spawn([sys.executable, "-c", _PARENT, layer], stderr=None)
    try:
        child_pid = int(parent.stdout.readline())
        assert _alive(child_pid)
        # SIGKILL: no cleanup chance — the exact orphaning the watchdog
        # exists for.
        parent.send_signal(signal.SIGKILL)
        parent.wait(timeout=60)
        # The child reaps itself within a poll or two (0.2 s) on an idle
        # box; the deadline is what a child that never does costs, and it
        # holds with every core of the box compiling beside the test.
        deadline = time.monotonic() + 60.0
        while _alive(child_pid):
            assert time.monotonic() < deadline, (
                "orphaned child still alive 60s after its parent died")
            time.sleep(0.2)
    finally:
        if parent.poll() is None:
            parent.kill()
        try:
            os.kill(child_pid, signal.SIGKILL)
        except (ProcessLookupError, UnboundLocalError):
            pass


def _probe(env_value):
    """maybe_install_from_env() in a throwaway interpreter (arming a
    watchdog inside the pytest process would watch pytest's own parent)."""
    env = child_env({"HOROVOD_PARENT_WATCHDOG": env_value})
    out = run_cmd(
        [sys.executable, "-c",
         "from horovod_tpu.run.watchdog import maybe_install_from_env;"
         "print(maybe_install_from_env())"],
        timeout=60, env=env)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_forked_child_rearms():
    """_installed is keyed on os.getpid(): after a fork the child inherits
    the flag but NOT the watchdog thread, so install() must re-arm there
    instead of refusing."""
    body = r"""
import os, sys, threading
import horovod_tpu.run.watchdog as w
assert w.install(poll_interval=5.0)
assert w.install()  # idempotent in the same process
pid = os.fork()
if pid == 0:  # child: no watchdog thread survived the fork
    alive = [t.name for t in threading.enumerate()]
    assert "hvd-parent-watchdog" not in alive, alive
    assert w.install(poll_interval=5.0), "child failed to re-arm"
    alive = [t.name for t in threading.enumerate()]
    assert "hvd-parent-watchdog" in alive, alive
    os._exit(0)
_, status = os.waitpid(pid, 0)
sys.exit(os.waitstatus_to_exitcode(status))
"""
    res = run_cmd([sys.executable, "-c", body], timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr


def test_env_gate():
    assert _probe(None) == "False"      # standalone runs are never watched
    assert _probe("0") == "False"       # explicit opt-out
    assert _probe("1") == "True"        # launcher-exported opt-in


def test_launcher_exports_watchdog_env():
    from horovod_tpu.run.launch import build_rank_env

    env = build_rank_env({}, rank=0, size=2, local_rank=0, local_size=2,
                         cross_rank=0, cross_size=1,
                         controller_addr="127.0.0.1:1", secret="ab",
                         bind_chips=False)
    assert env["HOROVOD_PARENT_WATCHDOG"] == "1"
    # User opt-out in the launcher environment is inherited, not clobbered.
    env = build_rank_env({"HOROVOD_PARENT_WATCHDOG": "0"}, rank=0, size=2,
                         local_rank=0, local_size=2, cross_rank=0,
                         cross_size=1, controller_addr="127.0.0.1:1",
                         secret="ab", bind_chips=False)
    assert env["HOROVOD_PARENT_WATCHDOG"] == "0"
