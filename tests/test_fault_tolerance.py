"""Fault-tolerant control plane: deterministic FaultPlan injection, wire
liveness (heartbeats / deadlines / coordinated aborts), hello validation,
init retry hardening, and the stall warn→suppress→forced-shutdown path.

Multi-process chaos scenarios (kill a worker mid-allreduce, kill the
coordinator, drop a tick frame) live here too, driven by seeded
``HOROVOD_FAULT_PLAN`` rules so every failure is reproducible CPU-only;
the heavyweight end-to-end recipes are marked ``slow``.
"""

import json
import logging as pylogging
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

from horovod_tpu import fault
from horovod_tpu.fault.plan import FaultInjected, FaultPlan, InitWedged
from mp_harness import child_env, run_cmd, run_ranks

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    fault.reset()


# ---------------------------------------------------------------------------
# FaultPlan unit tests (deterministic, single process)


def test_plan_disabled_is_noop():
    fault.install_plan(None)
    assert fault.hook("wire_send") is None
    assert fault.active_plan() is None


def test_plan_counts_and_fires_at_nth_event():
    plan = FaultPlan.from_json(json.dumps({
        "seed": 7,
        "faults": [{"site": "wire_send", "action": "drop", "at": 3},
                   {"site": "cycle", "action": "raise", "at": 2,
                    "message": "boom at cycle 2"}],
    }), rank=0)
    assert plan.fire("wire_send") is None
    assert plan.fire("wire_send") is None
    assert plan.fire("wire_send") == "drop"
    assert plan.fire("wire_send") is None  # times=1: fires exactly once
    assert plan.fire("cycle") is None
    with pytest.raises(FaultInjected, match="boom at cycle 2"):
        plan.fire("cycle")
    assert plan.count("wire_send") == 4


def test_plan_rank_filtering():
    rules = json.dumps({"faults": [
        {"site": "cycle", "action": "raise", "at": 1, "rank": 1},
        {"site": "init", "action": "wedge", "times": 1},  # all ranks
    ]})
    plan0 = FaultPlan.from_json(rules, rank=0)
    assert plan0.fire("cycle") is None  # rank-1 rule filtered out
    with pytest.raises(InitWedged):
        plan0.fire("init")
    plan1 = FaultPlan.from_json(rules, rank=1)
    with pytest.raises(FaultInjected):
        plan1.fire("cycle")


def test_plan_wedge_recovers_after_times():
    plan = FaultPlan.from_json(
        '{"faults": [{"site": "init", "action": "wedge", "times": 2}]}')
    for _ in range(2):
        with pytest.raises(InitWedged, match="wedged"):
            plan.fire("init")
    assert plan.fire("init") is None  # healthy from attempt 3 on


def test_plan_seeded_delay_jitter_is_deterministic(monkeypatch):
    spec = json.dumps({"seed": 42, "faults": [
        {"site": "cycle", "action": "delay", "at": 1, "times": 3,
         "seconds": 0.01, "jitter": 0.5}]})

    def run_plan():
        slept = []
        from horovod_tpu.fault import plan as plan_mod

        monkeypatch.setattr(plan_mod.time, "sleep",
                            lambda s: slept.append(s))
        p = FaultPlan.from_json(spec)
        for _ in range(3):
            p.fire("cycle")
        return slept

    assert run_plan() == run_plan()  # same seed, same delays


def test_plan_env_loading_inline_and_file(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_FAULT_PLAN",
                       '{"faults": [{"site": "cycle", "action": "drop"}]}')
    # invalid: drop outside wire_send must fail loudly at load
    with pytest.raises(ValueError, match="drop"):
        FaultPlan.from_env()
    spec = {"faults": [{"site": "wire_send", "action": "drop", "at": 1}]}
    monkeypatch.setenv("HOROVOD_FAULT_PLAN", json.dumps(spec))
    plan = FaultPlan.from_env()
    assert plan.fire("wire_send") == "drop"
    path = tmp_path / "plan.json"
    path.write_text(json.dumps(spec))
    monkeypatch.setenv("HOROVOD_FAULT_PLAN", f"@{path}")
    assert FaultPlan.from_env().fire("wire_send") == "drop"
    monkeypatch.delenv("HOROVOD_FAULT_PLAN")
    assert FaultPlan.from_env() is None


def test_plan_rejects_unknown_site_and_action():
    with pytest.raises(ValueError, match="site"):
        FaultPlan.from_json(
            '{"faults": [{"site": "nope", "action": "kill", "at": 1}]}')
    with pytest.raises(ValueError, match="action"):
        FaultPlan.from_json(
            '{"faults": [{"site": "cycle", "action": "nope", "at": 1}]}')


def test_plan_rejects_rule_that_can_never_fire():
    # A non-wedge rule without "at" would silently inject nothing.
    with pytest.raises(ValueError, match='needs "at"'):
        FaultPlan.from_json(
            '{"faults": [{"site": "cycle", "action": "kill"}]}')
    # wedge legitimately omits it (always the first `times` attempts),
    # on either init site.
    FaultPlan.from_json(
        '{"faults": [{"site": "init", "action": "wedge", "times": 3}]}')
    FaultPlan.from_json('{"faults": [{"site": "init_distributed", '
                        '"action": "wedge", "times": 1}]}')


# ---------------------------------------------------------------------------
# Hello validation (CoordinatorService rendezvous hardening)


def test_coordinator_rejects_bad_hellos_and_still_completes():
    from horovod_tpu.common.wire import Wire
    from horovod_tpu.controller.service import CoordinatorService

    port = socket.socket()
    port.bind(("127.0.0.1", 0))
    addr = f"127.0.0.1:{port.getsockname()[1]}"
    port.close()

    svc_box = {}

    def serve():
        svc_box["svc"] = CoordinatorService(addr, size=3, accept_timeout=30)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    host, p = addr.split(":")

    def dial():
        for _ in range(100):
            try:
                return socket.create_connection((host, int(p)), timeout=2)
            except OSError:
                time.sleep(0.05)
        raise AssertionError("coordinator never came up")

    # 1. out-of-range rank id
    bad = Wire(dial())
    bad.send_obj({"rank": 7})
    # 2. rank 0 (the coordinator itself) is not a valid worker hello
    zero = Wire(dial())
    zero.send_obj({"rank": 0})
    # 3. garbage hello (not even a dict)
    garbage = Wire(dial())
    garbage.send_obj("not-a-hello")
    # 4. legit rank 1
    w1 = Wire(dial())
    w1.send_obj({"rank": 1})
    time.sleep(0.3)  # let the coordinator admit rank 1 first
    # 5. duplicate rank 1: rejected, original connection kept
    dup = Wire(dial())
    dup.send_obj({"rank": 1})
    # 6. legit rank 2 completes the rendezvous
    w2 = Wire(dial())
    w2.send_obj({"rank": 2})
    t.join(timeout=30)
    assert not t.is_alive(), "rendezvous did not complete"
    svc = svc_box["svc"]
    assert sorted(svc.wires) == [1, 2]
    # The kept rank-1 wire is the ORIGINAL one: a frame sent by the first
    # client arrives, proving the duplicate didn't overwrite it.
    w1.send_obj({"ping": 1})
    assert svc.recv_from(1) == {"ping": 1}
    for w in (bad, zero, garbage, dup, w1, w2):
        w.close()
    svc.close()


# ---------------------------------------------------------------------------
# Stall path: warn → repeat-warn suppression → forced shutdown


class _LogCapture(pylogging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)

    def messages(self):
        return [r.getMessage() for r in self.records]


@pytest.fixture
def hvd_log():
    from horovod_tpu.common import hvd_logging

    hvd_logging.configure("warning")
    cap = _LogCapture()
    hvd_logging._logger.addHandler(cap)
    yield cap
    hvd_logging._logger.removeHandler(cap)


def _bare_controller(size=4, stall_seconds=10.0, shutdown_seconds=0.0):
    """A Controller shell with just the stall-check state — no sockets, no
    thread; _check_stalls only touches these fields."""
    from horovod_tpu.common.config import Config
    from horovod_tpu.controller.controller import Controller

    ctl = Controller.__new__(Controller)
    ctl.cfg = Config(stall_check_seconds=stall_seconds,
                     stall_shutdown_seconds=shutdown_seconds)
    ctl.topo = type("T", (), {"size": size})()
    ctl._lock = threading.Lock()
    ctl._first_seen = {}
    ctl._message_table = {}
    ctl._stall_warned = {}
    ctl._shutdown_requested = False
    return ctl


def test_stall_warning_names_missing_ranks(hvd_log):
    ctl = _bare_controller(size=4, stall_seconds=10.0)
    t0 = 1000.0
    ctl._first_seen["grad.w"] = t0
    ctl._message_table["grad.w"] = {0: object(), 2: object()}
    ctl._check_stalls(t0 + 5.0)  # under threshold: silence
    assert not hvd_log.messages()
    ctl._check_stalls(t0 + 11.0)
    msgs = hvd_log.messages()
    assert len(msgs) == 1 and "grad.w" in msgs[0]
    assert "missing ranks: 1, 3" in msgs[0]


def test_stall_repeat_warning_suppressed_then_reissued(hvd_log):
    ctl = _bare_controller(size=2, stall_seconds=10.0)
    t0 = 2000.0
    ctl._first_seen["t"] = t0
    ctl._message_table["t"] = {0: object()}
    ctl._check_stalls(t0 + 11.0)
    ctl._check_stalls(t0 + 12.0)  # within the suppression window
    ctl._check_stalls(t0 + 15.0)
    assert len(hvd_log.messages()) == 1
    ctl._check_stalls(t0 + 22.5)  # window elapsed: warn again
    assert len(hvd_log.messages()) == 2
    assert not ctl._shutdown_requested  # no shutdown time configured


def test_stall_forced_shutdown_after_deadline(hvd_log):
    ctl = _bare_controller(size=2, stall_seconds=1.0, shutdown_seconds=30.0)
    t0 = 3000.0
    ctl._first_seen["t"] = t0
    ctl._message_table["t"] = {0: object()}
    ctl._check_stalls(t0 + 2.0)
    assert not ctl._shutdown_requested
    ctl._check_stalls(t0 + 31.0)
    assert ctl._shutdown_requested
    assert any("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS" in m
               for m in hvd_log.messages())


def test_stall_check_disabled(hvd_log):
    ctl = _bare_controller(size=2, stall_seconds=1.0)
    ctl.cfg = type(ctl.cfg)(stall_check_disable=True,
                            stall_check_seconds=1.0)
    ctl._first_seen["t"] = 0.0
    ctl._message_table["t"] = {0: object()}
    ctl._check_stalls(1e9)
    assert not hvd_log.messages()


# ---------------------------------------------------------------------------
# Unified HOROVOD_START_TIMEOUT parser + liveness knobs


def test_start_timeout_one_parser_for_all_consumers(monkeypatch):
    from horovod_tpu.common.config import start_timeout_seconds

    monkeypatch.delenv("HOROVOD_START_TIMEOUT", raising=False)
    assert start_timeout_seconds() == 120.0
    monkeypatch.setenv("HOROVOD_START_TIMEOUT", "60.5")
    assert start_timeout_seconds() == 60.5
    for garbage in ("soon", "", "0", "-3", "nan"):
        monkeypatch.setenv("HOROVOD_START_TIMEOUT", garbage)
        assert start_timeout_seconds() == 120.0, garbage


def test_heartbeats_default_off_when_deadline_disabled(monkeypatch):
    from horovod_tpu.common.config import (comm_timeout_seconds,
                                           heartbeat_interval_seconds)

    monkeypatch.setenv("HOROVOD_COMM_TIMEOUT_SECONDS", "0")
    monkeypatch.delenv("HOROVOD_HEARTBEAT_INTERVAL_SECONDS", raising=False)
    assert comm_timeout_seconds() == 0.0
    assert heartbeat_interval_seconds() == 0.0  # nothing would consume them
    monkeypatch.setenv("HOROVOD_HEARTBEAT_INTERVAL_SECONDS", "3")
    assert heartbeat_interval_seconds() == 3.0  # explicit override wins
    monkeypatch.setenv("HOROVOD_COMM_TIMEOUT_SECONDS", "40")
    monkeypatch.delenv("HOROVOD_HEARTBEAT_INTERVAL_SECONDS", raising=False)
    assert heartbeat_interval_seconds() == 10.0  # min(10, 40/4)


# ---------------------------------------------------------------------------
# Retry / init hardening


def test_retry_call_succeeds_after_transient_failures():
    from horovod_tpu.common import retry

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    slept = []
    assert retry.retry_call(flaky, attempts=4, backoff=1.0, jitter=0.0,
                            sleep=slept.append) == "ok"
    assert len(calls) == 3
    assert slept == [1.0, 2.0]  # exponential, no jitter


def test_retry_call_exhausts_and_chains_last_error():
    from horovod_tpu.common import retry

    with pytest.raises(retry.RetryError, match="after 2 attempt"):
        retry.retry_call(lambda: (_ for _ in ()).throw(ValueError("nope")),
                         attempts=2, backoff=0.0, sleep=lambda s: None)


def test_retry_jitter_deterministic_per_seed():
    from horovod_tpu.common import retry

    def delays(seed):
        out = []
        with pytest.raises(retry.RetryError):
            retry.retry_call(lambda: 1 / 0, attempts=4, backoff=1.0,
                             jitter=0.5, seed=seed, sleep=out.append,
                             retry_on=(ZeroDivisionError,))
        return out

    assert delays(3) == delays(3)
    assert delays(3) != delays(4)


def test_run_with_deadline():
    from horovod_tpu.common import retry

    assert retry.run_with_deadline(lambda: 42, 5.0) == 42
    with pytest.raises(retry.DeadlineExceeded, match="within 0.2"):
        retry.run_with_deadline(lambda: time.sleep(10), 0.2, "wedge probe")
    with pytest.raises(ValueError, match="inner"):
        retry.run_with_deadline(
            lambda: (_ for _ in ()).throw(ValueError("inner")), 5.0)


def _init_subprocess(extra_env, code=None):
    env = child_env()
    env.update(extra_env)
    code = code or ("import horovod_tpu as hvd; hvd.init(); "
                    "print('init-ok', hvd.size())")
    return run_cmd([sys.executable, "-c", code], timeout=180, env=env)


def test_wedged_init_recovers_within_retry_budget():
    """Acceptance: an init wedged K=2 times (seeded fault injection)
    succeeds on attempt 3 under HOROVOD_TPU_INIT_RETRIES=3."""
    res = _init_subprocess({
        "HOROVOD_FAULT_PLAN": json.dumps(
            {"faults": [{"site": "init", "action": "wedge", "times": 2}]}),
        "HOROVOD_TPU_INIT_RETRIES": "3",
        "HOROVOD_TPU_INIT_BACKOFF": "0.05",
    })
    assert res.returncode == 0, res.stdout + res.stderr
    assert "init-ok" in res.stdout
    assert res.stderr.count("retrying") == 2, res.stderr


def test_wedged_init_exhausted_budget_fails_loudly():
    res = _init_subprocess({
        "HOROVOD_FAULT_PLAN": json.dumps(
            {"faults": [{"site": "init", "action": "wedge", "times": 9}]}),
        "HOROVOD_TPU_INIT_RETRIES": "2",
        "HOROVOD_TPU_INIT_BACKOFF": "0.05",
    })
    assert res.returncode != 0
    assert "failed after 2 attempt" in res.stderr


# ---------------------------------------------------------------------------
# Multi-process chaos: injected deaths over the TCP star (python engine)


def _run_chaos(scenario, plan, size=2, timeout=90.0, extra_env=None,
               expect_killed=()):
    """``mp_harness.run_ranks`` with a shared seeded fault plan; returns
    the ranks' outputs. Every chaos run
    also runs under the wire-protocol conformance monitor
    (HOROVOD_PROTOCHECK=1) and asserts zero recorded violations — the
    kill/drop chaos suite doubles as a conformance suite."""
    env = {
        # The fault hooks live in the python controller's star control
        # plane (run_ranks' engine).
        "HOROVOD_FAULT_PLAN": json.dumps(plan),
        "HOROVOD_STALL_CHECK_TIME_SECONDS": "5",
    }
    env.update(extra_env or {})
    return run_ranks(scenario, size, timeout, extra_env=env,
                     allowed_exit={rank: (-9,) for rank in expect_killed})


def test_worker_death_mid_allreduce_aborts_survivors_descriptively():
    """Acceptance: kill one worker mid-job (seeded, at cycle 300) — every
    surviving rank raises a descriptive abort naming the dead rank within
    the comm timeout, never hangs."""
    t0 = time.monotonic()
    outs = _run_chaos(
        "fault_survivor",
        {"seed": 1, "faults": [
            {"site": "cycle", "action": "kill", "at": 300, "rank": 1}]},
        extra_env={"HOROVOD_COMM_TIMEOUT_SECONDS": "10"},
        expect_killed=(1,))
    assert "fault error surfaced" in outs[0], outs[0]
    assert "rank 1 died or became unreachable" in outs[0], outs[0]
    # Bounded: well within the 10s comm timeout (+ process startup slack).
    assert time.monotonic() - t0 < 60.0


def test_coordinator_death_aborts_workers_descriptively():
    outs = _run_chaos(
        "fault_survivor",
        {"seed": 2, "faults": [
            {"site": "cycle", "action": "kill", "at": 300, "rank": 0}]},
        extra_env={"HOROVOD_COMM_TIMEOUT_SECONDS": "10"},
        expect_killed=(0,))
    assert "fault error surfaced" in outs[1], outs[1]
    assert "lost contact with the coordinator" in outs[1], outs[1]


@pytest.mark.slow  # tier-1 sibling: test_simcluster.py::test_sim_dropped_tick_trips_deadline_and_aborts
def test_dropped_tick_trips_deadline_and_coordinated_abort():
    """A dropped (not closed — the socket stays open) frame is invisible
    until the per-recv deadline fires: with heartbeats off, the coordinator
    must diagnose the silent rank within HOROVOD_COMM_TIMEOUT_SECONDS and
    broadcast the abort."""
    t0 = time.monotonic()
    outs = _run_chaos(
        "fault_survivor",
        {"seed": 3, "faults": [
            # Drop every control/data frame rank 1 sends from event 200 on:
            # rank 1 goes silent without dying.
            {"site": "wire_send", "action": "drop", "at": 200,
             "times": 1000000, "rank": 1}]},
        extra_env={"HOROVOD_COMM_TIMEOUT_SECONDS": "3",
                   "HOROVOD_HEARTBEAT_INTERVAL_SECONDS": "0"},
        timeout=120.0)
    assert "fault error surfaced" in outs[0], outs[0]
    assert "rank 1 died or became unreachable" in outs[0], outs[0]
    assert "no frame within 3.0s" in outs[0], outs[0]
    # Rank 1 is still alive: it must be failed too — by the coordinator's
    # abort broadcast or its own deadline — with a descriptive error.
    assert "fault error surfaced" in outs[1], outs[1]
    assert time.monotonic() - t0 < 90.0


def test_no_fault_run_is_byte_identical_with_plan_machinery_loaded():
    """Acceptance: with injection disabled (empty plan), results are
    byte-identical to the plain path and nothing fires."""
    import horovod_tpu as hvd

    fault.install_plan(FaultPlan.from_json('{"faults": []}'))
    hvd.init()
    x = (np.arange(64, dtype=np.float32) * 3.25 + 1.5)
    out = np.asarray(hvd.allreduce(x, average=False, name="nofault.t"))
    assert out.tobytes() == x.tobytes()  # size-1 sum: exact bytes
    hvd.shutdown()


@pytest.mark.slow
def test_wedged_init_then_supervised_restart_end_to_end(tmp_path):
    """Chaos recipe: attempt 0 wedges init beyond its retry budget and the
    job fails; horovodrun --max-restarts relaunches with
    HOROVOD_RESTART_EPOCH=1, the (epoch-gated) plan no longer wedges, and
    the job completes — the full detect→supervise→recover loop."""
    script = (
        "import os, horovod_tpu as hvd\n"
        "if os.environ.get('HOROVOD_RESTART_EPOCH') == '0':\n"
        "    pass  # plan wedges init on this attempt\n"
        "hvd.init()\n"
        "print('epoch', os.environ['HOROVOD_RESTART_EPOCH'], 'up')\n"
        "hvd.shutdown()\n")
    path = tmp_path / "train.py"
    path.write_text(script)
    env = child_env()
    # The wedge applies only while HOROVOD_RESTART_EPOCH=0 via a wrapper
    # that injects the plan conditionally.
    wrapper = tmp_path / "wrapped.py"
    wrapper.write_text(
        "import json, os, runpy, sys\n"
        "if os.environ.get('HOROVOD_RESTART_EPOCH') == '0':\n"
        "    os.environ['HOROVOD_FAULT_PLAN'] = json.dumps({'faults': [\n"
        "        {'site': 'init', 'action': 'wedge', 'times': 9}]})\n"
        f"sys.argv = [{str(path)!r}]\n"
        f"runpy.run_path({str(path)!r}, run_name='__main__')\n")
    env["HOROVOD_TPU_INIT_RETRIES"] = "2"
    env["HOROVOD_TPU_INIT_BACKOFF"] = "0.05"
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "1",
         "--max-restarts", "2", "--restart-backoff", "0.1",
         sys.executable, str(wrapper)],
        timeout=180, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "epoch 1 up" in res.stdout
    assert "restarting (attempt 1/2)" in res.stderr
