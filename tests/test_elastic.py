"""Elastic membership (ISSUE 7, docs/elastic.md): wire JOIN/RESHAPE frame
units, FaultPlan join/leave kinds, torn-checkpoint atomicity, the
membership_churn doctor rule, launcher flags, and the 3-rank mp
acceptance matrix — kill-shrink, graceful leave, late join, and a
kill+join storm with bit-identical state across the re-formed world.
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import pytest

from mp_harness import (
    assert_protocheck_clean,
    child_env,
    counter_by_label,
    finish,
    free_port,
    launch_rank,
    protocheck_env,
    run_cmd,
    run_ranks,
)

import horovod_tpu.fault.plan as plan_mod
from horovod_tpu.common.wire import (
    FRAME_DATA,
    FRAME_JOIN,
    AuthError,
    RanksChangedError,
    Wire,
)
from horovod_tpu.doctor import Evidence, diagnose
from horovod_tpu.fault import FaultPlan, FaultRule
from horovod_tpu.metrics import MetricsRegistry
from horovod_tpu.utils.checkpoint import _write_atomically, latest_checkpoint


SECRET = b"x" * 32


def _wire_pair():
    a, b = socket.socketpair()
    return Wire(a, secret=SECRET), Wire(b, secret=SECRET)


# ---------------------------------------------------------------------------
# Wire: JOIN/RESHAPE frame kinds


def test_reshape_frame_raises_ranks_changed_with_assignment():
    a, b = _wire_pair()
    a.send_reshape(rank=1, size=2, epoch=5)
    with pytest.raises(RanksChangedError) as exc_info:
        b.recv_obj()
    exc = exc_info.value
    assert (exc.rank, exc.size, exc.epoch) == (1, 2, 5)
    a.close(), b.close()


def test_join_hello_roundtrip_via_recv_hello():
    a, b = _wire_pair()
    a.send_join({"join": True, "rank": 7})
    kind, hello = b.recv_hello()
    assert kind == FRAME_JOIN
    assert hello == {"join": True, "rank": 7}
    # A rendezvous (DATA) hello comes back with its own kind.
    a.send_obj({"rank": 3})
    kind, hello = b.recv_hello()
    assert kind == FRAME_DATA and hello == {"rank": 3}
    a.close(), b.close()


def test_reshape_ack_drain_discards_dead_epoch_traffic():
    a, b = _wire_pair()
    # The dead epoch's in-flight tick + tensor bytes, a stale ack from a
    # superseded reshape attempt, then the real acknowledgement.
    a.send_obj({"rank": 1, "requests": "stale-tick"})
    a.send_bytes(b"\x00" * 128)
    a.send_join({"ack": 3})
    a.send_join({"ack": 4})
    b.recv_reshape_ack(4)  # returns only at the matching ack
    # The stream is clean afterwards: next frame is the new epoch's.
    a.send_obj({"fresh": True})
    assert b.recv_obj() == {"fresh": True}
    a.close(), b.close()


def test_unexpected_join_frame_in_data_stream_is_auth_error():
    a, b = _wire_pair()
    a.send_join({"join": True})
    with pytest.raises(AuthError, match="join frame"):
        b.recv_bytes()
    a.close(), b.close()


# ---------------------------------------------------------------------------
# CoordinatorService: reform handshake edges (bare service, socketpair wires)


def _bare_service(wires=None, pending=None):
    from horovod_tpu.analysis.lockorder import make_lock
    from horovod_tpu.controller.service import CoordinatorService

    svc = CoordinatorService.__new__(CoordinatorService)
    svc.epoch = 1
    svc._wires_lock = make_lock("test.service.wires")
    svc.wires = dict(wires or {})
    svc._pending_joins = list(pending or [])
    svc._comm_timeout = 0
    svc._join_stop = None
    svc._join_thread = None
    return svc


def test_heartbeats_reach_parked_joiners():
    # A joiner parked behind --max-ranks blocks in await_assignment with
    # its recv deadline armed; without heartbeats it would time itself
    # out and die long before a slot frees.
    w1a, w1b = _wire_pair()
    wja, wjb = _wire_pair()
    svc = _bare_service(wires={1: w1a}, pending=[(wja, {"join": True})])
    assert svc._hb_wires() == [w1a, wja]
    for w in (w1a, w1b, wja, wjb):
        w.close()


def test_reform_below_min_ranks_reparks_absorbed_joiners():
    # "Membership untouched" on the None return includes joiners already
    # popped off the parked list: they go back (close() owns them again)
    # instead of leaking as wires nobody will ever read.
    wja, wjb = _wire_pair()
    svc = _bare_service(pending=[(wja, {"join": True})])
    assert svc.reform(dead=set(), min_ranks=3) is None
    assert svc.epoch == 1  # no epoch burned on an abandoned attempt
    assert [wire for wire, _ in svc._pending_joins] == [wja]
    wja.close(), wjb.close()


def test_reform_admits_parked_joiner_with_ack_handshake():
    import threading

    wja, wjb = _wire_pair()
    svc = _bare_service(pending=[(wja, {"join": True})])

    def joiner():
        with pytest.raises(RanksChangedError) as exc_info:
            wjb.recv_obj()
        exc = exc_info.value
        assert (exc.rank, exc.size, exc.epoch) == (1, 2, 2)
        wjb.send_join({"ack": exc.epoch})

    t = threading.Thread(target=joiner, name="test-joiner", daemon=True)
    t.start()
    res = svc.reform(dead=set(), min_ranks=1)
    t.join(timeout=10)
    assert (res.epoch, res.size, res.lost, res.joined) == (2, 2, (), 1)
    assert list(svc.wires) == [1] and svc.wires[1] is wja
    assert not svc._pending_joins
    wja.close(), wjb.close()


# ---------------------------------------------------------------------------
# FaultPlan: join/leave membership kinds


def test_membership_actions_only_at_cycle_site():
    for action in ("join", "leave"):
        FaultRule(site="cycle", action=action, at=10)  # valid
        with pytest.raises(ValueError, match="cycle"):
            FaultRule(site="wire_send", action=action, at=10)


def test_leave_rule_retires_gracefully(monkeypatch):
    calls = []
    monkeypatch.setattr(plan_mod, "_graceful_leave",
                        lambda: calls.append("leave"))
    plan = FaultPlan.from_json(
        '{"faults": [{"site": "cycle", "action": "leave", "at": 3}]}')
    for _ in range(2):
        assert plan.fire("cycle") is None
    assert not calls
    plan.fire("cycle")
    assert calls == ["leave"]
    plan.fire("cycle")  # at=3, times=1: fires exactly once
    assert calls == ["leave"]


def test_join_rule_spawns_one_clone(monkeypatch):
    calls = []
    monkeypatch.setattr(plan_mod, "_spawn_joiner",
                        lambda: calls.append("join"))
    plan = FaultPlan.from_json(
        '{"faults": [{"site": "cycle", "action": "join", "at": 2, '
        '"rank": 1}]}', rank=1)
    plan.fire("cycle")
    plan.fire("cycle")
    assert calls == ["join"]
    # Rank-scoped: the same plan in another rank's process never fires.
    other = FaultPlan.from_json(
        '{"faults": [{"site": "cycle", "action": "join", "at": 2, '
        '"rank": 1}]}', rank=2)
    other.fire("cycle")
    other.fire("cycle")
    assert calls == ["join"]


def test_spawn_joiner_scrubs_plan_and_sets_join_env(monkeypatch):
    captured = {}

    def fake_popen(cmd, env=None, **kwargs):
        captured.update(cmd=cmd, env=env)

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    monkeypatch.setenv("HOROVOD_FAULT_PLAN", "[]")
    plan_mod._spawn_joiner()
    assert captured["cmd"] == [sys.executable] + sys.argv
    assert captured["env"]["HOROVOD_ELASTIC_JOIN"] == "1"
    assert "HOROVOD_FAULT_PLAN" not in captured["env"]


# ---------------------------------------------------------------------------
# Torn-checkpoint atomicity


def _fake_save(marker):
    def write(path):
        os.makedirs(path)
        with open(os.path.join(path, "data"), "w") as f:
            f.write(marker)
    return write


def test_atomic_write_lands_whole_and_leaves_no_tmp(tmp_path):
    target = str(tmp_path / "ckpt_5")
    _write_atomically(target, _fake_save("v1"))
    assert open(os.path.join(target, "data")).read() == "v1"
    assert os.listdir(tmp_path) == ["ckpt_5"]
    # Overwrite in place (force default): old content fully replaced.
    _write_atomically(target, _fake_save("v2"))
    assert open(os.path.join(target, "data")).read() == "v2"
    assert os.listdir(tmp_path) == ["ckpt_5"]
    with pytest.raises(FileExistsError):
        _write_atomically(target, _fake_save("v3"), force=False)
    assert open(os.path.join(target, "data")).read() == "v2"


def test_interrupted_save_leaves_previous_checkpoint_loadable(tmp_path):
    target = str(tmp_path / "ckpt_5")
    _write_atomically(target, _fake_save("good"))

    def torn(path):
        os.makedirs(path)
        raise KeyboardInterrupt("rank killed mid-save")

    with pytest.raises(KeyboardInterrupt):
        _write_atomically(target, torn)
    # The complete checkpoint survives; the torn attempt is a .tmp.
    # orphan the resume path ignores.
    assert open(os.path.join(target, "data")).read() == "good"
    assert latest_checkpoint(str(tmp_path)) == target


def test_latest_checkpoint_skips_incomplete_entries(tmp_path):
    for name in ("ckpt_3", "ckpt_10"):
        _write_atomically(str(tmp_path / name), _fake_save(name))
    # Torn-save leftovers in both transient shapes, with steps that would
    # otherwise win.
    os.makedirs(tmp_path / "ckpt_99.tmp.1234")
    os.makedirs(tmp_path / "ckpt_99.tmp.1234.old")
    os.makedirs(tmp_path / "ckpt_junk")  # unparseable step: also skipped
    assert latest_checkpoint(str(tmp_path)) == str(tmp_path / "ckpt_10")


def test_stale_tmp_orphans_of_other_pids_are_swept(tmp_path):
    # Elastic respawns give every writer a fresh pid: orphans of EARLIER
    # crashed attempts must be swept by the next save, or periodic
    # preemption mid-save grows the directory without bound.
    target = str(tmp_path / "ckpt_5")
    os.makedirs(f"{target}.tmp.99999")  # crashed attempt, foreign pid
    _write_atomically(target, _fake_save("fresh"))
    assert sorted(os.listdir(tmp_path)) == ["ckpt_5"]
    assert open(os.path.join(target, "data")).read() == "fresh"


def test_kill_between_overwrite_renames_resumes_from_prev(tmp_path):
    # The overwrite swing is two renames (directories cannot be
    # os.replace'd); a kill exactly between them leaves <path>.prev (the
    # complete previous save) and a .tmp. orphan — the resume path must
    # fall back to .prev, and a whole primary must win over its own
    # .prev leftover.
    _fake_save("old")(str(tmp_path / "ckpt_5.prev"))
    os.makedirs(tmp_path / "ckpt_5.tmp.1234")
    assert latest_checkpoint(str(tmp_path)) == str(tmp_path / "ckpt_5.prev")
    _fake_save("whole")(str(tmp_path / "ckpt_5"))
    assert latest_checkpoint(str(tmp_path)) == str(tmp_path / "ckpt_5")


# ---------------------------------------------------------------------------
# Doctor: membership_churn rule


def _membership_snapshot(transitions, departures=None, epoch=None):
    r = MetricsRegistry()
    t = r.counter("hvd_membership_transitions_total", "", ("kind",))
    for kind, n in transitions.items():
        t.labels(kind).inc(n)
    if departures:
        d = r.counter("hvd_membership_rank_departures_total", "", ("rank",))
        for rank, n in departures.items():
            d.labels(str(rank)).inc(n)
    if epoch is not None:
        r.gauge("hvd_membership_epoch", "").set(epoch)
    return r.snapshot()


def _churn_findings(snap):
    return [f for f in diagnose(Evidence(snapshots={0: snap}))
            if f.rule == "membership_churn"]


def test_membership_churn_quiet_below_threshold():
    snap = _membership_snapshot({"shrink": 1, "grow": 1})
    assert not _churn_findings(snap)


def test_membership_churn_warns_and_names_flapping_rank():
    snap = _membership_snapshot({"shrink": 3, "grow": 2},
                                departures={2: 3, 1: 1}, epoch=6)
    [finding] = _churn_findings(snap)
    assert finding.severity == "warning"
    assert finding.rank == 2
    assert "rank 2" in finding.hint
    assert finding.evidence["transitions"] == 5
    assert finding.evidence["membership_epoch"] == 6


def test_membership_churn_critical_on_heavy_churn():
    snap = _membership_snapshot({"shrink": 7, "grow": 6},
                                departures={1: 7})
    [finding] = _churn_findings(snap)
    assert finding.severity == "critical"


# ---------------------------------------------------------------------------
# Config knobs + launcher flags


def test_elastic_config_defaults_and_garbage(monkeypatch):
    from horovod_tpu.common import config

    for var in ("HOROVOD_ELASTIC", "HOROVOD_ELASTIC_JOIN",
                "HOROVOD_ELASTIC_MIN_RANKS", "HOROVOD_ELASTIC_MAX_RANKS"):
        monkeypatch.delenv(var, raising=False)
    assert not config.elastic_enabled()
    assert not config.elastic_join()
    assert config.elastic_min_ranks() == 1
    assert config.elastic_max_ranks() == 0
    monkeypatch.setenv("HOROVOD_ELASTIC", "1")
    monkeypatch.setenv("HOROVOD_ELASTIC_MIN_RANKS", "garbage")
    monkeypatch.setenv("HOROVOD_ELASTIC_MAX_RANKS", "-5")
    assert config.elastic_enabled()
    assert config.elastic_min_ranks() == 1  # garbage -> default
    assert config.elastic_max_ranks() == 0  # negative -> unbounded


def test_build_rank_env_elastic_exports_and_ring_scrub():
    from horovod_tpu.run.launch import build_rank_env

    base = {"HOROVOD_RING_ADDRS": "stale:1", "HOROVOD_ELASTIC_JOIN": "1"}
    env = build_rank_env(base, rank=1, size=3, local_rank=1, local_size=3,
                         cross_rank=0, cross_size=1,
                         controller_addr="127.0.0.1:1", secret="ab",
                         bind_chips=False, elastic=True, min_ranks=2,
                         max_ranks=4)
    assert env["HOROVOD_ELASTIC"] == "1"
    assert env["HOROVOD_ELASTIC_MIN_RANKS"] == "2"
    assert env["HOROVOD_ELASTIC_MAX_RANKS"] == "4"
    assert env["HOROVOD_ENGINE"] == "python"
    assert "HOROVOD_RING_ADDRS" not in env
    # Not a joiner: the inherited join flag must not leak into a fresh rank.
    assert "HOROVOD_ELASTIC_JOIN" not in env
    joiner = build_rank_env({}, rank=1, size=3, local_rank=1, local_size=3,
                            cross_rank=0, cross_size=1,
                            controller_addr="127.0.0.1:1", secret="ab",
                            bind_chips=False, elastic=True,
                            elastic_join=True)
    assert joiner["HOROVOD_ELASTIC_JOIN"] == "1"
    # Non-elastic env is unchanged (byte-identical static behavior).
    static = build_rank_env({"HOROVOD_ELASTIC": "1"}, rank=0, size=2,
                            local_rank=0, local_size=2, cross_rank=0,
                            cross_size=1, controller_addr="127.0.0.1:1",
                            secret="ab", bind_chips=False)
    assert "HOROVOD_ELASTIC" not in static


def test_launcher_rejects_spmd_elastic_and_bad_min_ranks():
    from horovod_tpu.run.launch import main

    with pytest.raises(SystemExit):
        main(["-np", "2", "--spmd", "--elastic", "true"])
    with pytest.raises(SystemExit):
        main(["-np", "2", "--elastic", "--min-ranks", "5", "true"])


# ---------------------------------------------------------------------------
# hvd.elastic.State semantics (single-process, subprocess for isolation)


def test_elastic_state_commit_restore_semantics():
    code = """
import numpy as np
import horovod_tpu as hvd
hvd.init()
state = hvd.elastic.State(step=3, weights=np.arange(4.0))
assert state.step == 3
state.step = 10
state.weights = state.weights + 1
state.restore()   # rolls back to the last commit (construction time)
assert state.step == 3, state.step
assert np.array_equal(state.weights, np.arange(4.0)), state.weights
state.step = 10
state.commit()
state.step = 99
state.restore()
assert state.step == 10, state.step
assert hvd.elastic.epoch() == 1
try:
    hvd.elastic.State()
except ValueError:
    pass
else:
    raise AssertionError("empty State() must be rejected")
print("STATE_OK")
"""
    env = child_env()
    res = run_cmd([sys.executable, "-c", code], timeout=120, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "STATE_OK" in res.stdout


# ---------------------------------------------------------------------------
# mp acceptance: the reshape path end to end


def _rank0_snapshot(outputs):
    lines = [line for line in outputs[0].splitlines()
             if line.startswith("METRICS_SNAPSHOT ")]
    assert lines, f"rank 0 printed no snapshot:\n{outputs[0]}"
    return json.loads(lines[-1].split(" ", 1)[1])


_counter_by_label = counter_by_label  # shared helper (mp_harness)


def _elastic_env():
    return {"HOROVOD_ELASTIC": "1", "HOROVOD_METRICS": "1"}


def test_elastic_shrink_survives_killed_rank():
    """ISSUE 7 acceptance: a seeded FaultPlan SIGKILL of rank 2 in a
    3-rank elastic job produces no job-level failure — the survivors
    re-form at membership epoch 2 / size 2, the shrink transition and
    departure counters increment, and further allreduces stay
    consistent."""
    plan = json.dumps({"faults": [
        {"site": "cycle", "action": "kill", "at": 30, "rank": 2}]})
    outputs = run_ranks(
        "elastic_shrink", size=3, timeout=120.0,
        extra_env=_elastic_env(),
        per_rank_env={2: {"HOROVOD_FAULT_PLAN": plan}},
        allowed_exit={2: (-9,)})
    for rank in (0, 1):
        assert "ELASTIC size=2 epoch=2" in outputs[rank], outputs[rank]
    snap = _rank0_snapshot(outputs)
    transitions = _counter_by_label(snap,
                                    "hvd_membership_transitions_total")
    assert transitions.get("shrink", 0) >= 1, transitions
    departures = _counter_by_label(snap,
                                   "hvd_membership_rank_departures_total")
    assert departures.get("2", 0) >= 1, departures
    epoch_entry = snap.get("hvd_membership_epoch") or {}
    assert epoch_entry.get("values") and \
        epoch_entry["values"][0][1] == 2.0, epoch_entry


def test_elastic_graceful_leave_shrinks_cleanly():
    """FaultPlan "leave": rank 2 retires with exit code 0 at cycle 30;
    the survivors re-form exactly as for a crash, and no process reports
    failure."""
    plan = json.dumps({"faults": [
        {"site": "cycle", "action": "leave", "at": 30, "rank": 2}]})
    outputs = run_ranks(
        "elastic_shrink", size=3, timeout=120.0,
        extra_env=_elastic_env(),
        per_rank_env={2: {"HOROVOD_FAULT_PLAN": plan}})
    for rank in (0, 1):
        assert "ELASTIC size=2 epoch=2" in outputs[rank], outputs[rank]


@pytest.mark.slow  # tier-1 sibling: test_simcluster.py::test_sim_kill_shrink_then_join_regrow
def test_elastic_join_admits_third_rank():
    """A 2-rank elastic job absorbs a late joiner: the joiner's JOIN
    hello is parked, admitted at the next epoch boundary, state syncs
    from rank 0, and all three members settle into lockstep."""
    addr = f"127.0.0.1:{free_port()}"
    base = _elastic_env()
    # The join handshake (JOIN hello -> parked -> admission RESHAPE ->
    # ack) runs under the conformance monitor: the grow path must be
    # violation-free end to end, joiner included.
    with tempfile.TemporaryDirectory(prefix="hvd-protocheck-") as pc_dir:
        base = {**base, **protocheck_env(pc_dir)}
        procs = [launch_rank("elastic_join", rank, 2, addr, extra_env=base)
                 for rank in range(2)]
        time.sleep(1.5)  # the 2-rank job is rendezvoused and training
        # (~1.3s to rendezvous; a joiner dialing DURING rendezvous is
        # rejected and retried by init anyway, so early is safe)
        procs.append(launch_rank(
            "elastic_join", 2, 3, addr,
            extra_env={**base, "HOROVOD_ELASTIC_JOIN": "1"}))
        outputs = finish(procs, 120.0, "elastic_join")
        for out in outputs:
            assert "ELASTIC size=3" in out, out
        assert assert_protocheck_clean(pc_dir, "elastic_join") == 3
    snap = _rank0_snapshot(outputs)
    transitions = _counter_by_label(snap,
                                    "hvd_membership_transitions_total")
    assert transitions.get("grow", 0) >= 1, transitions


@pytest.mark.slow  # tier-1 sibling: test_simcluster.py::test_sim_parked_joiner_at_max_ranks_epoch_stable
def test_elastic_parked_joiner_at_max_ranks_does_not_livelock():
    """A joiner dialing a job already at --max-ranks stays PARKED: the
    members keep training at epoch 1 with no reshape (an unconditional
    boundary reshape would admit nobody yet drain in-flight work every
    cycle — a livelock), and the coordinator keeps the parked wire alive
    with heartbeats instead of letting its deadline kill it."""
    addr = f"127.0.0.1:{free_port()}"
    pc_dir = tempfile.mkdtemp(prefix="hvd-protocheck-")
    base = {"HOROVOD_ELASTIC": "1", "HOROVOD_ELASTIC_MAX_RANKS": "2",
            **protocheck_env(pc_dir)}
    procs = [launch_rank("elastic_parked", rank, 2, addr, extra_env=base)
             for rank in range(2)]
    time.sleep(1.5)  # members are rendezvoused and mid-run
    joiner = launch_rank("elastic_parked", 2, 3, addr,
                         extra_env={**base, "HOROVOD_ELASTIC_JOIN": "1"})
    try:
        for out in finish(procs, 120.0, "elastic_parked"):
            assert "PARKED_OK size=2 epoch=1" in out, out
        # The members' wires (and the coordinator's parked-joiner wire,
        # heartbeats only) stayed on-spec the whole time.
        assert_protocheck_clean(pc_dir, "elastic_parked", require=2)
    finally:
        # The joiner stayed (correctly) parked for the members' whole
        # run: either it is still blocked in await_assignment, or — the
        # members having just exited and closed the coordinator — it
        # died of the teardown's "peer closed connection" moments ago
        # (a photo-finish race this assertion must not depend on). What
        # it must NEVER show is a liveness-deadline death while parked:
        # that would mean the coordinator's heartbeats stopped reaching
        # the parked wire.
        if joiner.poll() is None:
            joiner.kill()
            joiner.communicate()
        else:
            out = joiner.communicate()[0]
            assert "peer closed connection" in out, (
                f"parked joiner died for the wrong reason:\n{out}")
            assert "CommTimeoutError" not in out, (
                f"parked joiner was deadline-killed while parked:\n{out}")


@pytest.mark.slow
def test_elastic_kill_join_storm_settles_consistent():
    """Scripted churn storm: rank 2 SIGKILLed at cycle 40, rank 1 spawns
    a joiner clone at cycle 400 (both via FaultPlan membership kinds).
    The job must settle back at 3 ranks on a bumped epoch with
    bit-identical state on every member — including the clone, whose OK
    line lands in rank 1's stream."""
    kill = json.dumps({"faults": [
        {"site": "cycle", "action": "kill", "at": 40, "rank": 2}]})
    join = json.dumps({"faults": [
        {"site": "cycle", "action": "join", "at": 400, "rank": 1}]})
    outputs = run_ranks(
        "elastic_storm", size=3, timeout=180.0,
        extra_env=_elastic_env(),
        per_rank_env={1: {"HOROVOD_FAULT_PLAN": join},
                      2: {"HOROVOD_FAULT_PLAN": kill}},
        allowed_exit={2: (-9,)})
    for rank in (0, 1):
        assert "ELASTIC size=3" in outputs[rank], outputs[rank]
    # The clone (admitted as the new rank 2) shares rank 1's stdout.
    assert "worker rank=2 scenario=elastic_storm: OK" in outputs[1], \
        outputs[1]
    snap = _rank0_snapshot(outputs)
    transitions = _counter_by_label(snap,
                                    "hvd_membership_transitions_total")
    assert transitions.get("shrink", 0) >= 1, transitions
    assert transitions.get("grow", 0) >= 1, transitions


@pytest.mark.slow
def test_elastic_launcher_respawns_dead_worker(tmp_path):
    """horovodrun --elastic end to end: rank 1 dies (exit 7) after a few
    steps; the launcher respawns its slot as a joiner instead of tearing
    the job down, and rank 0 trains through the shrink and the re-grow to
    a clean exit."""
    script = tmp_path / "elastic_train.py"
    script.write_text(
        "import os, sys, time\n"
        "import numpy as np\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "state = hvd.elastic.State(step=0)\n"
        "fragile = (os.environ.get('HOROVOD_RANK') == '1'\n"
        "           and 'HOROVOD_ELASTIC_JOIN' not in os.environ)\n"
        "deadline = time.monotonic() + 90.0\n"
        "@hvd.elastic.run\n"
        "def train(state):\n"
        "    settled = 0\n"
        "    while True:\n"
        "        total = float(np.asarray(hvd.allreduce(\n"
        "            np.ones(1, np.float32), average=False,\n"
        "            name=f't.{state.step}'))[0])\n"
        "        state.step += 1\n"
        "        state.commit()\n"
        "        if fragile and state.step >= 5:\n"
        "            sys.stdout.flush()\n"
        "            os._exit(7)  # simulated preemption\n"
        "        if total == 2.0 and hvd.elastic.epoch() >= 2:\n"
        "            settled += 1\n"
        "            if settled >= 5:\n"
        "                return state.step\n"
        # A wall-clock guard, not a step bound: the shrunken size-1 world
        # takes the local allreduce fast path and can burn any fixed step
        # budget before the joiner finishes importing jax.
        "        assert time.monotonic() < deadline, \\\n"
        "            'never re-grew to 2 ranks'\n"
        "train(state)\n"
        "print(f'rank {hvd.rank()} done size={hvd.size()} '\n"
        "      f'epoch={hvd.elastic.epoch()}', flush=True)\n"
        "hvd.shutdown()\n")
    env = child_env()
    env["HOROVOD_CYCLE_TIME"] = "1"
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2", "--elastic",
         sys.executable, str(script)],
        timeout=180, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "respawning its slot as an elastic joiner" in res.stderr, \
        res.stderr
    assert "rank 0 done size=2" in res.stdout, res.stdout + res.stderr
