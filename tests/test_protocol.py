"""Protocol conformance (ISSUE 8, docs/static-analysis.md): the
declarative wire/epoch spec, its static handler↔spec bijection gate, the
HOROVOD_PROTOCHECK runtime monitor (units + real wires + a 2-rank job),
the protocheck CLI contract, and the static lock-order graph + its
static×runtime join.
"""

import json
import os
import socket
import sys

import pytest

from mp_harness import (
    assert_protocheck_clean,
    finish,
    free_port,
    launch_rank,
    protocheck_env,
)

from horovod_tpu.analysis import lockorder, protocol
from horovod_tpu.analysis.protocol import (
    INITIAL_EPOCH,
    KINDS,
    ROLES,
    SPEC,
    ProtocolMonitor,
    ProtocolViolationError,
    epoch_advances,
    epoch_is_stale,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
PKG = os.path.join(REPO, "horovod_tpu")

SECRET = b"x" * 32


# ---------------------------------------------------------------------------
# 1. The spec itself (tier-1 gates)


def test_spec_is_internally_consistent():
    assert protocol.check_spec() == []


def test_spec_covers_every_kind_for_every_role():
    """The spec half of the handler↔spec bijection: all five frame kinds
    appear (as a transition or a declared violation) in both directions
    for all three roles — heartbeat implicitly, it is legal everywhere."""
    assert set(ROLES) == {"coordinator", "worker", "joiner"}
    for role in ROLES:
        for direction in ("send", "recv"):
            covered = {kind for state in SPEC[role]["states"]
                       for (d, kind) in SPEC[role]["states"][state]
                       if d == direction} | {"heartbeat"}
            assert covered == set(KINDS), (role, direction, covered)


def test_spec_initial_epochs():
    assert INITIAL_EPOCH == {"coordinator": 1, "worker": 1, "joiner": 0}


def test_epoch_helpers_are_the_one_ordering():
    assert epoch_advances(2, 1) and not epoch_advances(1, 1)
    assert not epoch_advances(1, 2)
    assert epoch_is_stale(1, 2) and not epoch_is_stale(2, 2)
    assert not epoch_is_stale(3, 2)


def test_static_handler_spec_bijection_holds():
    """THE static conformance gate: the real wire.py/service.py/
    controller.py dispatch and the spec agree exactly. Any drift —
    a new kind branch, a missing one, an undeclared dispatch site —
    fails tier-1 here until spec and code are reconciled."""
    findings = protocol.check_handlers(PKG)
    assert findings == [], "\n".join(
        f"{f['path']}:{f['line']}: {f['message']}" for f in findings)


def test_invariants_are_documented():
    names = {inv["name"] for inv in protocol.INVARIANTS}
    assert {"ack_before_commit", "fence_before_enqueue",
            "epoch_monotonicity"} <= names


# ---------------------------------------------------------------------------
# 2. Monitor units (no wires: drive the machine directly)


def _fresh_recorder():
    rec = protocol._Recorder()
    return rec


def test_monitor_legal_worker_lifecycle():
    rec = _fresh_recorder()
    m = ProtocolMonitor("worker", recorder_=rec)
    m.observe("send", "data")                      # hello
    m.observe("recv", "data")
    m.observe("send", "heartbeat")
    m.observe("recv", "reshape", {"epoch": 2, "rank": 1, "size": 2})
    assert m.state == "reshaping" and m.pending_epoch == 2
    m.observe("send", "join", {"ack": 2})
    assert m.state == "steady" and m.epoch == 2
    assert rec.report()["ok"]


def test_monitor_coordinator_drain_with_stale_ack():
    rec = _fresh_recorder()
    m = ProtocolMonitor("coordinator", recorder_=rec)
    m.observe("recv", "data")                      # rendezvous hello
    m.observe("send", "reshape", {"epoch": 2})
    assert m.state == "draining"
    m.observe("recv", "data")                      # dead-epoch discard
    m.observe("recv", "join", {"ack": 1})          # stale: stays draining
    assert m.state == "draining"
    m.observe("recv", "join", {"ack": 2})          # commit
    assert m.state == "steady" and m.epoch == 2
    # Retry path: fresh epoch while already draining.
    m.observe("send", "reshape", {"epoch": 3})
    m.observe("send", "reshape", {"epoch": 4})
    m.observe("recv", "join", {"ack": 4})
    assert m.epoch == 4 and rec.report()["ok"]


def test_monitor_joiner_admission():
    rec = _fresh_recorder()
    m = ProtocolMonitor("joiner", recorder_=rec)
    assert m.epoch == 0
    m.observe("send", "join", {"join": True, "rank": None})
    assert m.state == "parked"
    m.observe("recv", "heartbeat")
    m.observe("recv", "reshape", {"epoch": 3, "rank": 2, "size": 3})
    m.observe("send", "join", {"ack": 3})
    assert m.state == "steady" and m.epoch == 3
    # Admitted joiner now plays the worker machine (aliased states).
    m.observe("send", "data")
    m.observe("recv", "reshape", {"epoch": 4, "rank": 1, "size": 2})
    assert m.state == "reshaping"
    assert rec.report()["ok"]


@pytest.mark.parametrize("case,expect_detail", [
    # Epoch monotonicity: a reshape that does not advance the epoch.
    (lambda m: (m.observe("recv", "data"),
                m.observe("send", "reshape", {"epoch": 1})),
     "epoch must advance"),
    # Ack from the future.
    (lambda m: (m.observe("recv", "data"),
                m.observe("send", "reshape", {"epoch": 2}),
                m.observe("recv", "join", {"ack": 5})),
     "ack for epoch 5"),
    # Join hello where an ack belongs.
    (lambda m: (m.observe("recv", "data"),
                m.observe("send", "reshape", {"epoch": 2}),
                m.observe("recv", "join", {"join": True})),
     "expected a reshape ack"),
    # Declared violation branch: join in the coordinator's data stream.
    (lambda m: (m.observe("recv", "data"),
                m.observe("recv", "join", {"join": True})),
     "join frame in the data stream"),
])
def test_monitor_guard_and_violation_paths(case, expect_detail):
    rec = _fresh_recorder()
    m = ProtocolMonitor("coordinator", recorder_=rec)
    case(m)
    report = rec.report()
    assert not report["ok"]
    assert expect_detail in report["violations"][-1]["detail"]


def test_monitor_raise_mode(monkeypatch):
    monkeypatch.setattr(protocol, "_mode", "raise")
    rec = _fresh_recorder()
    m = ProtocolMonitor("worker", recorder_=rec)
    m.observe("send", "data")
    with pytest.raises(ProtocolViolationError, match="send join"):
        m.observe("send", "join", {"join": True})
    monkeypatch.setattr(protocol, "_mode", None)


def test_unknown_role_rejected():
    with pytest.raises(ValueError):
        ProtocolMonitor("bystander")


# ---------------------------------------------------------------------------
# 3. Real wires under the monitor


@pytest.fixture
def protocheck_on(monkeypatch):
    monkeypatch.setattr(protocol, "_mode", "record")
    protocol.recorder().clear()
    yield
    protocol.recorder().clear()
    monkeypatch.setattr(protocol, "_mode", None)


def _wire_pair():
    from horovod_tpu.common.wire import Wire

    a, b = socket.socketpair()
    return Wire(a, secret=SECRET), Wire(b, secret=SECRET)


def test_wire_reshape_handshake_is_conformant(protocheck_on):
    from horovod_tpu.common.wire import RanksChangedError

    worker, coord = _wire_pair()
    worker.set_protocol_role("worker")
    coord.set_protocol_role("coordinator")
    worker.send_obj({"rank": 1})
    assert coord.recv_obj() == {"rank": 1}
    worker.send_obj({"tick": 0})
    coord.recv_obj()
    coord.send_obj({"reply": 0})
    worker.recv_obj()
    coord.send_reshape(rank=1, size=2, epoch=2)
    with pytest.raises(RanksChangedError):
        worker.recv_obj()
    worker.send_join({"ack": 2})
    coord.recv_reshape_ack(2)
    coord.send_obj({"epoch2": True})
    assert worker.recv_obj() == {"epoch2": True}
    report = protocol.recorder().report()
    assert report["ok"], report["violations"]
    assert report["transitions"] >= 10
    worker.close(), coord.close()


def test_join_in_data_stream_fires_monitor_naming_the_transition(
        protocheck_on):
    """The deliberately-broken seam from the acceptance criteria: a JOIN
    frame inside the data stream must be recorded as a violation naming
    the exact off-spec transition on BOTH sides — the sender's
    worker.steady send join and the receiver's coordinator.steady recv
    join — in addition to the existing AuthError."""
    from horovod_tpu.common.wire import AuthError

    worker, coord = _wire_pair()
    worker.set_protocol_role("worker")
    coord.set_protocol_role("coordinator")
    worker.send_obj({"rank": 1})
    coord.recv_obj()
    worker.send_join({"join": True})        # off-spec: no reshape pending
    with pytest.raises(AuthError, match="join frame"):
        coord.recv_obj()
    report = protocol.recorder().report()
    assert not report["ok"]
    named = {(v["role"], v["state"], v["direction"], v["kind"])
             for v in report["violations"]}
    assert ("worker", "steady", "send", "join") in named
    assert ("coordinator", "steady", "recv", "join") in named
    detail = [v["detail"] for v in report["violations"]
              if v["role"] == "coordinator"][0]
    assert "join frame in the data stream" in detail
    worker.close(), coord.close()


def test_write_report_artifact(protocheck_on, tmp_path, monkeypatch):
    m = ProtocolMonitor("worker")
    m.observe("send", "data")
    out = tmp_path / "protocheck.json"
    assert protocol.write_report(str(out)) == str(out)
    payload = json.loads(out.read_text())
    assert payload["ok"] is True and payload["transitions"] >= 1
    # {rank} expansion mirrors the flight recorder's.
    monkeypatch.setenv("HOROVOD_PROTOCHECK_OUTPUT",
                       str(tmp_path / "pc-{rank}.json"))
    monkeypatch.setenv("HOROVOD_RANK", "3")
    assert protocol.output_path() == str(tmp_path / "pc-3.json")
    monkeypatch.setenv("HOROVOD_PROTOCHECK_OUTPUT",
                       str(tmp_path / "pc.json"))
    assert protocol.output_path() == str(tmp_path / "pc.json") + ".rank3"


# ---------------------------------------------------------------------------
# 4. A real 2-rank job under the monitor (clean-path conformance)


def test_two_rank_job_is_conformant(tmp_path):
    addr = f"127.0.0.1:{free_port()}"
    pc_dir = str(tmp_path)
    procs = [launch_rank("allreduce", rank, 2, addr,
                         extra_env=protocheck_env(pc_dir))
             for rank in range(2)]
    finish(procs, 120.0, "allreduce")
    assert assert_protocheck_clean(pc_dir, "allreduce") == 2
    for rank in range(2):
        payload = json.loads(
            (tmp_path / f"protocheck.json.rank{rank}").read_text())
        assert payload["transitions"] > 10, payload


# ---------------------------------------------------------------------------
# 5. protocheck CLI contract


def _cli(*args):
    from horovod_tpu.tools import protocheck as cli

    return cli


def test_cli_clean_exit_and_json(capsys):
    from horovod_tpu.tools import protocheck as cli

    assert cli.main(["--format", "json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["static_findings"] == []


def test_cli_exits_nonzero_on_handler_drift(monkeypatch, capsys):
    """Drift simulation: drop a declared handler from the table — its
    dispatch site becomes undeclared and the CLI must exit 1. This is
    the 'spec cannot rot' contract."""
    from horovod_tpu.tools import protocheck as cli

    trimmed = {k: v for k, v in sorted(protocol.HANDLERS.items())
               if not k.endswith("recv_reshape_ack")}
    monkeypatch.setattr(protocol, "HANDLERS", trimmed)
    assert cli.main(["--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert any("recv_reshape_ack" in f["message"]
               for f in payload["static_findings"])


def test_cli_validates_runtime_artifacts(tmp_path, capsys):
    from horovod_tpu.tools import protocheck as cli

    clean = tmp_path / "clean.json"
    clean.write_text(json.dumps(
        {"ok": True, "transitions": 5, "violations": []}))
    assert cli.main(["--runtime", str(clean)]) == 0
    capsys.readouterr()
    dirty = tmp_path / "dirty.json"
    dirty.write_text(json.dumps({
        "ok": False, "transitions": 5,
        "violations": [{"role": "worker", "state": "steady",
                        "direction": "send", "kind": "join",
                        "epoch": 1, "pending_epoch": None,
                        "detail": "reshape ack without a reshape"}]}))
    assert cli.main(["--runtime", str(clean), str(dirty)]) == 1
    out = capsys.readouterr().out
    assert "OFF-SPEC worker.steady send join" in out


def test_cli_dump_spec_renders_all_roles(capsys):
    from horovod_tpu.tools import protocheck as cli

    assert cli.main(["--dump-spec"]) == 0
    out = capsys.readouterr().out
    for role in ROLES:
        assert f"role `{role}`" in out
    assert "guard: epoch_advances" in out
    assert "heartbeats are legal in every state" in out


# ---------------------------------------------------------------------------
# 6. Static lock graph + static×runtime join


def test_static_lock_graph_finds_seeded_inversion(tmp_path):
    (tmp_path / "mod.py").write_text(
        "from horovod_tpu.analysis.lockorder import make_lock\n"
        "\n"
        "class Widget:\n"
        "    def __init__(self):\n"
        "        self._a = make_lock('seed.a')\n"
        "        self._b = make_lock('seed.b')\n"
        "\n"
        "    def forwards(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "\n"
        "    def backwards(self):\n"
        "        with self._b:\n"
        "            self.helper()\n"
        "\n"
        "    def helper(self):\n"
        "        with self._a:\n"
        "            pass\n")
    rep = lockorder.static_graph([str(tmp_path)])
    edges = {(e["from"], e["to"]) for e in rep["edges"]}
    # forwards: direct a->b; backwards: b->a THROUGH the call graph.
    assert ("seed.a", "seed.b") in edges
    assert ("seed.b", "seed.a") in edges
    assert not rep["acyclic"]
    assert any(c["locks"][:-1] in (["seed.a", "seed.b"],
                                   ["seed.b", "seed.a"])
               for c in rep["cycles"])
    # The actionable part: the edge names where it was derived.
    via = [e["via"] for e in rep["edges"]
           if (e["from"], e["to"]) == ("seed.b", "seed.a")][0]
    assert "backwards" in via and "helper" in via


def test_package_static_lock_graph_gate():
    """Tier-1 gate (same empty-baseline discipline as r10): the
    package's potential lock-order graph has NO cycles. A cycle here is
    a potential deadlock that never needed to happen at runtime to be
    real — fix the ordering, don't baseline it."""
    rep = lockorder.static_graph()
    assert rep["locks"], "no make_lock sites found — pass is broken"
    assert rep["acyclic"], (
        "statically-possible lock-order cycle(s): "
        + "; ".join(" -> ".join(c["locks"]) for c in rep["cycles"]))
    # Known-real runtime orderings must be present (coverage canaries —
    # an empty or gutted static graph would vacuously pass acyclicity).
    edges = {(e["from"], e["to"]) for e in rep["edges"]}
    assert ("timeline.pids", "metrics.metric") in edges
    assert ("wire.send", "metrics.metric") in edges


def test_join_reports_superset_and_unobserved_cycles():
    static = {
        "edges": [{"from": "a", "to": "b", "via": "x"},
                  {"from": "b", "to": "a", "via": "y"},
                  {"from": "a", "to": "c", "via": "z"}],
        "cycles": [{"locks": ["a", "b", "a"]}],
    }
    runtime = [{"edges": [{"from": "a", "to": "b"}], "cycles": []}]
    join = lockorder.join_reports(static, runtime)
    assert join["superset"] is True
    assert join["unobserved_cycles"] == [["a", "b", "a"]]
    # A runtime edge the static pass missed breaks the contract.
    runtime.append({"edges": [{"from": "c", "to": "a"}], "cycles": []})
    join = lockorder.join_reports(static, runtime)
    assert join["superset"] is False
    assert join["uncovered_runtime_edges"] == [["c", "a"]]


def test_cli_lockgraph_join(tmp_path, capsys):
    from horovod_tpu.tools import protocheck as cli

    rt = tmp_path / "lockgraph.json"
    rt.write_text(json.dumps({
        "edges": [{"from": "timeline.pids", "to": "metrics.metric",
                   "count": 1, "thread": "t", "stack_held": [],
                   "stack_acquired": []}],
        "cycles": [], "acyclic": True, "locks": []}))
    rc = cli.main(["--lockgraph", str(rt)])
    out = capsys.readouterr().out
    assert "superset=True" in out
    # Exit 0 only when the static graph is acyclic AND a superset; the
    # package graph is acyclic, so unobserved cycles are empty and this
    # run is clean.
    assert rc == 0
