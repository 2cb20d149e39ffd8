"""Cluster doctor: rule-catalog units over synthetic evidence, evidence
collection from artifact directories, the offline CLI, the /doctor HTTP
route, and the 3-rank FaultPlan delay-chaos acceptance (a seeded delay
on rank 1 must yield a deterministic persistent-straggler Diagnosis
naming rank 1 via BOTH the live rank-0 endpoint and the offline
``python -m horovod_tpu.tools.doctor`` over the artifact dir).
"""

import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np  # noqa: F401  (parity with the other mp test modules)
import pytest

from mp_harness import free_port as _free_port
from mp_harness import run_cmd
from mp_harness import run_ranks as _run_ranks

from horovod_tpu import doctor, metrics
from horovod_tpu.doctor import Evidence, diagnose
from horovod_tpu.doctor import rules as doctor_rules
from horovod_tpu.metrics import MetricsRegistry



@pytest.fixture(autouse=True)
def _fresh_metrics(monkeypatch):
    for var in ("HOROVOD_METRICS", "HOROVOD_METRICS_PORT",
                "HOROVOD_FLIGHT_RECORDER", "HOROVOD_TRACE_DIR",
                "HOROVOD_RANK", "HOROVOD_RESTART_EPOCH"):
        monkeypatch.delenv(var, raising=False)
    metrics.reset_for_tests()
    yield
    metrics.reset_for_tests()


# ---------------------------------------------------------------------------
# Synthetic-evidence builders


def _hist_snapshot(name, per_label, labelnames=("rank",)):
    """Registry snapshot holding ONE histogram with observations per
    label value (or per '' for an unlabeled histogram)."""
    r = MetricsRegistry()
    h = r.histogram(name, "", labelnames)
    for label, observations in per_label.items():
        child = h.labels(label) if labelnames else h
        for value in observations:
            child.observe(value)
    return r.snapshot()


def _counter_snapshot(name, per_label, labelnames=("side",)):
    r = MetricsRegistry()
    c = r.counter(name, "", labelnames)
    for label, value in per_label.items():
        c.labels(label).inc(value)
    return r.snapshot()


def _gauge_snapshot(values, objective=None):
    """Snapshot of hvd_autotune_* gauges: {name: value} plus the
    component-labeled objective gauge."""
    r = MetricsRegistry()
    for name, value in values.items():
        r.gauge(name, "").set(value)
    if objective:
        g = r.gauge("hvd_autotune_objective", "", ("component",))
        for component, value in objective.items():
            g.labels(component).set(value)
    return r.snapshot()


def _straggler_report(collectives=200, late_rank=1, p99=0.05, cycles=None):
    cycles = collectives if cycles is None else cycles
    per_rank = {}
    for rank in range(3):
        late = rank == late_rank
        per_rank[str(rank)] = {
            "straggler_cycles": cycles if late else 0,
            "lateness_p50_seconds": p99 * 0.9 if late else 0.0,
            "lateness_p99_seconds": p99 if late else 0.0001,
            "lateness_max_seconds": p99 * 1.1 if late else 0.0002,
        }
    return {"collectives": collectives, "ranks": [0, 1, 2],
            "per_rank": per_rank, "worst_rank": late_rank,
            "worst_collectives": [], "clock": {}}


# ---------------------------------------------------------------------------
# Rule units


def test_persistent_straggler_from_report_names_rank_with_hint():
    ev = Evidence(straggler_report=_straggler_report(late_rank=1))
    findings = diagnose(ev)
    [finding] = [f for f in findings if f.rule == "persistent_straggler"]
    assert finding.rank == 1
    assert finding.severity == "warning"
    assert "rank 1" in finding.hint and "NIC" in finding.hint
    assert finding.evidence["straggler_cycles"] == 200
    # 100ms+ lateness escalates to critical.
    ev2 = Evidence(straggler_report=_straggler_report(p99=0.25))
    [f2] = [f for f in diagnose(ev2) if f.rule == "persistent_straggler"]
    assert f2.severity == "critical"


def test_persistent_straggler_below_thresholds_is_silent():
    # Too few collectives, too little lateness, too small a share: quiet.
    for report in (
        _straggler_report(collectives=5),
        _straggler_report(p99=0.001),
        _straggler_report(collectives=200, cycles=10),
    ):
        assert not [f for f in diagnose(Evidence(straggler_report=report))
                    if f.rule == "persistent_straggler"], report


def test_persistent_straggler_live_from_tick_lateness():
    snap = _hist_snapshot(
        "hvd_controller_tick_lateness_seconds",
        {"1": [0.05] * 30, "2": [0.0] * 30})
    findings = diagnose(Evidence(snapshots={0: snap}))
    [finding] = [f for f in findings if f.rule == "persistent_straggler"]
    assert finding.rank == 1
    assert finding.evidence["source"] == "tick_lateness"
    assert finding.evidence["cycles"] == 30
    # A uniformly-slow cluster (no skew) is not a straggler.
    flat = _hist_snapshot(
        "hvd_controller_tick_lateness_seconds",
        {"1": [0.05] * 30, "2": [0.05] * 30})
    assert not [f for f in diagnose(Evidence(snapshots={0: flat}))
                if f.rule == "persistent_straggler"]
    # A 2-rank job (ONE observed worker) has no cluster to compare
    # against — the ≥3x-median contract must not degenerate into an
    # absolute threshold that names a merely compute-bound lone worker.
    lone = _hist_snapshot(
        "hvd_controller_tick_lateness_seconds", {"1": [0.05] * 30})
    assert not [f for f in diagnose(Evidence(snapshots={0: lone}))
                if f.rule == "persistent_straggler"]


def test_persistent_straggler_dedupes_report_and_live():
    snap = _hist_snapshot(
        "hvd_controller_tick_lateness_seconds",
        {"1": [0.05] * 30, "2": [0.0] * 30})
    ev = Evidence(snapshots={0: snap},
                  straggler_report=_straggler_report(late_rank=1, p99=0.25))
    hits = [f for f in diagnose(ev) if f.rule == "persistent_straggler"]
    assert len(hits) == 1  # one (rule, rank) verdict, not two
    assert hits[0].severity == "critical"  # the worse severity wins


def test_clock_sync_degraded_unsynced_and_uncertain():
    ev = Evidence(clock={
        0: {"offset_seconds": 0.0, "synced": True},
        1: {"offset_seconds": 0.0, "synced": False},
        2: {"offset_seconds": 0.1, "uncertainty_seconds": 0.02,
            "synced": True},
    })
    findings = [f for f in diagnose(ev) if f.rule == "clock_sync_degraded"]
    assert {f.rank for f in findings} == {1, 2}
    by_rank = {f.rank: f for f in findings}
    assert "pong" in by_rank[1].hint
    assert "20ms" in by_rank[2].summary
    # A healthy table (or a single-rank job) is silent.
    assert not diagnose(Evidence(clock={0: {"synced": True}}))


def test_clock_sync_native_job_without_ping_plane_is_one_info():
    """NO worker synced = no python-side ping plane ran at all (a
    native-engine traced job, docs/tracing.md "Native engine"): one
    info-severity finding explaining the property — not a per-rank
    broken-heartbeat warning."""
    ev = Evidence(clock={
        0: {"applied_offset_seconds": 0.0, "synced": True},
        1: {"applied_offset_seconds": 0.0, "synced": False,
            "uncertainty_seconds": None},
        2: {"applied_offset_seconds": 0.0, "synced": False,
            "uncertainty_seconds": None},
    })
    findings = [f for f in diagnose(ev) if f.rule == "clock_sync_degraded"]
    assert len(findings) == 1
    assert findings[0].severity == "info"
    assert findings[0].rank is None
    assert "native" in findings[0].summary
    assert set(findings[0].evidence["clock"]) == {"1", "2"}
    # A python-engine job ALWAYS writes the offsets TABLE (entries carry
    # offset_seconds/samples) — all-unsynced THERE is a genuinely broken
    # ping plane and must stay a per-rank WARNING, never the info branch.
    broken = Evidence(clock={
        0: {"offset_seconds": 0.0, "synced": True},
        1: {"offset_seconds": 0.0, "samples": 0, "synced": False},
        2: {"offset_seconds": 0.0, "samples": 0, "synced": False},
    })
    findings = [f for f in diagnose(broken)
                if f.rule == "clock_sync_degraded"]
    assert {f.rank for f in findings} == {1, 2}
    assert all(f.severity == "warning" for f in findings)


def test_recv_wait_skew_names_outlier_rank():
    snaps = {
        0: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.001] * 30}, labelnames=()),
        1: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.001] * 30}, labelnames=()),
        2: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.1] * 30}, labelnames=()),
    }
    [finding] = [f for f in diagnose(Evidence(snapshots=snaps))
                 if f.rule == "recv_wait_skew"]
    assert finding.rank == 2
    assert finding.evidence["recvs"] == 30
    # One snapshot alone (no cluster view) cannot judge skew.
    assert not [f for f in diagnose(Evidence(snapshots={2: snaps[2]}))
                if f.rule == "recv_wait_skew"]


def test_recv_wait_skew_fires_at_two_worker_minimum():
    """The documented minimum is 2 WORKER snapshots: the comparison
    floor is the median of the OTHER workers' p99s, so a 2-worker
    outlier is judged against its peer, not against its own value
    (which would make the rule unable to ever fire at the minimum)."""
    snaps = {
        1: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.001] * 30}, labelnames=()),
        2: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.1] * 30}, labelnames=()),
    }
    [finding] = [f for f in diagnose(Evidence(snapshots=snaps))
                 if f.rule == "recv_wait_skew"]
    assert finding.rank == 2
    # Two healthy equal workers stay silent.
    healthy = {
        1: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.03] * 30}, labelnames=()),
        2: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.03] * 30}, labelnames=()),
    }
    assert not [f for f in diagnose(Evidence(snapshots=healthy))
                if f.rule == "recv_wait_skew"]


def test_recv_wait_skew_never_blames_the_coordinator():
    """Star topology: rank 0's recvs block waiting for the slowest
    worker's tick, so a sick WORKER inflates the COORDINATOR's
    recv-wait profile. The rule must exclude rank 0 on both sides —
    blaming it here would name exactly the wrong rank (2-rank job:
    rank 1 is slow, rank 0 shows the 50ms waits)."""
    snaps = {
        0: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.05] * 30}, labelnames=()),
        1: _hist_snapshot("hvd_wire_recv_wait_seconds",
                          {"": [0.001] * 30}, labelnames=()),
    }
    assert not [f for f in diagnose(Evidence(snapshots=snaps))
                if f.rule == "recv_wait_skew"]


def test_heartbeat_flapping_thresholds():
    snap = _counter_snapshot("hvd_wire_deadline_trips_total", {"recv": 4})
    [finding] = [f for f in diagnose(Evidence(snapshots={1: snap}))
                 if f.rule == "heartbeat_flapping"]
    assert finding.rank == 1 and finding.severity == "warning"
    crit = _counter_snapshot("hvd_wire_deadline_trips_total", {"recv": 12})
    [f2] = [f for f in diagnose(Evidence(snapshots={1: crit}))
            if f.rule == "heartbeat_flapping"]
    assert f2.severity == "critical"
    one = _counter_snapshot("hvd_wire_deadline_trips_total", {"recv": 1})
    assert not [f for f in diagnose(Evidence(snapshots={1: one}))
                if f.rule == "heartbeat_flapping"]


def test_heartbeat_flapping_from_postmortems():
    events = [{"kind": "flight_recorder_dump", "rank": 2},
              {"kind": "deadline_trip", "side": "recv", "rank": 2},
              {"kind": "deadline_trip", "side": "recv", "rank": 2},
              {"kind": "deadline_trip", "side": "recv", "rank": 2}]
    [finding] = [f for f in diagnose(Evidence(postmortems=[events]))
                 if f.rule == "heartbeat_flapping"]
    assert finding.rank == 2 and finding.evidence["deadline_trips"] == 3


def test_cache_hit_collapse_needs_traffic_and_membership_context():
    r = MetricsRegistry()
    r.counter("hvd_controller_cache_hits_total", "").inc(10)
    r.counter("hvd_controller_cache_misses_total", "").inc(490)
    ev = Evidence(snapshots={0: r.snapshot()}, restart_epoch=1)
    [finding] = [f for f in diagnose(ev) if f.rule == "cache_hit_collapse"]
    assert finding.evidence["hit_rate"] == pytest.approx(0.02)
    assert "restart_epoch" in finding.evidence
    # Healthy hit rate, or too little traffic to judge: silent.
    healthy = MetricsRegistry()
    healthy.counter("hvd_controller_cache_hits_total", "").inc(300)
    healthy.counter("hvd_controller_cache_misses_total", "").inc(100)
    assert not [f for f in
                diagnose(Evidence(snapshots={0: healthy.snapshot()}))
                if f.rule == "cache_hit_collapse"]
    tiny = MetricsRegistry()
    tiny.counter("hvd_controller_cache_misses_total", "").inc(50)
    assert not [f for f in
                diagnose(Evidence(snapshots={0: tiny.snapshot()}))
                if f.rule == "cache_hit_collapse"]


def test_restart_churn_severity_scale():
    assert not [f for f in diagnose(Evidence(restart_epoch=1))
                if f.rule == "restart_churn"]
    [warning] = [f for f in diagnose(Evidence(restart_epoch=2))
                 if f.rule == "restart_churn"]
    assert warning.severity == "warning"
    [critical] = [f for f in diagnose(Evidence(restart_epoch=6))
                  if f.rule == "restart_churn"]
    assert critical.severity == "critical"
    assert "crash-looping" in critical.hint


def test_autotune_stalled_and_wandering():
    # Scoreless EARLY in the job (warmup + first sample window still in
    # progress) is normal, not a finding — a fresh autotuned job must
    # scrape healthy.
    young = _gauge_snapshot({"hvd_autotune_active": 1,
                             "hvd_autotune_steps_completed": 0})
    young.update(_hist_snapshot("hvd_controller_cycle_seconds",
                                {"": [0.001] * 100}, labelnames=()))
    assert not [f for f in diagnose(Evidence(snapshots={0: young}))
                if f.rule.startswith("autotune")]
    # Still scoreless after hundreds of cycles: stalled.
    stalled = _gauge_snapshot({"hvd_autotune_active": 1,
                               "hvd_autotune_steps_completed": 0})
    stalled.update(_hist_snapshot("hvd_controller_cycle_seconds",
                                  {"": [0.001] * 600}, labelnames=()))
    [finding] = [f for f in diagnose(Evidence(snapshots={0: stalled}))
                 if f.rule == "autotune_stalled"]
    assert finding.severity == "info"
    assert finding.evidence["cycles_observed"] == 600
    wandering = _gauge_snapshot(
        {"hvd_autotune_active": 1, "hvd_autotune_steps_completed": 12,
         "hvd_autotune_best_objective": 100.0},
        objective={"score": 30.0, "throughput_bytes_per_sec": 30.0,
                   "slack_penalty": 0.0, "recv_wait_penalty": 0.0})
    [f2] = [f for f in diagnose(Evidence(snapshots={0: wandering}))
            if f.rule == "autotune_wandering"]
    assert "30%" in f2.summary
    # Search complete (active 0) or scoring near its best: silent.
    done = _gauge_snapshot({"hvd_autotune_active": 0,
                            "hvd_autotune_steps_completed": 20})
    assert not [f for f in diagnose(Evidence(snapshots={0: done}))
                if f.rule.startswith("autotune")]
    healthy = _gauge_snapshot(
        {"hvd_autotune_active": 1, "hvd_autotune_steps_completed": 12,
         "hvd_autotune_best_objective": 100.0},
        objective={"score": 90.0})
    assert not [f for f in diagnose(Evidence(snapshots={0: healthy}))
                if f.rule.startswith("autotune")]


def test_diagnose_orders_most_severe_first():
    ev = Evidence(
        snapshots={1: _counter_snapshot("hvd_wire_deadline_trips_total",
                                        {"recv": 3})},
        straggler_report=_straggler_report(late_rank=2, p99=0.25),
        restart_epoch=2)
    findings = diagnose(ev)
    assert [f.severity for f in findings] == sorted(
        [f.severity for f in findings],
        key=["critical", "warning", "info"].index)
    assert findings[0].rule == "persistent_straggler"


# ---------------------------------------------------------------------------
# Report / summary / rendering / gauges


def test_report_shape_and_doctor_gauges():
    metrics.enable()
    rep = doctor.report()
    assert rep["healthy"] is True and rep["findings"] == []
    assert rep["source"] == "live"
    assert rep == json.loads(json.dumps(rep))  # JSON-clean
    snap = metrics.snapshot()
    [[_, runs]] = snap["hvd_doctor_runs_total"]["values"]
    assert runs == 1
    by_rule = dict((tuple(k), v) for k, v in
                   snap["hvd_doctor_findings"]["values"])
    assert set(r for (r,) in by_rule) == set(doctor.RULE_SLUGS)
    assert all(v == 0 for v in by_rule.values())


def test_summary_and_render_and_periodic_line():
    ev = Evidence(straggler_report=_straggler_report(late_rank=1))
    rep = doctor.report(ev)
    assert rep["healthy"] is False
    assert rep["counts"]["warning"] == 1
    s = doctor.summary(rep)
    assert s["findings"] == 1
    assert s["rules_hit"] == ["persistent_straggler"]
    assert s["worst_rank"] == 1 and "NIC" in s["worst_hint"]
    text = doctor.render_text(rep)
    assert "[warning] persistent_straggler rank 1" in text
    assert "hint:" in text
    line = doctor.periodic_line(ev)
    assert "1 finding(s)" in line and "rank 1 persistent_straggler" in line
    healthy_line = doctor.periodic_line(Evidence())
    assert healthy_line.startswith("healthy")
    empty = doctor.summary(doctor.report(Evidence()))
    assert empty == {"findings": 0, "rules_hit": [], "worst_rank": None,
                     "worst_hint": None}


# ---------------------------------------------------------------------------
# Evidence from artifacts


def _write_trace_dir(tmp_path, late_rank=1, late_us=400_000, n=12):
    """A small artifact dir: per-rank traces whose merged attribution
    names ``late_rank``, plus a clock table."""
    def rank_file(rank, spans):
        events = [{"name": "clock_sync", "ph": "M", "pid": rank,
                   "args": {"wall_anchor": 1000.0, "monotonic_origin": 0.0,
                            "rank": rank}}] + spans
        with open(os.path.join(str(tmp_path), f"trace.rank{rank}.json"),
                  "w") as f:
            json.dump(events, f)

    for rank in range(3):
        spans = []
        for seq in range(n):
            ts = seq * 2_000_000 + (late_us if rank == late_rank else 0)
            spans.append({"name": "negotiate", "ph": "X", "pid": rank,
                          "tid": 2, "ts": ts, "dur": 100,
                          "args": {"seq": seq, "op": f"t.{seq}"}})
        rank_file(rank, spans)
    offsets = {str(r): {"offset_seconds": 0.0, "uncertainty_seconds": 1e-5,
                        "rtt_seconds": 2e-5, "samples": 4, "synced": True}
               for r in range(3)}
    with open(os.path.join(str(tmp_path), "clock_offsets.json"), "w") as f:
        json.dump(offsets, f)


def test_evidence_from_artifacts_attributes_in_memory(tmp_path):
    _write_trace_dir(tmp_path)
    ev = Evidence.from_artifacts(str(tmp_path))
    assert ev.source == f"artifacts:{tmp_path}"
    # No straggler_report.json on disk: attributed from the rank traces —
    # and NOT written back (the doctor is read-only).
    assert ev.straggler_report["collectives"] == 12
    assert not os.path.exists(
        os.path.join(str(tmp_path), "straggler_report.json"))
    assert ev.clock[1]["synced"] is True
    assert ev.ranks_observed() == [0, 1, 2]
    [finding] = [f for f in diagnose(ev)
                 if f.rule == "persistent_straggler"]
    assert finding.rank == 1
    assert finding.severity == "critical"  # 400ms lateness


def test_evidence_from_artifacts_reads_postmortems(tmp_path):
    lines = [{"kind": "flight_recorder_dump", "reason": "fail_all",
              "rank": 2, "events": 3}]
    lines += [{"kind": "deadline_trip", "side": "recv", "rank": 2}] * 3
    with open(tmp_path / "fr.jsonl.rank2", "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    (tmp_path / "not_a_dump.jsonl").write_text('{"kind": "other"}\n')
    # A dump killed between temp-write and os.replace leaves its private
    # temp file behind; it must NOT be ingested as a second postmortem
    # (it would double-count every event the completed dump carries).
    with open(tmp_path / "fr.jsonl.rank2.tmp.123.456", "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    ev = Evidence.from_artifacts(str(tmp_path))
    assert len(ev.postmortems) == 1
    [finding] = [f for f in diagnose(ev)
                 if f.rule == "heartbeat_flapping"]
    assert finding.rank == 2
    assert finding.evidence["deadline_trips"] == 3  # not 6


def test_evidence_from_artifacts_empty_dir(tmp_path):
    ev = Evidence.from_artifacts(str(tmp_path))
    assert ev.straggler_report is None and ev.clock is None
    assert ev.postmortems == [] and ev.ranks_observed() == []


# ---------------------------------------------------------------------------
# Offline CLI


def _run_cli(args, timeout=120):
    return run_cmd(
        [sys.executable, "-m", "horovod_tpu.tools.doctor"] + args,
        timeout=timeout)


def test_tools_doctor_cli_json_text_and_exit_codes(tmp_path):
    report_path = tmp_path / "straggler_report.json"
    report_path.write_text(json.dumps(_straggler_report(late_rank=1)))
    res = _run_cli([str(tmp_path), "--format", "json"])
    assert res.returncode == 0, res.stdout + res.stderr
    rep = json.loads(res.stdout)
    [finding] = [f for f in rep["findings"]
                 if f["rule"] == "persistent_straggler"]
    assert finding["rank"] == 1 and "NIC" in finding["hint"]
    text = _run_cli([str(tmp_path)])
    assert text.returncode == 0
    assert "persistent_straggler rank 1" in text.stdout
    gate = _run_cli([str(tmp_path), "--fail-on-findings"])
    assert gate.returncode == 1
    empty = tmp_path / "empty"
    empty.mkdir()
    assert _run_cli([str(empty)]).returncode == 2
    assert _run_cli([str(tmp_path / "missing")]).returncode == 2


# ---------------------------------------------------------------------------
# HTTP route


def test_exporter_serves_doctor_route(monkeypatch):
    base = _free_port()
    monkeypatch.setenv("HOROVOD_METRICS_PORT", str(base))
    metrics.reset_for_tests()
    exp = metrics.maybe_start_exporter(0)
    try:
        assert exp is not None
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/doctor", timeout=5
        ).read().decode()
        rep = json.loads(body)
        assert rep["healthy"] is True and rep["source"] == "live"
        # The 404 for unknown paths now advertises both routes.
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/nope", timeout=5)
        assert err.value.code == 404
    finally:
        if exp:
            exp.close()


# ---------------------------------------------------------------------------
# Multi-process acceptance: seeded delay on rank 1 -> deterministic
# persistent-straggler Diagnosis naming rank 1, live AND offline.


@pytest.mark.slow  # tier-1 sibling: the 64-rank storm (test_simcluster.py) pins live straggler naming; rule units + CLI tests cover offline
def test_delay_chaos_doctor_names_rank1_live_and_offline(tmp_path):
    """Acceptance: a seeded FaultPlan delay on every rank-1 wire_send
    yields a persistent-straggler Diagnosis naming rank 1 — (a) live via
    rank 0's /doctor endpoint mid-run (tick-lateness evidence), and (b)
    offline via the tools.doctor CLI over the artifact dir the traced
    shutdown left behind (straggler-report evidence)."""
    trace_dir = tmp_path / "trace"
    port = _free_port()
    outs = _run_ranks("doctor", size=3, extra_env={
        "HOROVOD_TRACE_DIR": str(trace_dir),
        "HOROVOD_METRICS_PORT": str(port),
        "HOROVOD_METRICS_PUSH_CYCLES": "5",
        "HOROVOD_FAULT_PLAN": json.dumps({"seed": 7, "faults": [
            {"site": "wire_send", "action": "delay", "at": 5,
             "times": 1000000, "seconds": 0.05, "rank": 1}]}),
    })
    # (a) the live endpoint named rank 1 while the job was running.
    live = None
    for line in outs[0].splitlines():
        if line.startswith("DOCTOR_HTTP "):
            live = json.loads(line[len("DOCTOR_HTTP "):])
    assert live is not None, outs[0]
    assert live["rule"] == "persistent_straggler"
    assert live["rank"] == 1
    assert live["evidence"]["source"] == "tick_lateness"
    assert live["evidence"]["tick_lateness_p99_seconds"] >= 0.03
    assert "rank 1" in live["hint"]

    # (b) the offline CLI over the artifact dir reaches the same verdict
    # from the straggler report the lockstep shutdown wrote.
    assert (trace_dir / "straggler_report.json").exists(), \
        list(trace_dir.iterdir())
    res = _run_cli([str(trace_dir), "--format", "json"], timeout=180)
    assert res.returncode == 0, res.stdout + res.stderr
    rep = json.loads(res.stdout)
    offline = [f for f in rep["findings"]
               if f["rule"] == "persistent_straggler"]
    assert offline and all(f["rank"] == 1 for f in offline), rep
    assert offline[0]["evidence"].get("source") in (
        "straggler_report", "tick_lateness")
    assert not rep["healthy"]
