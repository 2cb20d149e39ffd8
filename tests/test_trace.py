"""Cluster-wide distributed tracing: clock-offset estimator bounds,
span-writer contract, clock-corrected merge (byte-exact golden),
straggler attribution + metrics feed, wire-level ping-pong, the offline
CLI, and the multi-process acceptance runs (3-rank merged trace on one
timebase; FaultPlan delay chaos naming the delayed rank).
"""

import json
import os
import socket
import sys
import time

import numpy as np  # noqa: F401  (parity with the other mp test modules)
import pytest

from mp_harness import run_cmd
from mp_harness import run_ranks as _run_ranks

from horovod_tpu import metrics
from horovod_tpu import trace as hvd_trace
from horovod_tpu.trace import (
    ALL_PHASES,
    PHASES,
    ClockSync,
    TraceWriter,
    attribute,
    load_offsets,
    merge_trace_dir,
)

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "golden", "merged_trace.golden")


@pytest.fixture(autouse=True)
def _fresh_metrics(monkeypatch):
    for var in ("HOROVOD_METRICS", "HOROVOD_METRICS_PORT",
                "HOROVOD_FLIGHT_RECORDER", "HOROVOD_TRACE_DIR",
                "HOROVOD_RANK"):
        monkeypatch.delenv(var, raising=False)
    metrics.reset_for_tests()
    yield
    metrics.reset_for_tests()


# ---------------------------------------------------------------------------
# Clock-offset estimator


def test_clock_sync_symmetric_rtt_recovers_offset_exactly():
    cs = ClockSync(2)
    # Worker clock +3s ahead; 5ms out, 5ms back (symmetric).
    t0, t1 = 100.0, 100.010
    peer_wall = (t0 + 0.005) + 3.0
    cs.observe(1, t0, peer_wall, t1)
    offset, unc, rtt = cs.estimate(1)
    assert offset == pytest.approx(3.0, abs=1e-12)
    assert unc == pytest.approx(0.005)
    assert rtt == pytest.approx(0.010)


def test_clock_sync_asymmetric_rtt_error_within_uncertainty():
    cs = ClockSync(2)
    # True offset +2s, but the path is 1ms out / 9ms back: the midpoint
    # estimate is wrong by 4ms — which must be inside the reported
    # uncertainty of rtt/2 = 5ms.
    t0, t1 = 50.0, 50.010
    peer_wall = (t0 + 0.001) + 2.0
    cs.observe(1, t0, peer_wall, t1)
    offset, unc, _ = cs.estimate(1)
    assert offset != pytest.approx(2.0, abs=1e-6)  # midpoint IS biased here
    assert abs(offset - 2.0) <= unc + 1e-12


def test_clock_sync_min_rtt_sample_wins_and_window_ages_out():
    cs = ClockSync(2, window=2)
    # Clean 2ms sample, then a queue-delayed 40ms one: min-RTT keeps the
    # clean estimate.
    cs.observe(1, 10.0, 10.001 + 1.0, 10.002)
    cs.observe(1, 20.0, 20.030 + 1.2, 20.040)
    offset, unc, rtt = cs.estimate(1)
    assert rtt == pytest.approx(0.002)
    assert offset == pytest.approx(1.0)
    # A second noisy sample evicts the clean one (window=2): the estimate
    # degrades but stays honest about it via the larger uncertainty.
    cs.observe(1, 30.0, 30.030 + 1.2, 30.040)
    offset, unc, rtt = cs.estimate(1)
    assert rtt == pytest.approx(0.040)
    assert unc == pytest.approx(0.020)


def test_clock_sync_negative_rtt_discarded_and_rank0_is_reference():
    cs = ClockSync(2)
    cs.observe(1, 100.0, 99.0, 99.5)  # our clock stepped: t1 < t0
    assert cs.estimate(1) is None
    assert cs.estimate(0) == (0.0, 0.0, 0.0)


def test_clock_sync_table_roundtrip_and_unsynced_ranks(tmp_path):
    cs = ClockSync(3)
    cs.observe(1, 10.0, 10.005 + 0.25, 10.010)
    path = cs.write(str(tmp_path / "clock_offsets.json"))
    table = load_offsets(path)
    assert set(table) == {0, 1, 2}
    assert table[0]["synced"] is True
    assert table[1]["synced"] is True
    assert table[1]["offset_seconds"] == pytest.approx(0.25)
    assert table[1]["uncertainty_seconds"] == pytest.approx(0.005)
    assert table[1]["samples"] == 1
    # Rank 2 was never observed: rebased with 0 but FLAGGED, not invented.
    assert table[2] == {"offset_seconds": 0.0, "uncertainty_seconds": None,
                        "rtt_seconds": None, "samples": 0, "synced": False}
    assert load_offsets(str(tmp_path / "missing.json")) == {}


# ---------------------------------------------------------------------------
# Span writer


def test_trace_writer_spans_anchor_and_fixed_vocabulary(tmp_path):
    w = TraceWriter(str(tmp_path / "trace.rank2.json"), 2)
    t0 = time.monotonic()
    w.span("negotiate", t0, t0 + 0.002, seq=7, op="grad.w")
    w.span("execute", t0 + 0.002, t0 + 0.003, seq=7, op="grad.w")
    with pytest.raises(ValueError, match="vocabulary"):
        w.span("warble", t0, t0 + 1.0)
    path = w.close()
    events = json.loads(open(path).read())
    [clock] = [e for e in events if e["name"] == "clock_sync"]
    assert clock["args"]["rank"] == 2
    assert clock["args"]["wall_anchor"] > 0
    [neg] = [e for e in events if e["name"] == "negotiate"]
    assert neg["ph"] == "X" and neg["pid"] == 2
    assert neg["args"] == {"seq": 7, "op": "grad.w"}
    assert 1500 <= neg["dur"] <= 2500
    # Distinct per-phase chrome threads, named.
    tids = {e["name"]: e["tid"] for e in events if e.get("ph") == "X"}
    assert tids["negotiate"] != tids["execute"]
    thread_names = {e["args"]["name"] for e in events
                    if e.get("name") == "thread_name"}
    # Thread metadata covers the FULL vocabulary (collective + serving
    # phases); the controller's spans only ever use the collective five.
    assert thread_names == set(ALL_PHASES)
    assert events[-1]["name"] == "trace_end"
    assert events[-1]["args"] == {"dropped_events": 0, "events": 2}
    # Idempotent close; bytes match the file (the shutdown wire push).
    assert w.close() is None
    assert w.read_bytes() == open(path, "rb").read()


def test_trace_writer_overflow_drops_with_count(tmp_path):
    w = TraceWriter(str(tmp_path / "trace.rank0.json"), 0, max_events=2)
    t = time.monotonic()
    for _ in range(5):
        w.span("execute", t, t)
    events = json.loads(open(w.close()).read())
    assert events[-1]["args"] == {"dropped_events": 3, "events": 2}


# ---------------------------------------------------------------------------
# Merge (clock-corrected, golden-pinned)


def _write_golden_inputs(tmp_path):
    """Three handcrafted rank traces + offset table with KNOWN skews:
    rank 1's clock reads 0.5s ahead, rank 2's 0.25s behind."""

    def span(rank, phase, ts, dur, seq, op):
        return {"name": phase, "ph": "X", "pid": rank,
                "tid": PHASES.index(phase) + 1, "ts": ts, "dur": dur,
                "args": {"seq": seq, "op": op}}

    def rank_file(rank, anchor, spans):
        events = [
            {"name": "clock_sync", "ph": "M", "pid": rank,
             "args": {"wall_anchor": anchor, "monotonic_origin": 0.0,
                      "rank": rank}},
            {"name": "process_name", "ph": "M", "pid": rank,
             "args": {"name": f"rank {rank}"}},
        ] + spans
        with open(os.path.join(str(tmp_path), f"trace.rank{rank}.json"),
                  "w") as f:
            json.dump(events, f)

    rank_file(0, 1000.0, [
        span(0, "negotiate", 100000, 3000, 1, "grad.w"),
        span(0, "execute", 103200, 1500, 1, "grad.w"),
        span(0, "negotiate", 300000, 2000, 2, "grad.b"),
    ])
    rank_file(1, 1000.4, [
        span(1, "negotiate", 199000, 2400, 1, "grad.w"),
        span(1, "execute", 202000, 1200, 1, "grad.w"),
        span(1, "negotiate", 399000, 1800, 2, "grad.b"),
    ])
    rank_file(2, 1000.1, [
        span(2, "negotiate", 5000, 2600, 1, "grad.w"),
        span(2, "execute", 8000, 1400, 1, "grad.w"),
        span(2, "negotiate", 160000, 2100, 2, "grad.b"),
    ])
    offsets = {
        "0": {"offset_seconds": 0.0, "uncertainty_seconds": 0.0,
              "rtt_seconds": 0.0, "samples": 0, "synced": True},
        "1": {"offset_seconds": 0.5, "uncertainty_seconds": 0.002,
              "rtt_seconds": 0.004, "samples": 12, "synced": True},
        "2": {"offset_seconds": -0.25, "uncertainty_seconds": 0.001,
              "rtt_seconds": 0.002, "samples": 12, "synced": True},
    }
    with open(os.path.join(str(tmp_path), "clock_offsets.json"), "w") as f:
        json.dump(offsets, f)


def test_merge_rebases_onto_one_timebase(tmp_path):
    _write_golden_inputs(tmp_path)
    out = merge_trace_dir(str(tmp_path))
    events = json.loads(open(out).read())
    # Corrected origins: r0 = 1000.0, r1 = 1000.4-0.5 = 999.9 (base),
    # r2 = 1000.1+0.25 = 1000.35 → shifts +100ms / 0 / +450ms.
    neg1 = {e["pid"]: e["ts"] for e in events
            if e.get("name") == "negotiate" and e["args"]["seq"] == 1}
    assert neg1 == {0: 200000, 1: 199000, 2: 455000}
    # Per-rank metadata rows exist; offsets are recorded in the output.
    clock = {e["args"]["rank"]: e["args"] for e in events
             if e.get("name") == "clock_sync"}
    assert clock[1]["applied_offset_seconds"] == 0.5
    assert clock[2]["uncertainty_seconds"] == 0.001
    assert clock[0]["synced"] is True


def test_merge_matches_golden_file(tmp_path):
    """Byte-exact pin of the merged format: event ordering, rebased
    timestamps, metadata rewriting, trailer."""
    _write_golden_inputs(tmp_path)
    out = merge_trace_dir(str(tmp_path))
    with open(GOLDEN) as f:
        assert open(out).read() == f.read()


def test_merge_without_offsets_still_works_and_flags(tmp_path):
    _write_golden_inputs(tmp_path)
    os.remove(os.path.join(str(tmp_path), "clock_offsets.json"))
    events = json.loads(open(merge_trace_dir(str(tmp_path))).read())
    clock = {e["args"]["rank"]: e["args"] for e in events
             if e.get("name") == "clock_sync"}
    assert clock[1]["applied_offset_seconds"] == 0.0
    assert clock[1]["synced"] is False
    assert clock[0]["synced"] is True  # rank 0 IS the reference clock


def test_merge_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        merge_trace_dir(str(tmp_path))


# ---------------------------------------------------------------------------
# Straggler attribution


def _synthetic_merged(late_rank=2, late_us=500, n=10, ranks=3):
    events = []
    for r in range(ranks):
        events.append({"name": "clock_sync", "ph": "M", "pid": r,
                       "args": {"rank": r, "applied_offset_seconds": 0.0,
                                "uncertainty_seconds": 0.0, "synced": True}})
    for seq in range(n):
        base = 10000 + seq * 5000
        for r in range(ranks):
            ts = base + (late_us if r == late_rank else 0)
            events.append({"name": "negotiate", "ph": "X", "pid": r,
                           "tid": 2, "ts": ts, "dur": 100,
                           "args": {"seq": seq, "op": f"t.{seq}"}})
            events.append({"name": "execute", "ph": "X", "pid": r,
                           "tid": 4, "ts": base + 1000, "dur": 500,
                           "args": {"seq": seq, "op": f"t.{seq}"}})
    return events


def test_attribution_names_late_rank_and_feeds_metrics():
    metrics.enable()
    report = attribute(_synthetic_merged(late_rank=2, late_us=500))
    assert report["collectives"] == 10
    assert report["ranks"] == [0, 1, 2]
    assert report["worst_rank"] == 2
    assert report["per_rank"]["2"]["straggler_cycles"] == 10
    assert report["per_rank"]["0"]["straggler_cycles"] == 0
    assert report["per_rank"]["2"]["lateness_p99_seconds"] \
        == pytest.approx(0.0005)
    assert report["slack_p50_seconds"] == pytest.approx(0.0005)
    assert report["worst_collectives"][0]["straggler"] == 2
    assert report["clock"]["1"]["synced"] is True
    # The registry got the two series (docs/metrics.md catalog).
    snap = metrics.snapshot()
    cycles = dict((tuple(k), v) for k, v in
                  snap["hvd_straggler_cycles_total"]["values"])
    assert cycles[("2",)] == 10
    [[_, slack]] = snap["hvd_negotiation_slack_seconds"]["values"]
    assert slack["count"] == 10
    # The compact summary reads the same registry.
    summary = hvd_trace.summary()
    assert summary["worst_rank"] == 2
    # The registry quantile interpolates inside log-spaced buckets:
    # bracket, don't pin.
    assert 0.0004 <= summary["slack_p99_seconds"] <= 0.002


def test_attribution_epsilon_filters_clock_noise():
    metrics.enable()
    report = attribute(_synthetic_merged(late_us=50))  # below 100us eps
    assert report["collectives"] == 10  # slack still measured...
    assert report["per_rank"]["2"]["straggler_cycles"] == 0  # ...not blamed
    assert report["worst_collectives"] == []
    # Registered (the slack histogram was fed) but no rank was blamed.
    snap = metrics.snapshot()
    assert snap["hvd_straggler_cycles_total"]["values"] == []


def test_attribution_summary_empty_without_data():
    assert hvd_trace.summary() == {"slack_p99_seconds": None,
                                   "worst_rank": None}


def test_attribution_exact_tie_all_ranks_blames_nobody():
    """All ranks arrive at the identical corrected timestamp: slack is
    exactly 0 — measured, but below any epsilon, so nobody is blamed."""
    report = attribute(_synthetic_merged(late_us=0), feed=False)
    assert report["collectives"] == 10
    assert report["slack_max_seconds"] == 0.0
    assert all(stats["straggler_cycles"] == 0
               for stats in report["per_rank"].values())
    assert report["worst_collectives"] == []


def test_attribution_tie_between_two_late_ranks_is_deterministic():
    """Two ranks tied for LAST above the epsilon: the blame must land on
    one deterministic rank (the tie-break is by rank id), not flip-flop
    between runs or ranks."""
    events = _synthetic_merged(late_rank=2, late_us=500)
    for ev in events:
        # Make rank 1 exactly as late as rank 2 at every negotiation.
        if ev.get("name") == "negotiate" and ev["pid"] == 1:
            ev["ts"] += 500
    report = attribute(events, feed=False)
    assert report["collectives"] == 10
    assert report["per_rank"]["2"]["straggler_cycles"] == 10
    assert report["per_rank"]["1"]["straggler_cycles"] == 0
    assert report["worst_rank"] == 2
    assert all(w["straggler"] == 2 for w in report["worst_collectives"])
    # Both late ranks still show the same lateness distribution — the
    # tie-break decides blame, not the measurements.
    assert report["per_rank"]["1"]["lateness_p99_seconds"] == \
        report["per_rank"]["2"]["lateness_p99_seconds"]


def test_attribution_epsilon_boundary_slack():
    """slack == epsilon is clock noise (not blamed); the first value
    strictly above the epsilon is. Timestamps are chosen so the slack is
    float-exact (0.5s), making the boundary comparison exact too."""
    def span(rank, ts, seq):
        return {"name": "negotiate", "ph": "X", "pid": rank, "tid": 2,
                "ts": ts, "dur": 100, "args": {"seq": seq, "op": "t"}}

    events = []
    for seq in range(3):
        base = seq * 2_000_000  # /1e6 -> exact small integers
        events += [span(0, base, seq), span(1, base, seq),
                   span(2, base + 500_000, seq)]
    at_eps = attribute(events, epsilon=0.5, feed=False)
    assert at_eps["slack_max_seconds"] == 0.5
    assert at_eps["per_rank"]["2"]["straggler_cycles"] == 0
    assert at_eps["worst_collectives"] == []
    above_eps = attribute(events, epsilon=0.499, feed=False)
    assert above_eps["per_rank"]["2"]["straggler_cycles"] == 3
    assert above_eps["worst_rank"] == 2


def test_attribution_single_rank_job_report_is_empty():
    """A single-rank job has nobody to straggle behind: the report must
    be empty — no collectives, no worst rank, no self-attribution — and
    must feed nothing into the metrics registry."""
    metrics.enable()
    events = [{"name": "clock_sync", "ph": "M", "pid": 0,
               "args": {"rank": 0, "applied_offset_seconds": 0.0,
                        "uncertainty_seconds": 0.0, "synced": True}}]
    for seq in range(10):
        events.append({"name": "negotiate", "ph": "X", "pid": 0, "tid": 2,
                       "ts": 10_000 + seq * 5_000, "dur": 100,
                       "args": {"seq": seq, "op": f"t.{seq}"}})
    report = attribute(events)
    assert report["collectives"] == 0
    assert report["worst_rank"] is None
    assert report["worst_collectives"] == []
    assert report["per_rank"]["0"]["straggler_cycles"] == 0
    assert report["slack_max_seconds"] is None
    snap = metrics.snapshot()
    assert "hvd_negotiation_slack_seconds" not in snap
    assert "hvd_straggler_cycles_total" not in snap


# ---------------------------------------------------------------------------
# Wire-level clock ping-pong (piggybacked on HEARTBEAT frames)


def test_wire_clock_ping_pong_roundtrip():
    from horovod_tpu.common.wire import Wire

    a, b = socket.socketpair()
    try:
        wa, wb = Wire(a), Wire(b)
        cs = ClockSync(2)
        wa.set_clock_callback(lambda t0, wall, t1: cs.observe(1, t0, wall,
                                                              t1))
        assert wa.send_clock_ping()
        # The ping is handled inside wb's next recv (pong sent in place)
        # and stays invisible to the payload protocol...
        wa.send_obj({"x": 1})
        assert wb.recv_obj() == {"x": 1}
        # ...and the pong is consumed inside wa's next recv.
        wb.send_obj({"y": 2})
        assert wa.recv_obj() == {"y": 2}
        offset, unc, rtt = cs.estimate(1)
        # Same process, same clock: offset ~0 within the RTT bound.
        assert abs(offset) <= unc + 1e-6
        assert 0 <= rtt < 5.0
        # A wire WITH a clock callback heartbeats as pings (the
        # coordinator's refresh path); one without stays plain.
        assert wa.try_send_heartbeat()
        wa.send_obj("fin")
        assert wb.recv_obj() == "fin"
        wb.send_obj("fin2")
        assert wa.recv_obj() == "fin2"
        assert cs.sample_count(1) == 2
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# Offline CLI


def test_tools_straggler_cli_merges_and_reports(tmp_path):
    _write_golden_inputs(tmp_path)
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.tools.straggler",
         str(tmp_path)],
        timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    report = json.loads(res.stdout)
    assert report["collectives"] == 2
    # Rebased arrivals (see test_merge_rebases_onto_one_timebase): rank 2
    # lands last on both collectives despite its ts LOOKING earliest in
    # its own file — the whole point of the clock correction.
    assert report["worst_rank"] == 2
    assert os.path.exists(os.path.join(str(tmp_path),
                                       "straggler_report.json"))
    assert os.path.exists(os.path.join(str(tmp_path), "merged_trace.json"))
    res2 = run_cmd(
        [sys.executable, "-m", "horovod_tpu.tools.straggler",
         str(tmp_path / "nothing-here")],
        timeout=120)
    assert res2.returncode != 0


# ---------------------------------------------------------------------------
# Multi-process acceptance


def _parse_snapshot(output):
    for line in output.splitlines():
        if line.startswith("METRICS_SNAPSHOT "):
            return json.loads(line[len("METRICS_SNAPSHOT "):])
    raise AssertionError(f"no METRICS_SNAPSHOT line in:\n{output}")


def test_three_rank_run_produces_merged_trace_and_report(tmp_path):
    """Acceptance: a 3-rank CPU run with HOROVOD_TRACE_DIR produces ONE
    merged trace whose per-rank rows share a timebase, plus the clock
    table and straggler report."""
    trace_dir = tmp_path / "trace"
    outs = _run_ranks("trace", size=3, extra_env={
        "HOROVOD_TRACE_DIR": str(trace_dir),
        "HOROVOD_METRICS": "1",
    })
    merged = trace_dir / "merged_trace.json"
    assert merged.exists(), list(trace_dir.iterdir())
    events = json.loads(merged.read_text())
    # One process-row per rank.
    rows = {e["args"]["name"] for e in events
            if e.get("name") == "process_name"}
    assert rows >= {"rank 0", "rank 1", "rank 2"}
    spans = [e for e in events if e.get("ph") == "X"]
    assert {e["name"] for e in spans} <= set(PHASES)  # fixed vocabulary
    # Per-collective correlation: the same seq appears on every rank, and
    # the clock-corrected arrivals for one collective sit together on the
    # merged axis (well under the job's multi-second wall span).
    arrivals = {}
    for e in spans:
        if e["name"] == "negotiate":
            arrivals.setdefault(e["args"]["seq"], {})[e["pid"]] = e["ts"]
    complete = {seq: per for seq, per in arrivals.items() if len(per) == 3}
    assert len(complete) >= 20, sorted(arrivals)
    for per in complete.values():
        assert max(per.values()) - min(per.values()) < 2_000_000
    # Every rank emitted the full phase set somewhere.
    for rank in range(3):
        phases = {e["name"] for e in spans if e["pid"] == rank}
        assert phases == set(PHASES), (rank, phases)
        assert (trace_dir / f"trace.rank{rank}.json").exists()
    # Clock table: both workers synced with bounded uncertainty.
    offsets = json.loads((trace_dir / "clock_offsets.json").read_text())
    for rank in ("1", "2"):
        assert offsets[rank]["synced"] is True, offsets
        assert offsets[rank]["samples"] >= 1
        assert offsets[rank]["uncertainty_seconds"] < 5.0
    # Straggler report written and self-consistent.
    report = json.loads((trace_dir / "straggler_report.json").read_text())
    assert report["collectives"] >= 20
    assert report["ranks"] == [0, 1, 2]
    # Attribution fed the metrics registry on rank 0.
    snap = _parse_snapshot(outs[0])
    [[_, slack]] = snap["hvd_negotiation_slack_seconds"]["values"]
    assert slack["count"] == report["collectives"]


def test_chaos_delay_rule_names_the_delayed_rank(tmp_path):
    """Acceptance: a FaultPlan delay on rank 1's wire_send makes the
    straggler report AND hvd_straggler_cycles_total name rank 1 with
    nonzero slack. EVERY send of rank 1 after the clock handshake (its
    first four: a delay there skews the offset the arrivals are corrected
    by) is held 50 ms (26 collectives, under 2 s in all), so that rank 1
    arrives last in all but one or two
    cycles on an idle box and the counts still name it when the box holds
    another rank up for a cycle or two; what one stall of another rank can
    take is the single worst collective, so that is read as a majority of
    the worst five (the flap under load: CHANGES.md, PR 39)."""
    trace_dir = tmp_path / "trace"
    outs = _run_ranks("trace", size=3, timeout=180.0, extra_env={
        "HOROVOD_TRACE_DIR": str(trace_dir),
        "HOROVOD_METRICS": "1",
        "HOROVOD_FAULT_PLAN": json.dumps({"seed": 3, "faults": [
            {"site": "wire_send", "action": "delay", "at": 5,
             "times": 400, "seconds": 0.05, "rank": 1}]}),
    })
    report = json.loads((trace_dir / "straggler_report.json").read_text())
    assert report["worst_rank"] == 1, report
    assert report["per_rank"]["1"]["straggler_cycles"] >= 3, report
    assert report["slack_max_seconds"] >= 0.03, report
    assert sum(w["straggler"] == 1
               for w in report["worst_collectives"][:5]) >= 3, report
    assert report["per_rank"]["1"]["lateness_max_seconds"] >= 0.03
    snap = _parse_snapshot(outs[0])
    cycles = dict((tuple(k), v) for k, v in
                  snap["hvd_straggler_cycles_total"]["values"])
    assert max(cycles, key=cycles.get) == ("1",), cycles
