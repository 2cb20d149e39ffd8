"""Live calibration plane (round 19, docs/capacity.md "Live
recalibration"): rolling-window telemetry, in-job drift re-fit of the
capacity curves, and the regression sentinel's doctor rule.

Four layers of coverage:

* **delta algebra** — ``set_mark``/``snapshot_delta`` watermark
  semantics: counter/histogram subtraction exactness under concurrent
  writers, watermark independence, label-set growth mid-window, and
  ``reset_for_tests`` dropping every watermark.
* **window roller** — deterministic ``roll_now`` windows, the bounded
  ring, idempotent observer registration, the
  ``hvd_metrics_windows_total`` counter, and the scrape endpoint's
  ``?window=recent`` delta view.
* **live re-fit units** — ``LiveCalibration`` recovering an exact
  injected per-rank slope (the 25%-of-truth acceptance bar, met here
  with zero measurement noise), the bounded horizon healing after a
  transient, the persisted ``capacity_live.json`` loading through the
  same ``control_plane_from_artifact`` the planner uses, the
  ``drift_report`` ratio/threshold arithmetic, and the
  ``calibration_drift`` rule's observation/window gates.
* **acceptance drive** — an in-process SimCluster with per-rank delay
  injected mid-run: the drift sentinel fires naming the negotiation
  plane within 3 windows, heals once healthy windows displace the
  horizon, leaves a loadable ``capacity_live.json``, and an undisturbed
  twin run stays silent for 20+ windows — protocheck zero on both.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.doctor.evidence import Evidence
from horovod_tpu.doctor.rules import (
    ALL_RULES,
    CAPACITY_MIN_CYCLES,
    RULE_SLUGS,
    check_calibration_drift,
    diagnose,
)
from horovod_tpu.metrics import MetricsRegistry
from horovod_tpu.metrics.registry import subtract_snapshots
from horovod_tpu.sim import SimCluster, allreduce_spec
from horovod_tpu.utils import live_calibration as lc
from horovod_tpu.utils import scaling_model as sm


@pytest.fixture(autouse=True)
def _fresh_metrics(monkeypatch):
    """Tests share one interpreter: isolate the process-global registry,
    the window roller, the live-calibration state, and the env knobs."""
    for var in ("HOROVOD_METRICS", "HOROVOD_METRICS_PORT",
                "HOROVOD_FLIGHT_RECORDER", "HOROVOD_RANK",
                "HOROVOD_METRICS_WINDOW_SECONDS",
                "HOROVOD_CAPACITY_REFIT_WINDOWS",
                "HOROVOD_CAPACITY_LIVE_DIR",
                "HOROVOD_CAPACITY_CALIBRATION"):
        monkeypatch.delenv(var, raising=False)
    metrics.reset_for_tests()
    yield
    metrics.reset_for_tests()


def _enable(monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS", "1")
    metrics.reset_for_tests()


# ---------------------------------------------------------------------------
# delta-snapshot algebra: set_mark / snapshot_delta / subtract_snapshots


def test_snapshot_delta_counters_histograms_gauges():
    r = MetricsRegistry()
    c = r.counter("hvd_d_total", "")
    h = r.histogram("hvd_d_seconds", "", buckets=(1.0, 10.0))
    g = r.gauge("hvd_d_level", "")
    c.inc(5)
    h.observe(0.5)
    h.observe(50.0)
    g.set(3)
    r.set_mark("w")
    c.inc(2)
    h.observe(5.0)
    g.set(9)
    delta = r.snapshot_delta("w")
    [[_, cval]] = delta["hvd_d_total"]["values"]
    assert cval == 2  # only what happened after the mark
    [[_, hval]] = delta["hvd_d_seconds"]["values"]
    assert hval["counts"] == [0, 1, 0] and hval["count"] == 1
    assert hval["sum"] == pytest.approx(5.0)
    # Gauges are levels, not rates: the delta passes the current value.
    [[_, gval]] = delta["hvd_d_level"]["values"]
    assert gval == 9


def test_snapshot_delta_exact_under_concurrent_writes():
    """The subtraction must be exact against whatever totals the mark
    captured: writers hammer a counter and a histogram from multiple
    threads; after they join, the delta equals exactly what was written
    after the mark (and a mid-flight delta is internally consistent)."""
    r = MetricsRegistry()
    c = r.counter("hvd_cc_total", "")
    h = r.histogram("hvd_cc_seconds", "", buckets=(1.0,))
    c.inc(7)  # pre-mark noise the delta must subtract away
    h.observe(0.5)
    r.set_mark("w")
    threads, per_thread = 8, 500
    start = threading.Barrier(threads)

    def spin():
        start.wait()
        for _ in range(per_thread):
            c.inc()
            h.observe(0.5)

    pool = [threading.Thread(target=spin, name=f"hvd-test-spin-{i}",
                             daemon=True) for i in range(threads)]
    for t in pool:
        t.start()
    # Mid-flight delta: counts may be anything from 0 to the final
    # total, but each histogram value must be self-consistent.
    mid = r.snapshot_delta("w")
    [[_, mval]] = mid["hvd_cc_seconds"]["values"]
    assert sum(mval["counts"]) == mval["count"]
    for t in pool:
        t.join()
    delta = r.snapshot_delta("w")
    [[_, cval]] = delta["hvd_cc_total"]["values"]
    assert cval == threads * per_thread
    [[_, hval]] = delta["hvd_cc_seconds"]["values"]
    assert hval["count"] == threads * per_thread
    assert hval["sum"] == pytest.approx(0.5 * threads * per_thread)


def test_snapshot_delta_watermarks_are_independent():
    r = MetricsRegistry()
    c = r.counter("hvd_wm_total", "")
    c.inc(10)
    r.set_mark("early")
    c.inc(5)
    r.set_mark("late")
    c.inc(1)
    [[_, early]] = r.snapshot_delta("early")["hvd_wm_total"]["values"]
    [[_, late]] = r.snapshot_delta("late")["hvd_wm_total"]["values"]
    assert early == 6 and late == 1
    # Re-setting one mark moves only that watermark.
    r.set_mark("early")
    c.inc(2)
    [[_, early2]] = r.snapshot_delta("early")["hvd_wm_total"]["values"]
    [[_, late2]] = r.snapshot_delta("late")["hvd_wm_total"]["values"]
    assert early2 == 2 and late2 == 3
    # A mark never set reads as a mark at process start.
    [[_, never]] = r.snapshot_delta("never-set")["hvd_wm_total"]["values"]
    assert never == 18


def test_snapshot_delta_label_growth_mid_window():
    """A label first observed after the mark has no baseline: its delta
    is its full value, while pre-existing labels subtract normally."""
    r = MetricsRegistry()
    c = r.counter("hvd_lbl_total", "", ("op",))
    c.labels("allreduce").inc(100)
    r.set_mark("w")
    c.labels("allreduce").inc(3)
    c.labels("broadcast").inc(4)  # born mid-window
    by_label = {tuple(k): v for k, v in
                r.snapshot_delta("w")["hvd_lbl_total"]["values"]}
    assert by_label[("allreduce",)] == 3
    assert by_label[("broadcast",)] == 4
    # A metric born mid-window passes through whole as well.
    r.counter("hvd_born_total", "").inc(6)
    delta = r.snapshot_delta("w")
    [[_, born]] = delta["hvd_born_total"]["values"]
    assert born == 6


def test_reset_for_tests_drops_watermarks():
    r = metrics.default_registry()
    r.counter("hvd_rst_total", "").inc(3)
    metrics.set_mark("w")
    r.counter("hvd_rst_total", "").inc(2)
    [[_, before]] = metrics.snapshot_delta("w")["hvd_rst_total"]["values"]
    assert before == 2
    metrics.reset_for_tests()
    # The mark is gone with the registry: a fresh series reads whole.
    metrics.default_registry().counter("hvd_rst_total", "").inc(7)
    [[_, after]] = metrics.snapshot_delta("w")["hvd_rst_total"]["values"]
    assert after == 7


def test_subtract_snapshots_is_pure():
    cur = {"hvd_p_total": {"type": "counter", "values": [[[], 9.0]]}}
    base = {"hvd_p_total": {"type": "counter", "values": [[[], 4.0]]}}
    delta = subtract_snapshots(cur, base)
    [[_, val]] = delta["hvd_p_total"]["values"]
    assert val == 5.0
    # Inputs alias the ring's records: they must never be mutated.
    assert cur["hvd_p_total"]["values"] == [[[], 9.0]]
    assert base["hvd_p_total"]["values"] == [[[], 4.0]]


# ---------------------------------------------------------------------------
# window roller


def test_window_roller_ring_deltas_and_observers(monkeypatch):
    _enable(monkeypatch)
    c = metrics.counter("hvd_roll_probe_total", "")
    roller = metrics.start_window_roller(interval_s=3600, capacity=3)
    assert metrics.start_window_roller(interval_s=3600) is roller  # idem.
    seen = []
    roller.add_observer(seen.append)
    roller.add_observer(seen.append)  # identical fn: registered once
    c.inc(5)
    w0 = roller.roll_now()
    assert w0["index"] == 0 and w0["duration_seconds"] >= 0.0
    [[_, val]] = w0["snapshots"][0]["hvd_roll_probe_total"]["values"]
    assert val == 5
    c.inc(2)
    w1 = roller.roll_now()
    [[_, val1]] = w1["snapshots"][0]["hvd_roll_probe_total"]["values"]
    assert val1 == 2  # deltas, not lifetime totals
    assert len(seen) == 2  # one observer call per roll
    for _ in range(3):
        roller.roll_now()
    ring = metrics.windows()
    assert [w["index"] for w in ring] == [2, 3, 4]  # bounded, oldest first
    # The roller's own roll counter landed in the registry.
    [[_, rolls]] = metrics.snapshot()[
        "hvd_metrics_windows_total"]["values"]
    assert rolls == 5
    metrics.stop_window_roller()
    assert metrics.window_roller() is None and metrics.windows() == []


def test_window_roller_observer_errors_are_swallowed(monkeypatch):
    _enable(monkeypatch)
    roller = metrics.start_window_roller(interval_s=3600)

    def boom(window):
        raise RuntimeError("telemetry must never kill the job")

    roller.add_observer(boom)
    window = roller.roll_now()  # does not raise
    assert window["index"] == 0


def test_exporter_window_query_renders_recent_deltas(monkeypatch):
    _enable(monkeypatch)
    c = metrics.counter("hvd_wq_total", "")
    c.inc(4)
    # No roller yet: the query answers with the hint, not an error.
    body = metrics.render_all("window=recent")
    assert "no completed telemetry window" in body
    roller = metrics.start_window_roller(interval_s=3600)
    roller.roll_now()
    c.inc(2)
    roller.roll_now()
    windowed = metrics.render_all("window=recent")
    assert "hvd_wq_total 2" in windowed  # the window's delta
    assert "hvd_wq_total 6" in metrics.render_all()  # lifetime view
    # End to end through the HTTP exporter's query plumbing.
    exp = metrics.MetricsExporter(0, metrics.render_all)
    try:
        url = f"http://127.0.0.1:{exp.port}/metrics?window=recent"
        assert "hvd_wq_total 2" in urllib.request.urlopen(
            url, timeout=5).read().decode()
    finally:
        exp.close()


# ---------------------------------------------------------------------------
# live re-fit units


def _hist(mean, count, buckets=(0.01, 0.1, 1.0)):
    counts = [0] * (len(buckets) + 1)
    counts[-2] = count
    return {"type": "histogram", "buckets": list(buckets),
            "values": [[[], {"counts": counts, "sum": mean * count,
                             "count": count}]]}


def _gauge(value):
    return {"type": "gauge", "values": [[[], float(value)]]}


def _window(world, neg_mean=None, neg_count=0,
            reshape_mean=None, reshape_count=0):
    snap = {"hvd_membership_size": _gauge(world)}
    if neg_count:
        snap["hvd_controller_cycle_seconds"] = _hist(neg_mean, neg_count)
    if reshape_count:
        snap["hvd_elastic_reshape_seconds"] = _hist(reshape_mean,
                                                    reshape_count)
    return {"index": 0, "start": 0.0, "end": 1.0,
            "duration_seconds": 1.0, "snapshots": {0: snap}}


def _committed(per_rank=0.0005):
    """Exact-linear committed calibration (residual 0, so the drift
    threshold sits exactly at CALIBRATION_DRIFT_FACTOR = 2x)."""
    rows = {n: {"negotiate_step_seconds": per_rank * n,
                "reshape_seconds": per_rank * n,
                "heartbeat_fanout_seconds": per_rank * n}
            for n in (8, 16, 32, 64)}
    report = sm.control_plane_report(rows, relative=True)
    return {"control_plane": {str(n): r for n, r in sorted(rows.items())},
            **report}


def test_live_refit_recovers_injected_slope_exactly():
    """The acceptance precision bar: with noise-free windows the re-fit
    recovers the injected per-rank negotiation slope exactly (well
    inside 25% of truth), and the artifact loads through the SAME
    ``control_plane_from_artifact`` the planner and doctor use."""
    truth = 0.0005
    live = lc.LiveCalibration()
    for world in (8, 16, 32):
        live.ingest_window(_window(world, neg_mean=truth * world,
                                   neg_count=30, reshape_mean=0.01,
                                   reshape_count=2))
    artifact = live.refit()
    assert artifact["source"] == "live"
    assert artifact["substrate"] == "live"
    assert artifact["windows"] == 3
    assert artifact["world_sizes"] == [8, 16, 32]
    assert artifact["observations"]["negotiation"] == 90
    cal = sm.control_plane_from_artifact(artifact)
    assert cal.negotiation_per_rank_s == pytest.approx(truth, rel=1e-6)
    assert abs(cal.negotiation_per_rank_s - truth) <= 0.25 * truth
    assert cal.source == "live"


def test_live_refit_empty_and_summary_shapes():
    live = lc.LiveCalibration()
    assert live.refit() is None and live.summary() is None
    live.ingest_window(_window(16, neg_mean=0.008, neg_count=25))
    summary = live.summary()
    assert summary["source"] == "live" and summary["world_size"] == 16
    neg = summary["planes"]["negotiation"]
    assert neg["observations"] == 25 and neg["windows"] == 1
    assert summary["planes"]["reshape"]["observations"] == 0


def test_live_horizon_heals_after_transient():
    """A slow patch ages out: once healthy windows fill the bounded
    horizon, the fitted slope returns to the healthy rate."""
    live = lc.LiveCalibration(horizon_windows=4)
    for _ in range(4):
        live.ingest_window(_window(16, neg_mean=0.080, neg_count=30))
    sick = sm.control_plane_from_artifact(live.refit())
    for _ in range(4):
        live.ingest_window(_window(16, neg_mean=0.008, neg_count=30))
    healed = sm.control_plane_from_artifact(live.refit())
    assert sick.negotiation_per_rank_s == pytest.approx(0.005, rel=1e-6)
    assert healed.negotiation_per_rank_s == pytest.approx(5e-4, rel=1e-6)
    assert live.windows_ingested == 8


def test_summary_from_artifact_round_trip_and_rejection():
    live = lc.LiveCalibration()
    for world in (8, 16):
        live.ingest_window(_window(world, neg_mean=0.0005 * world,
                                   neg_count=30))
    rebuilt = lc.summary_from_artifact(live.refit())
    direct = live.summary()
    for plane in ("negotiation", "reshape"):
        assert rebuilt["planes"][plane]["live_per_rank_s"] == \
            pytest.approx(direct["planes"][plane]["live_per_rank_s"],
                          abs=1e-12)
        assert (rebuilt["planes"][plane]["observations"]
                == direct["planes"][plane]["observations"])
    # A committed calibration must never masquerade as live evidence.
    assert lc.summary_from_artifact(_committed()) is None
    assert lc.summary_from_artifact({"source": "live"}) is None


def _live_summary(neg_slope, obs=40, windows=4, world=64,
                  reshape_slope=0.0, reshape_obs=0):
    planes = {
        "negotiation": {"live_base_s": 0.0, "live_per_rank_s": neg_slope,
                        "observations": obs, "windows": windows},
        "reshape": {"live_base_s": 0.0, "live_per_rank_s": reshape_slope,
                    "observations": reshape_obs, "windows": windows},
        "restore": {"live_base_s": 0.0, "live_per_rank_s": 0.0,
                    "observations": 0, "windows": 0},
    }
    return {"source": "live", "windows_ingested": windows,
            "horizon_windows": 8, "world_size": world, "planes": planes}


def test_drift_report_ratio_and_residual_threshold():
    report = lc.drift_report(_live_summary(0.0015), _committed(0.0005))
    neg = report["negotiation"]
    assert neg["ratio"] == pytest.approx(3.0, rel=1e-4)
    assert neg["threshold"] == pytest.approx(2.0, rel=1e-4)  # residual 0
    # A committed plane whose fit clamped to zero slope is omitted —
    # absence of an honest committed rate is not drift.
    flat = {n: {"negotiate_step_seconds": 0.0005 * n,
                "reshape_seconds": 0.01}  # constant: slope clamps to 0
            for n in (8, 16, 32, 64)}
    flat_data = {"control_plane": {str(n): r for n, r in sorted(
        flat.items())}, **sm.control_plane_report(flat, relative=True)}
    assert "reshape" not in lc.drift_report(
        _live_summary(0.0015, reshape_slope=0.01), flat_data)
    # Garbage committed data yields an empty report, never a raise.
    assert lc.drift_report(_live_summary(0.0015), {"junk": 1}) == {}


def test_calibration_drift_rule_fires_and_names_the_plane():
    ev = Evidence(capacity_calibration=_committed(),
                  live_calibration=_live_summary(0.0015))
    findings = list(check_calibration_drift(ev))
    assert len(findings) == 1
    d = findings[0]
    assert d.rule == "calibration_drift" and d.severity == "warning"
    assert d.evidence["plane"] == "negotiation"
    assert d.evidence["ratio"] == pytest.approx(3.0, rel=1e-4)
    assert d.evidence["observations"] == 40
    assert "us/rank" in d.summary and "negotiation" in d.summary
    assert "--live" in d.hint and "HOROVOD_AUTOTUNE_PRIORS" in d.hint


def test_calibration_drift_rule_gates():
    committed = _committed()
    # Below the 2x(1+residual) threshold: box-pace swing, not drift.
    mild = Evidence(capacity_calibration=committed,
                    live_calibration=_live_summary(0.00095))
    assert list(check_calibration_drift(mild)) == []
    # Thin evidence: under the per-plane observation floors.
    thin = Evidence(capacity_calibration=committed,
                    live_calibration=_live_summary(
                        0.0015, obs=CAPACITY_MIN_CYCLES - 1))
    assert list(check_calibration_drift(thin)) == []
    # A single window can't establish a trend.
    brief = Evidence(capacity_calibration=committed,
                     live_calibration=_live_summary(0.0015, windows=1))
    assert list(check_calibration_drift(brief)) == []
    # No live summary / no committed calibration: stand down.
    assert list(check_calibration_drift(Evidence(
        capacity_calibration=committed))) == []
    assert list(check_calibration_drift(Evidence(
        live_calibration=_live_summary(0.0015)))) == []


def test_calibration_drift_registered_and_offline_evidence(tmp_path):
    assert check_calibration_drift in ALL_RULES
    assert "calibration_drift" in RULE_SLUGS
    # Offline: a dead job's capacity_live.json beside a committed
    # artifact is enough for the tools/doctor path to name the drift.
    live = lc.LiveCalibration()
    for world in (8, 16):
        live.ingest_window(_window(world, neg_mean=0.0015 * world,
                                   neg_count=30))
    with open(tmp_path / "capacity_live.json", "w", encoding="utf-8") as f:
        json.dump(live.refit(), f)
    with open(tmp_path / "capacity_r17.json", "w", encoding="utf-8") as f:
        json.dump(_committed(0.0005), f)
    ev = Evidence.from_artifacts(str(tmp_path))
    assert ev.live_calibration is not None
    assert ev.capacity_calibration is not None
    assert any(d.rule == "calibration_drift" for d in diagnose(ev))


# ---------------------------------------------------------------------------
# observer wiring: on_window -> gauges, periodic re-fit, persistence


def test_on_window_drift_gauges_refit_counter_and_persist(monkeypatch,
                                                          tmp_path):
    _enable(monkeypatch)
    committed_path = tmp_path / "committed.json"
    committed_path.write_text(json.dumps(_committed(0.0005)))
    monkeypatch.setenv("HOROVOD_CAPACITY_CALIBRATION", str(committed_path))
    monkeypatch.setenv("HOROVOD_CAPACITY_LIVE_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_CAPACITY_REFIT_WINDOWS", "2")
    for world in (8, 16):
        lc.on_window(_window(world, neg_mean=3 * 0.0005 * world,
                             neg_count=30))
    snap = metrics.snapshot()
    by_label = {tuple(k): v for k, v in
                snap["hvd_capacity_drift_ratio"]["values"]}
    assert by_label[("negotiation",)] == pytest.approx(3.0, rel=1e-3)
    [[_, refits]] = snap["hvd_capacity_refits_total"]["values"]
    assert refits == 1  # every HOROVOD_CAPACITY_REFIT_WINDOWS-th window
    artifact = json.loads((tmp_path / "capacity_live.json").read_text())
    assert artifact["source"] == "live"
    cal = sm.control_plane_from_artifact(artifact)
    assert cal.negotiation_per_rank_s == pytest.approx(0.0015, rel=1e-6)


def test_persist_on_shutdown_noop_without_dir_or_data(monkeypatch,
                                                      tmp_path):
    _enable(monkeypatch)
    assert lc.persist_on_shutdown() is None  # no HOROVOD_CAPACITY_LIVE_DIR
    monkeypatch.setenv("HOROVOD_CAPACITY_LIVE_DIR", str(tmp_path))
    assert lc.persist_on_shutdown() is None  # no data yet
    lc.ensure().ingest_window(_window(8, neg_mean=0.004, neg_count=30))
    path = lc.persist_on_shutdown()
    assert path is not None and path.endswith("capacity_live.json")


def test_reseed_from_live_applies_planner_seeds(monkeypatch):
    """HOROVOD_AUTOTUNE_PRIORS=capacity + confirmed drift: the one-time
    GP re-seed assigns the planner's recommendation for the live curves
    to the tuner's next probe — and an explicit env pin still wins."""
    from horovod_tpu.common.config import Config
    from horovod_tpu.controller.autotune_glue import (
        make_parameter_manager,
        reseed_from_live,
    )

    for env in ("HOROVOD_BUCKET_BYTES", "HOROVOD_RING_CHUNK_BYTES",
                "HOROVOD_AUTOTUNE_PRIORS"):
        monkeypatch.delenv(env, raising=False)
    live = lc.ensure()
    for world in (8, 16, 32):
        live.ingest_window(_window(world, neg_mean=0.0005 * world,
                                   neg_count=30))
    pm = make_parameter_manager(Config.from_env(), tune_bucket=True,
                                tune_ring_chunk=True, world_size=1024)
    applied = reseed_from_live(pm, 1024)
    # Same arithmetic as recommend_autotune_seeds over a 0.5 ms/rank
    # negotiation curve at 1024 ranks (see test_capacity.py).
    assert applied == {"bucket_bytes": 1 << 26,
                       "ring_chunk_bytes": 1 << 20}
    assert pm.bucket_bytes == 1 << 26
    assert pm.ring_chunk_bytes == 1 << 20
    monkeypatch.setenv("HOROVOD_BUCKET_BYTES", str(4 << 20))
    pm2 = make_parameter_manager(Config.from_env(), tune_bucket=True,
                                 tune_ring_chunk=True, world_size=1024)
    applied2 = reseed_from_live(pm2, 1024)
    assert pm2.bucket_bytes == 4 << 20  # the pin survives the re-seed
    assert not applied2 or "bucket_bytes" not in applied2


def test_reseed_from_live_without_data_or_tuner():
    from horovod_tpu.controller.autotune_glue import reseed_from_live

    assert reseed_from_live(None, 64) is None  # no tuner at all
    lc.ensure()  # live instance exists but has zero windows
    assert reseed_from_live(None, 64) is None


# ---------------------------------------------------------------------------
# CLI: tools/capacity --live


def test_tools_capacity_cli_live_no_windows_exit_2(tmp_path, capsys):
    from horovod_tpu.tools.capacity import main

    rc = main(["--ranks", "64", "--live", str(tmp_path)])
    err = capsys.readouterr().err
    assert rc == 2
    assert "HOROVOD_CAPACITY_LIVE_DIR" in err
    assert "HOROVOD_METRICS_WINDOW_SECONDS" in err
    assert "drop --live" in err


def test_tools_capacity_cli_live_plan(tmp_path, capsys):
    from horovod_tpu.tools.capacity import main

    live = lc.ensure()
    for world in (8, 16, 32):
        live.ingest_window(_window(world, neg_mean=0.0005 * world,
                                   neg_count=30))
    assert lc.persist(str(tmp_path)) is not None
    rc = main(["--ranks", "4096", "--live", str(tmp_path), "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    plan = json.loads(out)
    assert plan["calibration_source"] == "live"
    assert plan["artifacts"]["control_plane"].endswith(
        "capacity_live.json")
    assert plan["planes"]["negotiation"]["predicted_seconds"] == \
        pytest.approx(0.0005 * 4096, rel=1e-3)


# ---------------------------------------------------------------------------
# acceptance drive: drift injected mid-run fires, heals, persists


def _spec(name):
    return allreduce_spec(name, lambda r: np.ones(4, np.float32))


def test_live_drift_drive_fires_heals_and_persists(tmp_path, monkeypatch):
    """ISSUE 19's acceptance drive: a healthy phase calibrates the
    committed curves, a per-rank delay injected mid-run makes the drift
    sentinel fire naming the negotiation plane within 3 windows, the
    drifted ``capacity_live.json`` loads through the planner's own
    loader with a slope ≥ threshold x the committed one, and healthy
    windows displacing the horizon heal the finding. Protocheck zero
    throughout."""
    live_dir = tmp_path / "live"
    committed_path = tmp_path / "committed.json"
    step = 0
    cluster = SimCluster(ranks=4, elastic=True, protocheck=True,
                         env={"HOROVOD_CAPACITY_LIVE_DIR": str(live_dir)})
    with cluster as c:
        # Healthy phase: calibrate this box's own baseline — asserting
        # against a hardcoded curve would test the machine, not the code.
        for _ in range(3):
            for _ in range(8):
                c.run_step([_spec(f"s.{step}")])
                step += 1
            assert c.roll_window() is not None
        healthy = lc.get().refit()
        assert healthy is not None
        committed_path.write_text(json.dumps(healthy))
        monkeypatch.setenv("HOROVOD_CAPACITY_CALIBRATION",
                           str(committed_path))
        baseline_slope = sm.control_plane_from_artifact(
            healthy).negotiation_per_rank_s
        assert not [f for f in c.doctor_report()["findings"]
                    if f["rule"] == "calibration_drift"]

        # Drift phase: rank 1's ticks arrive 150 ms late — the
        # coordinator's cycle histogram prices it, the windows carry it.
        finding = None
        for _ in range(3):
            for _ in range(2):
                c.run_step([_spec(f"s.{step}")], delays={1: 0.15})
                step += 1
            c.roll_window()
            drift = [f for f in c.doctor_report()["findings"]
                     if f["rule"] == "calibration_drift"]
            if drift:
                finding = drift[0]
                break
        assert finding is not None, \
            "calibration_drift never fired within 3 drifted windows"
        assert finding["evidence"]["plane"] == "negotiation"
        assert finding["evidence"]["ratio"] >= \
            finding["evidence"]["threshold"]
        # The drifted live artifact is loadable by the planner's loader
        # and prices the negotiation plane way above the committed curve.
        drifted_path = lc.persist(str(live_dir))
        assert drifted_path is not None
        drifted = sm.control_plane_from_artifact(
            json.loads(open(drifted_path).read()))
        assert drifted.source == "live"
        assert drifted.negotiation_per_rank_s >= 2 * baseline_slope

        # Heal phase: the delay is gone; healthy windows displace the
        # whole horizon (8 windows) and the finding clears. On an idle box
        # that is the ninth window. The baseline above is this box's speed
        # in the second it was taken, so a box that six other workers
        # slow down for a while reads drifted until it is itself again
        # (the flap under load: CHANGES.md, PR 39): what is asserted is
        # that the finding clears, and 40 windows bound the wait.
        for window in range(40):
            for _ in range(4):
                c.run_step([_spec(f"s.{step}")])
                step += 1
            c.roll_window()
            if window >= 8 and not [
                    f for f in c.doctor_report()["findings"]
                    if f["rule"] == "calibration_drift"]:
                break
        else:
            raise AssertionError(
                "calibration_drift still reported after 40 healthy windows")
        # Rank-0 shutdown persists the final (healed) re-fit too.
    final = json.loads((live_dir / "capacity_live.json").read_text())
    assert final["source"] == "live"
    assert sm.control_plane_from_artifact(final).negotiation_per_rank_s \
        < drifted.negotiation_per_rank_s
    report = cluster.protocheck_report
    assert report is not None and not report["violations"]


def test_live_drift_twin_stays_silent(tmp_path, monkeypatch):
    """The undisturbed twin: same drive, no injected delay — the drift
    sentinel must stay silent across 20+ windows judged against the
    run's own early calibration.

    Every step carries the same 50 ms on rank 1's tick, in the windows
    that calibrate and in the windows that are judged. At the sim's own
    pace a cycle is under a millisecond and the whole calibration 20 ms:
    one rank thread descheduled for a few milliseconds on a loaded host
    doubled a window, which is the machine drifting and not the code
    firing falsely. Against 50 ms a cycle the sentinel needs half a
    second of lost time inside one horizon."""
    pace = {1: 0.05}
    committed_path = tmp_path / "committed.json"
    step = 0
    cluster = SimCluster(ranks=4, elastic=True, protocheck=True)
    with cluster as c:
        for _ in range(4):
            for _ in range(3):
                c.run_step([_spec(f"t.{step}")], delays=pace)
                step += 1
            c.roll_window()
        committed_path.write_text(json.dumps(lc.get().refit()))
        monkeypatch.setenv("HOROVOD_CAPACITY_CALIBRATION",
                           str(committed_path))
        for window in range(20):
            for _ in range(3):
                c.run_step([_spec(f"t.{step}")], delays=pace)
                step += 1
            c.roll_window()
            drift = [f for f in c.doctor_report()["findings"]
                     if f["rule"] == "calibration_drift"]
            assert not drift, (window, drift)
    report = cluster.protocheck_report
    assert report is not None and not report["violations"]
