"""bench.py supervisor: the driver must ALWAYS get one parseable JSON line.

Round-1 failure mode (VERDICT.md "What's weak" #1): the measurement child is
hard-killed by its kernel-level SIGALRM watchdog when the TPU runtime
wedges at backend init, so it can't print anything and the driver recorded
rc=142 with parsed=null. The supervisor parent never touches jax, so these
tests drive it with stubbed children and assert the contract: success line
passed through verbatim, failure line structured and phase-attributed, and
a machine with no TPU refused rather than measured on the CPU.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture()
def bench(monkeypatch):
    spec = importlib.util.spec_from_file_location("bench_under_test", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # No real sleeping/backoff in unit tests.
    monkeypatch.setattr(mod.time, "sleep", lambda s: None)
    old_handler = signal.getsignal(signal.SIGTERM)
    yield mod
    # supervisor() installs a SIGTERM handler and blocks SIGTERM once it has
    # printed its one JSON line; undo both so tests stay isolated.
    signal.signal(signal.SIGTERM, old_handler)
    signal.pthread_sigmask(signal.SIG_UNBLOCK, {signal.SIGTERM})


def _drive(bench, monkeypatch, capsys, script):
    """Run supervisor() with _run_child stubbed to pop results off `script`
    (a list of (parsed, rc, phase, err) tuples, probe/bench interleaved)."""
    calls = []

    def fake_run_child(mode, deadline):
        calls.append(mode)
        if not script:
            return None, None, "budget_exhausted", ""
        return script.pop(0)

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    rc = bench.supervisor()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(line), calls


def test_success_line_passthrough(bench, monkeypatch, capsys):
    good = {"metric": bench.METRIC, "value": 2400.0, "unit": bench.UNIT,
            "vs_baseline": 23.2}
    rc, parsed, calls = _drive(bench, monkeypatch, capsys, [
        ({"probe": "ok", "devices": 1}, 0, "ok", ""),
        (good, 0, "ok", ""),
    ])
    assert rc == 0
    assert parsed == good
    assert calls == ["probe", "bench"]


def test_wedged_backend_emits_backend_init_timeout(bench, monkeypatch,
                                                    capsys):
    rc, parsed, calls = _drive(bench, monkeypatch, capsys, [
        (None, -14, "backend_init", "watchdog armed"),
        (None, -14, "backend_init", "watchdog armed"),
    ])
    assert rc == 3
    assert parsed["value"] is None
    assert parsed["error"] == "tpu_backend_init_timeout"
    assert parsed["phase"] == "backend_init"
    assert parsed["probe_ok"] is False
    # Never burned a full bench attempt while the chip was unreachable.
    assert "bench" not in calls


def test_no_tpu_refused_at_once(bench, monkeypatch, capsys):
    """A probe that finds a non-TPU backend ends the run immediately: no
    retry, no bench attempt, and the record names the platform found."""
    rc, parsed, calls = _drive(bench, monkeypatch, capsys, [
        ({"error": "no_tpu", "platform": "cpu", "device_kind": "cpu",
          "device_count": 1}, 1, "backend_init", "NoTpuError ..."),
    ])
    assert rc == 3
    assert parsed["value"] is None
    assert parsed["error"] == "no_tpu"
    assert parsed["platform"] == "cpu"
    assert calls == ["probe"]


def test_framework_break_distinguished_from_unreachable_chip(
        bench, monkeypatch, capsys):
    """Probe succeeds but the measurement dies → error says bench_failed
    (framework problem), not a backend-init timeout, and records the phase
    reached."""
    rc, parsed, calls = _drive(bench, monkeypatch, capsys, [
        ({"probe": "ok", "devices": 1}, 0, "ok", ""),
        (None, 1, "compile_warmup", "Traceback ..."),
        ({"probe": "ok", "devices": 1}, 0, "ok", ""),
        (None, 1, "compile_warmup", "Traceback ..."),
    ])
    assert rc == 3
    assert parsed["error"] == "bench_failed"
    assert parsed["phase"] == "compile_warmup"
    assert parsed["probe_ok"] is True
    assert parsed["attempts"] == 2


def test_retry_after_transient_failure(bench, monkeypatch, capsys):
    good = {"metric": bench.METRIC, "value": 2300.0, "unit": bench.UNIT,
            "vs_baseline": 22.2}
    rc, parsed, calls = _drive(bench, monkeypatch, capsys, [
        (None, -14, "backend_init", ""),       # probe: init hiccup
        ({"probe": "ok", "devices": 1}, 0, "ok", ""),
        (good, 0, "ok", ""),
    ])
    assert rc == 0
    assert parsed["value"] == 2300.0


def test_deterministic_probe_error_stops_early(bench, monkeypatch, capsys):
    """A clean non-zero probe exit (ImportError, bad env) is not a chip
    outage: two in a row must end the run as probe_error, not burn the whole
    budget and mislabel it tpu_backend_init_timeout."""
    rc, parsed, calls = _drive(bench, monkeypatch, capsys, [
        (None, 1, "import", "ImportError: ..."),
        (None, 1, "import", "ImportError: ..."),
    ])
    assert rc == 3
    assert parsed["error"] == "probe_error"
    assert parsed["phase"] == "import"
    assert calls == ["probe", "probe"]


def test_bench_budget_exhaustion_preserves_last_real_phase(
        bench, monkeypatch, capsys):
    """When the budget dies at a bench attempt, the record must keep the
    previous real failure's phase, not the budget_exhausted sentinel."""
    rc, parsed, calls = _drive(bench, monkeypatch, capsys, [
        ({"probe": "ok", "devices": 1}, 0, "ok", ""),
        (None, 1, "compile_warmup", "Traceback ..."),
        ({"probe": "ok", "devices": 1}, 0, "ok", ""),
        (None, None, "budget_exhausted", ""),
    ])
    assert rc == 3
    assert parsed["error"] == "bench_failed"
    assert parsed["phase"] == "compile_warmup"
    assert parsed["rc"] == 1
    assert parsed["attempts"] == 1


def test_no_probe_when_bench_cannot_fit(bench, monkeypatch, capsys):
    """With less budget than one bench attempt, don't burn a wedged-probe
    timeout just to learn the bench can't run anyway."""
    monkeypatch.setattr(bench, "TOTAL_BUDGET_S",
                        bench.ATTEMPT_TIMEOUT_S)  # < ATTEMPT + 110
    rc, parsed, calls = _drive(bench, monkeypatch, capsys, [])
    assert rc == 3
    assert parsed["error"] == "budget_exhausted"
    assert calls == []


def test_child_probe_refuses_cpu_end_to_end():
    """Real subprocess round-trip of the probe child on the CPU backend:
    it must refuse (non-zero exit, error record naming the platform), not
    report the CPU as a usable measurement device."""
    env = dict(os.environ, BENCH_CHILD="probe", JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, BENCH], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    parsed = json.loads(out.stdout.strip().splitlines()[-1])
    assert parsed["error"] == "no_tpu"
    assert parsed["platform"] == "cpu"
    assert "NoTpuError" in out.stderr


def test_full_suite_cpu_rows_forced_off_the_chip(bench):
    """The rows that need no chip are started with JAX_PLATFORMS=cpu so
    they cannot take it from a later row; the chip rows are not."""
    cpu_rows = {n for n, spec in sorted(bench.FULL_ROWS.items())
                if spec.get("cpu")}
    assert "llama_tp_decode_path_proof" in cpu_rows
    assert "allreduce_bandwidth_wire_2rank" in cpu_rows
    assert "resnet50_b128" not in cpu_rows
    assert "llama_300m_serving_b8_loadgen" not in cpu_rows


# ---------------------------------------------------------------------------
# --check-trend: the regression sentinel over committed artifacts
# (round 19, docs/capacity.md "Live recalibration")


def _write_artifact(dirpath, name, data):
    path = os.path.join(str(dirpath), name)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f)
    return path


def _cal(negotiation, reshape=0.0004, heartbeat=0.0001):
    return {"calibration": {"negotiation_per_rank_s": negotiation,
                            "reshape_per_rank_s": reshape,
                            "heartbeat_per_rank_s": heartbeat}}


def test_check_trend_ok_within_tolerance(bench, tmp_path, capsys):
    base, cur = tmp_path / "base", tmp_path / "cur"
    base.mkdir(), cur.mkdir()
    _write_artifact(base, "capacity_r17.json", _cal(0.0005))
    # +20% is inside the 50% loopback-noise tolerance.
    _write_artifact(cur, "capacity_r18.json", _cal(0.0006))
    rc = bench.check_trend(str(cur), str(base))
    out = capsys.readouterr().out
    assert rc == 0
    assert "capacity_r18.json:negotiation_per_rank_s: ok" in out
    assert "vs capacity_r17.json" in out  # newest committed sibling
    assert "3 metric(s) compared, 0 regression(s)" in out


def test_check_trend_regression_exits_1_per_metric_verdicts(bench,
                                                            tmp_path,
                                                            capsys):
    base, cur = tmp_path / "base", tmp_path / "cur"
    base.mkdir(), cur.mkdir()
    _write_artifact(base, "capacity_r17.json", _cal(0.0005))
    # 3x the committed slope: a step-function regression, not noise.
    _write_artifact(cur, "capacity_r18.json", _cal(0.0015))
    rc = bench.check_trend(str(cur), str(base))
    out = capsys.readouterr().out
    assert rc == 1
    line = [ln for ln in out.splitlines()
            if "negotiation_per_rank_s" in ln][0]
    assert "REGRESSION" in line and "lower is better" in line
    assert "tolerance 50%" in line
    # The untouched metrics on the same artifact still read ok.
    assert "capacity_r18.json:reshape_per_rank_s: ok" in out
    assert "1 regression(s)" in out


def test_check_trend_higher_is_better_and_ratio_paths(bench, tmp_path,
                                                      capsys):
    base, cur = tmp_path / "base", tmp_path / "cur"
    base.mkdir(), cur.mkdir()
    # overlap efficiency regresses DOWNWARD (higher is better)...
    _write_artifact(base, "overlap_r16.json",
                    {"median_step_report": {"overlap_efficiency": 0.94}})
    _write_artifact(cur, "overlap_r17.json",
                    {"median_step_report": {"overlap_efficiency": 0.60}})
    # ...while the restore plane's sum/count RATIO stays inside 50%.
    _write_artifact(base, "elastic_restore_r15.json",
                    {"hvd_elastic_restore_seconds":
                     {"sum": 10.0, "count": 10}})
    _write_artifact(cur, "elastic_restore_r19.json",
                    {"hvd_elastic_restore_seconds":
                     {"sum": 12.0, "count": 10}})
    rc = bench.check_trend(str(cur), str(base))
    out = capsys.readouterr().out
    assert rc == 1
    assert "overlap_r17.json:overlap_efficiency: REGRESSION" in out
    assert "higher is better" in out
    assert "elastic_restore_r19.json:restore_mean_s: ok" in out


def test_check_trend_same_name_baseline_beats_newest_round(bench,
                                                           tmp_path,
                                                           capsys):
    """A re-run of an already-committed round compares against ITSELF,
    not a newer sibling whose schema may have diverged (the r10-vs-r12
    allreduce_bandwidth case)."""
    base, cur = tmp_path / "base", tmp_path / "cur"
    base.mkdir(), cur.mkdir()
    _write_artifact(base, "capacity_r17.json", _cal(0.0005))
    _write_artifact(base, "capacity_r99.json", _cal(0.0001))
    _write_artifact(cur, "capacity_r17.json", _cal(0.0006))
    rc = bench.check_trend(str(cur), str(base))
    out = capsys.readouterr().out
    # vs r99's 0.0001 this would be a 6x regression; vs the same-name
    # committed r17 it is +20%: ok.
    assert rc == 0 and "vs capacity_r17.json" in out


def test_check_trend_skips_are_reported_not_failed(bench, tmp_path,
                                                   capsys):
    base, cur = tmp_path / "base", tmp_path / "cur"
    base.mkdir(), cur.mkdir()
    # Unknown family: ignored. Known family, no committed sibling: skip.
    _write_artifact(cur, "widget_r3.json", {"value": 1.0})
    _write_artifact(cur, "capacity_r18.json", _cal(0.0005))
    # Known family, metric absent in the current artifact: skip.
    _write_artifact(base, "serving_r11.json", {"value": 2400.0})
    _write_artifact(cur, "serving_r12.json", {"other": 1})
    rc = bench.check_trend(str(cur), str(base))
    out = capsys.readouterr().out
    assert rc == 0
    assert "capacity_r18.json: skip (no committed" in out
    assert "serving_r12.json:tokens_per_s: skip (metric absent" in out
    assert "widget_r3.json" not in out
    assert "0 regression(s)" in out


def test_check_trend_cli_dispatch_exit_code(tmp_path):
    """python bench.py --check-trend DIR --baseline DIR end to end: the
    dispatch path parses args and propagates the regression exit."""
    base, cur = tmp_path / "base", tmp_path / "cur"
    base.mkdir(), cur.mkdir()
    _write_artifact(base, "capacity_r17.json", _cal(0.0005))
    _write_artifact(cur, "capacity_r18.json", _cal(0.0025))
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("BENCH_CHILD", None)
    out = subprocess.run(
        [sys.executable, BENCH, "--check-trend", str(cur),
         "--baseline", str(base)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "REGRESSION" in out.stdout
