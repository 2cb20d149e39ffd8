"""Metric-catalog lint: keeps the telemetry namespace coherent as future
PRs add series.

Round 8 enforced the catalog with regexes; the checks now ride the
hvdlint AST framework (``horovod_tpu.analysis``, rule HVD007) — the
registration inventory comes from real ``ast`` call nodes instead of a
regex over raw source, so formatting changes can't dodge the lint. The
assertions are unchanged:

1. every registered metric name is unique (one owning call site),
   snake_case, and ``hvd_``-prefixed — now simply "HVD007 reports no
   findings over the package";
2. no module registers metrics at **import time** — statically HVD006,
   and dynamically in a clean subprocess interpreter (this test is
   immune to whatever other tests already registered in this process).
"""

import ast
import json
import os
import re
import sys

from horovod_tpu.analysis import run_lint
from horovod_tpu.analysis.rules import MetricCatalogRule
from mp_harness import child_env, run_cmd

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
PKG = os.path.join(REPO, "horovod_tpu")


def _package_sources():
    for root, _, files in os.walk(PKG):
        if "__pycache__" in root:
            continue
        for fname in files:
            if fname.endswith(".py"):
                yield os.path.join(root, fname)


def _registered_names():
    """(name, relpath) for every literal counter/gauge/histogram
    registration — the AST inventory HVD007 itself is built on."""
    names = []
    for path in _package_sources():
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for name, _node in MetricCatalogRule.registrations(tree):
            names.append((name, os.path.relpath(path, REPO)))
    return names


def test_metric_names_unique_snake_case_hvd_prefixed():
    names = _registered_names()
    assert names, "no metric registrations found — did the AST scan rot?"
    result = run_lint([PKG], root=REPO, select=["HVD007"])
    assert not result.parse_errors, result.parse_errors
    assert not result.findings, (
        "metric catalog violations (hvd_ snake_case, one owner per name):\n"
        + "\n".join(f.render() for f in result.findings))


def test_known_series_present():
    """The catalog documented in docs/metrics.md actually exists in code —
    a rename must update the docs and this pin together."""
    names = {n for n, _ in _registered_names()}
    for expected in (
        "hvd_wire_frames_sent_total",
        "hvd_wire_bytes_recv_total",
        "hvd_wire_recv_wait_seconds",
        "hvd_wire_deadline_trips_total",
        "hvd_controller_cycle_seconds",
        "hvd_controller_fused_bytes_total",
        "hvd_controller_cache_hits_total",
        "hvd_controller_cache_misses_total",
        "hvd_controller_stall_warnings_total",
        "hvd_controller_aborts_total",
        "hvd_collective_ops_total",
        "hvd_collective_bytes_total",
        "hvd_timeline_events_dropped_total",
        "hvd_retry_giveups_total",
        "hvd_launcher_restarts_total",
        "hvd_negotiation_slack_seconds",
        "hvd_straggler_cycles_total",
        "hvd_controller_tick_lateness_seconds",
        "hvd_doctor_runs_total",
        "hvd_doctor_findings",
        "hvd_membership_epoch",
        "hvd_membership_size",
        "hvd_membership_transitions_total",
        "hvd_membership_rank_departures_total",
        "hvd_sim_logical_ranks",
        "hvd_sim_driver_threads",
        "hvd_elastic_reshape_seconds",
        "hvd_elastic_restore_seconds",
        "hvd_elastic_restore_bytes_total",
        "hvd_elastic_shard_fetches_total",
        "hvd_ckpt_commits_total",
        "hvd_ckpt_dropped_commits_total",
        "hvd_ckpt_write_seconds",
        "hvd_ckpt_written_bytes_total",
        "hvd_ring_wire_bytes_total",
        "hvd_ring_compress_seconds",
        "hvd_ring_chunk_bytes",
        "hvd_overlap_buckets_total",
        "hvd_overlap_efficiency",
        "hvd_overlap_priority_jumps_total",
        "hvd_autotune_active",
        "hvd_autotune_steps_completed",
        "hvd_autotune_steps_remaining",
        "hvd_autotune_fusion_threshold_bytes",
        "hvd_autotune_cycle_time_ms",
        "hvd_autotune_best_fusion_threshold_bytes",
        "hvd_autotune_best_cycle_time_ms",
        "hvd_autotune_objective",
        "hvd_autotune_best_objective",
        "hvd_serving_queue_depth",
        "hvd_serving_queue_limit",
        "hvd_serving_active_sequences",
        "hvd_serving_blocks_in_use",
        "hvd_serving_blocks_total",
        "hvd_serving_block_utilization",
        "hvd_serving_requests_total",
        "hvd_serving_preemptions_total",
        "hvd_serving_tokens_generated_total",
        "hvd_serving_steps_total",
        "hvd_serving_ttft_seconds",
        "hvd_serving_tpot_seconds",
        "hvd_serving_prefix_hits_total",
        "hvd_serving_prefix_misses_total",
        "hvd_serving_prefix_cached_blocks",
        "hvd_serving_prefix_evictions_total",
        "hvd_serving_blocks_shared",
        "hvd_serving_cow_copies_total",
        "hvd_router_replicas",
        "hvd_router_epoch",
        "hvd_router_requests_total",
        "hvd_router_reroutes_total",
        "hvd_router_replica_departures_total",
        "hvd_router_replica_joins_total",
        "hvd_router_affinity_hits_total",
        "hvd_native_cycles_total",
        "hvd_native_tensors_total",
        "hvd_native_fused_tensors_total",
        "hvd_native_fused_bytes_total",
        "hvd_native_cache_hits_total",
        "hvd_native_cache_misses_total",
        "hvd_native_spans_total",
        "hvd_native_spans_dropped_total",
        "hvd_native_fusion_buffer_capacity_bytes",
        "hvd_native_fusion_buffer_fill_bytes",
        "hvd_native_bucket_bytes",
        "hvd_native_pipeline_depth",
        "hvd_native_pipeline_stall_seconds",
        "hvd_native_cycle_seconds",
        "hvd_native_execute_seconds",
        "hvd_metrics_windows_total",
        "hvd_capacity_drift_ratio",
        "hvd_capacity_refits_total",
    ):
        assert expected in names, f"missing from the codebase: {expected}"


def test_no_import_time_registration_static():
    """Static half of the import-time contract: HVD006 over the package
    (registration calls, env value reads, and thread spawns at module
    top level) is clean."""
    result = run_lint([PKG], root=REPO, select=["HVD006"])
    assert not result.findings, "\n".join(
        f.render() for f in result.findings)


def test_trace_phase_names_fixed_vocabulary():
    """Same discipline for trace spans as for metric names: phase strings
    at every ``.span(...)`` emission site must come from the fixed
    vocabulary — the collective pipeline (enqueue/negotiate/fuse/
    execute/done) plus the serving loop (schedule/prefill/decode); ad-hoc
    strings would silently fall out of the merge's straggler attribution
    — and every phase must actually be emitted somewhere."""
    from horovod_tpu.trace import ALL_PHASES

    # ``hvd.profiler.span`` is the SPMD tier's set-up log, another plane
    # with names of its own (test_profiler_span_names_are_documented).
    span_call = re.compile(
        r"(?<!profiler)\.span\(\s*\n?\s*[\"']([a-z_]+)[\"']")
    found = []
    for path in _package_sources():
        with open(path) as f:
            src = f.read()
        for name in span_call.findall(src):
            found.append((name, os.path.relpath(path, REPO)))
    assert found, "no trace span emission sites found — did the regex rot?"
    bad = [(n, p) for n, p in found if n not in ALL_PHASES]
    assert not bad, (
        f"ad-hoc trace phase names (the vocabulary is fixed: "
        f"{ALL_PHASES}): {bad}")
    assert {n for n, _ in found} == set(ALL_PHASES), (
        "a phase in the fixed vocabulary is never emitted: "
        f"{set(ALL_PHASES) - {n for n, _ in found}}")


def test_profiler_span_names_are_documented():
    """Every span the package plants through ``hvd.profiler.span`` /
    ``record_span`` is listed in ``docs/timeline.md``, where an operator
    reading ``hvd.profiler.spans()`` looks its name up."""
    span_call = re.compile(
        r"(?<!hvd\.)profiler\.(?:span|record_span)\(\s*[\"']([a-z_.]+)[\"']")
    found = set()     # (``hvd.profiler.span(...)`` is a docstring's example)
    for path in _package_sources():
        with open(path) as f:
            found.update(span_call.findall(f.read()))
    assert {"import", "init", "init.backend", "make_mesh"} <= found
    with open(os.path.join(REPO, "docs", "timeline.md")) as f:
        documented = f.read()
    missing = sorted(n for n in found if f"`{n}`" not in documented)
    assert not missing, f"spans not in docs/timeline.md: {missing}"


def test_no_import_time_registration():
    """Import, in a fresh interpreter, every module that CONTAINS a
    registration call (telemetry env forced ON so a lazy guard can't hide
    an eager registration bug at the on() check) and assert the default
    registry is still empty. Modules with zero registration call sites —
    proven by the static scan above — cannot register and are skipped:
    importing the tensorflow/torch adapter trees would cost ~15s of
    tier-1 budget to verify nothing."""
    with_sites = {p for _, p in _registered_names()}
    modules = []
    for path in _package_sources():
        rel = os.path.relpath(path, REPO)
        in_metrics_pkg = os.sep + "metrics" + os.sep in path
        if rel not in with_sites and not in_metrics_pkg:
            continue
        mod = rel[:-3].replace(os.sep, ".")
        if mod.endswith(".__init__"):
            mod = mod[: -len(".__init__")]
        if mod.endswith(".__main__"):
            continue  # importing a __main__ runs the CLI
        modules.append(mod)
    modules.append("horovod_tpu")  # the package root itself
    code = (
        "import importlib, json, sys\n"
        "skipped = []\n"
        f"for mod in {modules!r}:\n"
        "    try:\n"
        "        importlib.import_module(mod)\n"
        "    except Exception as exc:\n"
        "        skipped.append((mod, str(exc)[:100]))\n"
        "from horovod_tpu import metrics\n"
        "print(json.dumps({'names': metrics.default_registry().names(),\n"
        "                  'skipped': skipped}))\n")
    env = child_env()
    env["HOROVOD_METRICS"] = "1"
    res = run_cmd([sys.executable, "-c", code], timeout=180, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    report = json.loads(res.stdout.strip().splitlines()[-1])
    assert report["names"] == [], (
        "metrics registered at import time (must be lazy): "
        f"{report['names']}")
    # Optional-dep modules (mxnet/pyspark fakes, etc.) may fail to import
    # in a bare interpreter; every instrumented module must NOT be skipped.
    skipped = {m for m, _ in report["skipped"]}
    for instrumented in ("horovod_tpu.common.wire",
                        "horovod_tpu.common.timeline",
                        "horovod_tpu.common.retry",
                        "horovod_tpu.common.basics",
                        "horovod_tpu.controller.controller",
                        "horovod_tpu.run.launch",
                        "horovod_tpu.trace.straggler",
                        "horovod_tpu.doctor",
                        "horovod_tpu.controller.autotune_glue",
                        "horovod_tpu.metrics"):
        assert instrumented not in skipped, (
            f"{instrumented} failed to import: {report['skipped']}")
