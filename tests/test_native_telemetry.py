"""Native-engine telemetry plane (round 14, ROADMAP item 1).

The C++ engine (core/src/engine.cc) stamps trace spans into a
fixed-capacity ring behind one atomic enabled flag and keeps cumulative
counters/histograms, drained over the ctypes ABI by controller/native.py
into the SAME TraceWriter / metrics registry the Python engine feeds.

Contracts pinned here:

* cross-engine trace parity: the same 2-rank workload traced under
  HOROVOD_ENGINE=native and =python yields merged traces with the same
  phase vocabulary, per-phase args shape, and >= 20 seq-correlated
  collectives on one timebase — merge.py and the straggler attribution
  consume native traces with zero changes;
* span-ring overflow drops the OLDEST spans, counts them in the
  dropped_spans counter, and never blocks or tears a record;
* span-stamp overhead: enabled-path cost fits well inside 1% of a cycle,
  disabled-path is a single relaxed atomic load (measured AND pinned at
  the source level);
* the autotuned gradient-bucket size rides the native engine's synced
  cycle reply to every rank (the r13 token-slot tail);
* hvd_native_* counters mirror into the registry and make
  hvd.metrics.controller_health() engine-agnostic.
"""

import ctypes
import json
import os
import re

import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.core import bindings
from horovod_tpu.trace import merge_trace_dir
from horovod_tpu.trace.tracer import PHASES
from mp_harness import run_ring_ranks

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ENGINE_CC = os.path.join(REPO, "horovod_tpu", "core", "src", "engine.cc")

pytestmark = pytest.mark.skipif(
    bindings.load() is None, reason="native core unavailable (no toolchain)")


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
# In-process engine helpers (size-1: the ring is skipped, the background
# thread negotiates against itself — the cheapest real engine there is)


def _fresh_engine(cycle_ms=2.0):
    lib = bindings.load()
    lib.hvd_eng_shutdown()  # turn any previous test's engine into a husk
    key = (ctypes.c_uint8 * 4)(1, 2, 3, 4)
    rc = lib.hvd_eng_init(0, 1, b"", key, 4, float(cycle_ms), 1 << 20, 256,
                          0, 60.0, 0.0, b"", 0, 0, 0, 0, 1)
    assert rc == 0, lib.hvd_eng_last_error()
    return lib


def _run_ops(lib, n, count=64, prefix="op"):
    for i in range(n):
        a = np.ones(count, np.float32)
        shape = (ctypes.c_longlong * 1)(count)
        h = lib.hvd_eng_enqueue(
            0, f"{prefix}.{i}".encode(),
            a.ctypes.data_as(ctypes.c_void_p), shape, 1, 0, -1, None, 0)
        assert h >= 0, h
        assert lib.hvd_eng_wait(h) == 0
        lib.hvd_eng_release(h)


def test_span_ring_overflow_drops_oldest_never_tears():
    """Fill a 256-slot ring with 500 spans (100 ops x 5 phases): the
    drain returns exactly the NEWEST 256 in stamping order, the overflow
    is counted in dropped_spans, and no record is torn."""
    lib = _fresh_engine()
    try:
        lib.hvd_eng_trace_set(1, 256)
        _run_ops(lib, 100, prefix="ovf")
        c = bindings.native_counters()
        assert c["spans"] == 500, c
        assert c["spans_dropped"] == 500 - 256, c
        spans = list(bindings.drain_engine_spans())
        assert len(spans) == 256
        # Oldest dropped: the first ops' spans are gone, the last op's
        # "done" span survived; order is stamping order.
        seqs = [s[1] for s in spans]
        assert max(seqs) == 99
        assert 0 not in seqs
        assert seqs == sorted(seqs)
        for phase, seq, t0, t1, tensors, op in spans:
            # Tear check: every drained record is internally consistent.
            assert 0 <= phase < len(PHASES)
            assert t1 >= t0 > 0
            assert op.startswith("ovf.") or op == "fused", op
        # A second drain finds an empty ring; the counter is cumulative.
        assert list(bindings.drain_engine_spans()) == []
        assert bindings.native_counters()["spans_dropped"] == 244
    finally:
        lib.hvd_eng_shutdown()


def test_span_stamp_overhead_guard():
    """Measured guard: the enabled-path span stamp fits well inside 1%
    of the default 5 ms cycle even at 5 phases x 4 collectives per
    cycle; the disabled path is a single relaxed atomic load (~ns)."""
    lib = _fresh_engine()
    try:
        n = 200_000
        lib.hvd_eng_trace_set(1, 4096)
        per_on = lib.hvd_eng_span_probe(n) / n
        lib.hvd_eng_trace_set(0, 0)
        per_off = lib.hvd_eng_span_probe(n) / n
        # Enabled budget: 5 phases x 4 collectives = 20 stamps per cycle
        # <= 1% of the 5 ms default cycle -> 2.5 us per stamp. Measured
        # ~40 ns on this box; the bound absorbs a 50x slower machine.
        assert per_on <= 2.5e-6, f"enabled span stamp {per_on*1e9:.0f}ns"
        # Disabled: a relaxed atomic load + return. Measured well under a
        # nanosecond; 50 ns absorbs timer noise on a loaded box.
        assert per_off <= 50e-9, f"disabled span stamp {per_off*1e9:.1f}ns"
        list(bindings.drain_engine_spans())  # leave the ring empty
    finally:
        lib.hvd_eng_shutdown()


def test_disabled_path_is_single_atomic_load_in_source():
    """Source-level pin of the zero-overhead-off contract: stamp_span's
    FIRST statement is the relaxed atomic guard — nothing (no clock
    read, no lock) precedes it on the disabled path."""
    with open(ENGINE_CC) as f:
        src = f.read()
    m = re.search(
        r"void stamp_span\([^)]*\)\s*\{\s*\n\s*"
        r"if \(!trace_on_\.load\(std::memory_order_relaxed\)\) return;",
        src)
    assert m, ("stamp_span must open with the relaxed trace_on_ guard — "
               "the disabled path is one atomic load by contract")


def test_native_counters_mirror_and_controller_health():
    """hvd_native_* series appear in the registry snapshot and
    controller_health() reads the native engine's cycle/fused-bytes/cache
    counters — bench 'metrics' rows stop reporting zeros under native."""
    lib = _fresh_engine()
    try:
        metrics.enable()
        _run_ops(lib, 20, prefix="health")
        # Repeated name -> response-cache bypass on later rounds.
        for _ in range(5):
            _run_ops(lib, 1, prefix="cached")
        snap = metrics.snapshot()
        for name in ("hvd_native_cycles_total", "hvd_native_tensors_total",
                     "hvd_native_fused_bytes_total",
                     "hvd_native_cycle_seconds",
                     "hvd_native_execute_seconds",
                     "hvd_native_spans_dropped_total"):
            assert name in snap, sorted(snap)
        [[_, cyc]] = snap["hvd_native_cycles_total"]["values"]
        assert cyc > 0
        [[_, hist]] = snap["hvd_native_cycle_seconds"]["values"]
        assert hist["count"] > 0
        assert sum(hist["counts"]) == hist["count"]
        health = metrics.controller_health(snap)
        assert health["cycle_seconds_p50"] > 0, health
        assert health["cycle_seconds_p99"] >= health["cycle_seconds_p50"]
        assert health["fused_bytes_total"] > 0, health
        assert health["cache_hit_rate"] > 0, health  # the bypass rounds
    finally:
        lib.hvd_eng_shutdown()


def test_counters_zero_without_engine_and_slot_pin():
    """A process that never built an engine reports None (the Python
    controller merely riding the ring data plane must not grow
    hvd_native_* series), and the C slot count matches the bindings
    layout — the telemetry twin of the ABI-freshness arg-count pin."""
    lib = bindings.load()
    arr = (ctypes.c_longlong * bindings.N_NATIVE_COUNTER_SLOTS)()
    n = lib.hvd_eng_get_counters(arr, bindings.N_NATIVE_COUNTER_SLOTS)
    assert n == bindings.N_NATIVE_COUNTER_SLOTS == 65


# ---------------------------------------------------------------------------
# Multi-process acceptance


def _parse_line(output, tag):
    for line in output.splitlines():
        if line.startswith(tag + " "):
            return json.loads(line[len(tag) + 1:])
    raise AssertionError(f"no {tag} line in:\n{output}")


def _load_merged(trace_dir):
    with open(os.path.join(trace_dir, "merged_trace.json")) as f:
        return json.load(f)


def _span_shape(events):
    """The merged trace's structural shape: phase vocabulary, per-phase
    args key-sets, phase->tid mapping, metadata event names."""
    spans = [e for e in events if e.get("ph") == "X"]
    phases = sorted({e["name"] for e in spans})
    args_keys = {}
    tids = {}
    for e in spans:
        keys = args_keys.setdefault(e["name"], set())
        keys.update(e.get("args", {}))
        tids.setdefault(e["name"], e["tid"])
    meta = sorted({e["name"] for e in events if e.get("ph") == "M"})
    return {"phases": phases,
            "args": {k: sorted(v) for k, v in sorted(args_keys.items())},
            "tids": dict(sorted(tids.items())), "meta": meta}


def _correlated(events, size):
    """{seq: {rank: negotiate-arrival-us}} for seqs seen by all ranks."""
    arrivals = {}
    for e in events:
        if e.get("ph") == "X" and e["name"] == "negotiate":
            seq = e.get("args", {}).get("seq")
            if seq is not None:
                arrivals.setdefault(seq, {})[e["pid"]] = e["ts"]
    return {seq: per for seq, per in sorted(arrivals.items())
            if len(per) == size}


def test_cross_engine_trace_parity(tmp_path):
    """THE acceptance gate: the same 2-rank workload traced under the
    native and python engines produces merged traces with the identical
    phase vocabulary, per-phase args shape, and >= 20 seq-correlated
    collectives on one timebase — no python pin, zero merge changes."""
    shapes = {}
    for engine in ("native", "python"):
        trace_dir = str(tmp_path / engine)
        run_ring_ranks("trace", 2, extra_env={
            "HOROVOD_ENGINE": engine,
            "HOROVOD_TRACE_DIR": trace_dir,
            "HOROVOD_METRICS": "1",
        })
        events = _load_merged(trace_dir)
        rows = {e["args"]["name"] for e in events
                if e.get("name") == "process_name"}
        assert rows >= {"rank 0", "rank 1"}, (engine, rows)
        spans = [e for e in events if e.get("ph") == "X"]
        assert {e["name"] for e in spans} == set(PHASES), engine
        complete = _correlated(events, 2)
        assert len(complete) >= 20, (engine, sorted(complete))
        for per in sorted(complete.values(), key=str):
            # One timebase: arrivals of one collective sit together on
            # the merged axis (well under the job's wall span).
            arrivals = sorted(per.values())
            assert arrivals[-1] - arrivals[0] < 2_000_000
        # The straggler report consumed the native trace unchanged.
        report = json.loads(open(os.path.join(
            trace_dir, "straggler_report.json")).read())
        assert report["collectives"] >= 20, (engine, report)
        assert report["ranks"] == [0, 1]
        shapes[engine] = _span_shape(events)
    assert shapes["native"] == shapes["python"], (
        "merged-trace shape diverged between engines:\n"
        f"native: {shapes['native']}\npython: {shapes['python']}")


def test_native_job_mergeable_offline(tmp_path):
    """Crash-path contract: the per-rank native files merge offline with
    the stock merge (no offsets table -> workers flagged synced: false,
    visible not wrong)."""
    trace_dir = str(tmp_path / "t")
    run_ring_ranks("trace", 2, extra_env={
        "HOROVOD_ENGINE": "native",
        "HOROVOD_TRACE_DIR": trace_dir,
    })
    os.remove(os.path.join(trace_dir, "merged_trace.json"))
    merge_trace_dir(trace_dir)
    events = _load_merged(trace_dir)
    sync = {e["args"]["rank"]: e["args"]["synced"] for e in events
            if e.get("name") == "clock_sync" and e.get("ph") == "M"}
    assert sync[0] is True  # rank 0 is the timebase
    assert sync[1] is False  # no python heartbeat plane ran: flagged


def test_native_telemetry_mp_bucket_sync_and_health(tmp_path):
    """2-rank native job: rank 0's tuned-bucket push arrives on BOTH
    ranks over the synced cycle reply, controller_health() reports live
    numbers, and the hvd_native_* series are present."""
    outs = run_ring_ranks("native_telemetry", 2, extra_env={
        "HOROVOD_ENGINE": "native",
        "HOROVOD_METRICS": "1",
    })
    for rank, out in enumerate(outs):
        health = _parse_line(out, "HEALTH")
        assert health["cycle_seconds_p50"] > 0, (rank, health)
        assert health["fused_bytes_total"] > 0, (rank, health)
        snap = _parse_line(out, "METRICS_SNAPSHOT")
        [[_, bucket]] = snap["hvd_native_bucket_bytes"]["values"]
        assert bucket == 7 << 20, (rank, bucket)
        [[_, cycles]] = snap["hvd_native_cycles_total"]["values"]
        assert cycles > 0
