"""Backward-order bucket scheduler (round 12, ROADMAP item 3):
partitioner units, schedule-derived planning, the shared
overlap-efficiency formula, model-vs-measured validation within a
documented tolerance, the autotune dimension, and — through a real
2-rank native engine — the bit-identity acceptance contract (bucketed
vs unbucketed allreduce results are the same bytes)."""

import hashlib
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from horovod_tpu.controller.bucket_scheduler import (
    BucketScheduler,
    current_bucket_bytes,
    partition_buckets,
    plan_from_compiled,
    set_autotuned_bucket_bytes,
)
from horovod_tpu.utils.scaling_model import (
    BucketEvent,
    modeled_events_from_measured,
    overlap_efficiency_from_events,
    predicted_bucket_events,
)



# ------------------------------------------------------------- partitioner

def test_partition_preserves_order_and_size_bound():
    entries = [(f"g{i}", 100) for i in range(10)]
    buckets = partition_buckets(entries, 250)
    # Consecutive packing: 2 tensors per bucket (a third would exceed).
    assert [b.names for b in buckets] == [
        ["g0", "g1"], ["g2", "g3"], ["g4", "g5"], ["g6", "g7"],
        ["g8", "g9"]]
    assert all(b.payload_bytes <= 250 for b in buckets)
    assert [b.index for b in buckets] == list(range(5))
    # Backward production order survives flattening.
    assert [n for b in buckets for n in b.names] == [e[0] for e in entries]


def test_partition_oversize_tensor_gets_own_bucket():
    buckets = partition_buckets(
        [("small", 10), ("huge", 999), ("tail", 10)], 100)
    assert [b.names for b in buckets] == [["small"], ["huge"], ["tail"]]
    assert buckets[1].payload_bytes == 999  # bound exceeded by necessity


def test_partition_degenerate_cases():
    assert partition_buckets([], 100) == []
    # Bound swallows everything: ONE bucket — the unbucketed fall-back.
    buckets = partition_buckets([("a", 1), ("b", 2)], 1 << 30)
    assert len(buckets) == 1 and buckets[0].names == ["a", "b"]
    with pytest.raises(ValueError):
        partition_buckets([("a", 1)], 0)


# ---------------------------------------------------------------- planning

_MARKED_SCHEDULE = """\
HloModule m, is_scheduled=true

ENTRY %main (p0: f32[64,64]) -> f32[] {
  %param.0 = f32[64,64]{1,0} parameter(0)
  %fusion.1 = f32[64,64]{1,0} fusion(%param.0), kind=kLoop
  %all-reduce.1 = f32[64,64]{1,0} all-reduce(%fusion.1), channel_id=1, replica_groups={{0}}, to_apply=%sum, metadata={op_name="jit(step)/hvd.allreduce.DistributedOptimizer.2/psum" source_file="x"}
  %fusion.2 = f32[64,64]{1,0} fusion(%fusion.1), kind=kLoop
  %all-reduce.2 = f32[64]{0} all-reduce(%fusion.2), channel_id=2, replica_groups={{0}}, to_apply=%sum, metadata={op_name="jit(step)/hvd.allreduce.DistributedOptimizer.1/psum" source_file="x"}
  %fusion.3 = f32[64,64]{1,0} fusion(%fusion.2), kind=kLoop
  %all-reduce.3 = f32[64,64]{1,0} all-reduce(%fusion.3), channel_id=3, replica_groups={{0}}, to_apply=%sum, metadata={op_name="jit(step)/hvd.allreduce.DistributedOptimizer.0/psum" source_file="x"}
  %fusion.4 = f32[]{} fusion(%fusion.3), kind=kLoop
  ROOT %all-reduce.4 = f32[]{} all-reduce(%fusion.4), channel_id=4, replica_groups={{0}}, to_apply=%sum, metadata={op_name="jit(step)/loss/psum" source_file="x"}
}
"""


def test_plan_from_compiled_backward_order_and_filter():
    plan = plan_from_compiled(_MARKED_SCHEDULE, bucket_bytes=1 << 20)
    # The unmarked scalar loss psum drops; the marked 64-element bias
    # survives the size filter (gradient by construction).
    names = plan.order
    assert len(names) == 3
    assert all("hvd.allreduce" in n for n in names)
    # Schedule order IS backward production order: .2 produced first.
    assert ["DistributedOptimizer.2" in names[0],
            "DistributedOptimizer.1" in names[1],
            "DistributedOptimizer.0" in names[2]] == [True, True, True]
    # Everything fits one bucket at 1 MiB.
    assert len(plan.buckets) == 1
    # Tight bound: one 16 KiB tensor + the bias fit, the next 16 KiB
    # tensor starts its own bucket.
    tight = plan_from_compiled(_MARKED_SCHEDULE,
                               bucket_bytes=64 * 64 * 4 + 64 * 4)
    assert len(tight.buckets) == 2
    # Model inputs ride along, same count as plan entries.
    assert len(plan.groups) == 3
    assert plan.groups[0].compute_after_frac >= plan.groups[-1].compute_after_frac


# ------------------------------------------------- overlap-efficiency math

def test_overlap_efficiency_union_and_clipping():
    # Two overlapping spans + one outside the window: union = [2,6] of a
    # 10s window, clipped tail ignored.
    events = [BucketEvent(2.0, 5.0), BucketEvent(4.0, 6.0),
              BucketEvent(11.0, 12.0)]
    assert overlap_efficiency_from_events(events, 0.0, 10.0) == \
        pytest.approx(0.4)
    # Span straddling the window end clips to it.
    assert overlap_efficiency_from_events(
        [BucketEvent(8.0, 20.0)], 0.0, 10.0) == pytest.approx(0.2)
    # Degenerate window / no events -> 0, never a crash.
    assert overlap_efficiency_from_events([], 0.0, 10.0) == 0.0
    assert overlap_efficiency_from_events(
        [BucketEvent(0.0, 1.0)], 5.0, 5.0) == 0.0
    # Cap at 1.0 even when spans over-cover.
    assert overlap_efficiency_from_events(
        [BucketEvent(-5.0, 20.0)], 0.0, 10.0) == 1.0


def test_predicted_events_match_dp_step_time_model():
    from horovod_tpu.utils.scaling_model import (
        GradGroup,
        dp_step_time,
        ring_wire_bytes,
    )

    t, bw, n = 0.1, 1e9, 8
    groups = [GradGroup(10_000_000, 0.8), GradGroup(10_000_000, 0.2)]
    events = predicted_bucket_events(t, groups, n, bw)
    # The last completion IS the comm-side clock dp_step_time takes the
    # max (against compute) over — the two model views must agree.
    assert max(t, max(e.complete_s for e in events)) == pytest.approx(
        dp_step_time(t, groups, n, bw))
    assert predicted_bucket_events(t, groups, 1, bw) == []
    # Serialized engine: second launch waits for the first completion.
    same = [GradGroup(10_000_000, 1.0), GradGroup(10_000_000, 1.0)]
    e1, e2 = predicted_bucket_events(t, same, n, bw)
    assert e2.launch_s == pytest.approx(e1.complete_s)
    assert e1.complete_s - e1.launch_s == pytest.approx(
        ring_wire_bytes(n, 10_000_000) / bw)


# --------------------------------------------- model-vs-measured validation

class _SerialFakeController:
    """Async-surface fake whose single worker thread reduces one bucket
    at a time, each taking ``comm_s`` — the serial comm engine the
    scaling model assumes. Results are the arrays themselves (sum with
    itself over a 1-rank 'ring')."""

    def __init__(self, comm_s: float):
        self.comm_s = comm_s
        self._q = []
        self._cv = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name="hvd-test-fake-comm", daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            with self._cv:
                while not self._q and not self._stop:
                    self._cv.wait(0.01)
                if self._stop and not self._q:
                    return
                batch = self._q.pop(0)
            time.sleep(self.comm_s)
            for h in batch:
                h["done"] = True

    def allreduce_async(self, array, average=True, name=None):
        h = {"done": False, "array": np.asarray(array)}

        class Handle:
            def done(self_inner):
                return h["done"]

            def wait(self_inner):
                while not h["done"]:
                    time.sleep(0.001)
                return h["array"]

        with self._cv:
            # One engine slot: tensors enqueued back-to-back (a bucket)
            # ride one comm_s window together, like one fused collective.
            if self._q and not self._q[-1][0]["done"] and \
                    len(self._q[-1]) < 64 and self._batch_open:
                self._q[-1].append(h)
            else:
                self._q.append([h])
            self._cv.notify()
        return Handle()

    _batch_open = False

    def __enter__(self):
        self._batch_open = True
        return self

    def __exit__(self, *exc):
        self._batch_open = False

    def shutdown(self):
        self._stop = True
        self._thread.join(timeout=2)


def test_model_vs_measured_overlap_within_tolerance():
    """Feed the MEASURED per-bucket launch/complete times back through
    the model's event construction (uniform production spacing, the
    measured comm time) and assert predicted-vs-measured
    overlap_efficiency within 0.2 absolute — the documented tolerance
    for a sleep-based harness on a +-20%-pace box (docs/overlap.md)."""
    n_tensors, dt, comm_s = 8, 0.02, 0.03
    ctl = _SerialFakeController(comm_s)
    try:
        sched = BucketScheduler(ctl, bucket_bytes=2 * 4000, average=False)
        sched.backward_started()
        for i in range(n_tensors):
            time.sleep(dt)
            with ctl:
                sched.grad_ready(f"g{i}", np.zeros(1000, np.float32))
        results, report = sched.finish()
    finally:
        ctl.shutdown()
    assert len(results) == n_tensors
    assert report["buckets"] == 4  # 2 tensors x 4 KB per 8 KB bucket
    assert report["overlap_efficiency"] > 0.0
    # Model reconstruction from the measured schedule — the probe's
    # exact recipe, shared in scaling_model so the two can't drift.
    window = report["compute_window_s"]
    events = [BucketEvent(e["launch_s"], e["complete_s"])
              for e in report["events"]]
    modeled = modeled_events_from_measured(events, window)
    predicted = overlap_efficiency_from_events(modeled, 0.0, window)
    assert abs(predicted - report["overlap_efficiency"]) <= 0.2, (
        predicted, report)


# ----------------------------------------------------------- autotune knob

def test_bucket_bytes_joins_gp_search_and_env_pins(monkeypatch):
    from horovod_tpu.common.autotune import (
        BUCKET_BYTES_LOG2_BOUNDS,
        ParameterManager,
    )
    from horovod_tpu.common.config import Config
    from horovod_tpu.controller.autotune_glue import make_parameter_manager

    pm = ParameterManager(1 << 26, 5.0, bucket_bytes=8 << 20,
                          fixed={"fusion_threshold", "cycle_time"})
    assert pm.tunable
    rng = np.random.RandomState(3)
    lo, hi = BUCKET_BYTES_LOG2_BOUNDS
    seen = set()
    for _ in range(600):
        pm.record(1000, 1.0 + rng.rand() * 0.1)
        if pm.bucket_bytes is not None:
            assert (1 << 26) >= pm.bucket_bytes >= 1 << 20
            assert lo <= np.log2(max(1, pm.bucket_bytes)) <= hi + 1e-9
            seen.add(pm.bucket_bytes)
    assert len(seen) > 1  # the knob actually moved
    assert pm.state()["best_bucket_bytes"] is not None

    # Env pin: explicit positive HOROVOD_BUCKET_BYTES fixes the knob.
    monkeypatch.setenv("HOROVOD_AUTOTUNE", "1")
    monkeypatch.setenv("HOROVOD_BUCKET_BYTES", "4194304")
    pm2 = make_parameter_manager(Config.from_env(), tune_bucket=True)
    assert pm2.bucket_bytes == 4194304
    assert "bucket_bytes" in pm2.fixed
    for _ in range(600):
        pm2.record(1000, 1.0)
    assert pm2.bucket_bytes == 4194304
    # Auto sentinel (0/unset) joins the search seeded at the default.
    monkeypatch.setenv("HOROVOD_BUCKET_BYTES", "0")
    pm3 = make_parameter_manager(Config.from_env(), tune_bucket=True)
    assert "bucket_bytes" not in pm3.fixed
    assert pm3.bucket_bytes == 8 << 20

    # The scheduler picks up a pushed autotuned value; None restores env.
    set_autotuned_bucket_bytes(12345678)
    try:
        assert current_bucket_bytes() == 12345678
    finally:
        set_autotuned_bucket_bytes(None)
    assert current_bucket_bytes() == 8 << 20


# ----------------------------------- synced push over the cycle reply (r13)


def test_tune_reply_element_applies_bucket_on_every_rank():
    """The worker-side half of the r13 bucket sync: Controller's
    _apply_tune adopts the reply's bucket element into the process-wide
    scheduler override — the docs/overlap.md rank-0-local limitation is
    gone on the TCP-star controller. Older 3-element pushes (no bucket)
    must keep working untouched."""
    from horovod_tpu.controller.controller import Controller

    ctl = Controller.__new__(Controller)
    ctl._fusion_threshold = 1 << 26
    ctl._cycle_time_ms = 5.0
    ctl._hier_allreduce = False
    ctl._hier_allgather = False
    ctl._cache_enabled = True
    try:
        off = ctl._apply_tune((1 << 25, 2.5, {}, {"bucket_bytes": 4 << 20}))
        assert off is False
        assert ctl._fusion_threshold == 1 << 25
        assert current_bucket_bytes() == 4 << 20
        # Legacy-shaped push: no extras element, override untouched.
        ctl._apply_tune((1 << 24, 1.0, {"cache_enabled": True}))
        assert current_bucket_bytes() == 4 << 20
        # Cache-off push still reports it (the caller renegotiates).
        assert ctl._apply_tune(
            (1 << 24, 1.0, {"cache_enabled": False}, {})) is True
    finally:
        set_autotuned_bucket_bytes(None)


def test_tuned_bucket_rides_synced_cycle_reply_to_every_rank():
    """End to end over real wires: an autotuning TCP-star coordinator's
    first scored configuration ships the bucket size in the cycle
    reply's tune element, and every logical rank receives + adopts the
    SAME value — pinned on the sim harness, whose workers record the
    reply verbatim (the sync the GP needs to score a world where all
    ranks moved together)."""
    from horovod_tpu.sim import SimCluster, allreduce_spec

    try:
        with SimCluster(ranks=4, elastic=False,
                        env={"HOROVOD_AUTOTUNE": "1"}) as c:
            # warmup(3) + samples(10) scored cycles reach the first BO
            # step; one more cycle carries the push. Generous margin.
            synced = None
            for k in range(40):
                c.run_step([allreduce_spec(
                    f"t.{k}", lambda r: np.ones(256, np.float32))])
                values = {w.tuned_bucket_bytes
                          for _, w in sorted(c.workers.items())}
                if values != {None}:
                    synced = values
                    if None not in values:
                        break
            assert synced is not None, \
                "no tune push carried a bucket size within 40 steps"
            final = {w.tuned_bucket_bytes
                     for _, w in sorted(c.workers.items())}
            assert len(final) == 1 and None not in final, final
            # The pushed value is the coordinator's live GP knob: the
            # apply-side override must agree on this (rank-0) process.
            assert current_bucket_bytes() in final
    finally:
        set_autotuned_bucket_bytes(None)


# ------------------------------------------- mp acceptance (bit identity)

from mp_harness import run_script_ranks  # noqa: E402


def test_bucketed_vs_unbucketed_bit_identical():
    """2-rank native engine: the same named gradients reduced (a) one
    async enqueue at a time off the full pytree and (b) through the
    bucket scheduler must be BIT-identical — bucketing changes when
    collectives launch, never what they compute."""
    from horovod_tpu.core import bindings

    if bindings.load() is None:
        pytest.skip("native core unavailable (no toolchain)")
    results = run_script_ranks(os.path.abspath(__file__), "bucket_bitident",
                               2, timeout=120)
    for res in results:
        assert res["bucketed"] == res["unbucketed"], (
            "bucketed and unbucketed allreduce results differ bitwise")
        assert res["overlap_efficiency"] >= 0.0
        assert res["buckets"] >= 2
    # And both engines agreed with each other.
    assert results[0]["bucketed"] == results[1]["bucketed"]


def _child_bucket_bitident(rank, size, addrs):
    os.environ["HOROVOD_RING_ADDRS"] = addrs
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.controller.native import NativeController

    topo = Topology(rank=rank, size=size, local_rank=rank, local_size=size,
                    cross_rank=0, cross_size=1)
    ctl = NativeController(Config.from_env(), topo)
    grads = [(f"g.{i}",
              np.random.RandomState(10 * rank + i).randn(20_000)
              .astype(np.float32))
             for i in range(8)]

    # Path A: unbucketed — full set first, then one enqueue per tensor.
    handles = [(n, ctl.allreduce_async(g, average=True, name=n))
               for n, g in grads]
    un = {n: np.asarray(h.wait()) for n, h in handles}

    # Path B: bucketed — same names, same values, bucketed launches.
    sched = BucketScheduler(ctl, bucket_bytes=2 * 20_000 * 4)
    sched.backward_started()
    for n, g in grads:
        sched.grad_ready(n, g)
    bucketed, report = sched.finish()

    def digest(d):
        h = hashlib.sha256()
        for n in sorted(d):
            h.update(np.asarray(d[n]).tobytes())
        return h.hexdigest()

    print("RESULT " + json.dumps({
        "unbucketed": digest(un),
        "bucketed": digest(bucketed),
        "overlap_efficiency": report["overlap_efficiency"],
        "buckets": report["buckets"],
    }), flush=True)
    ctl.shutdown()


if __name__ == "__main__":
    _scenario, _rank, _size, _addrs = sys.argv[1:5]
    assert _scenario == "bucket_bitident"
    _child_bucket_bitident(int(_rank), int(_size), _addrs)
