"""Round-16 pipelined data plane + priority bucket scheduling contracts.

Five contracts over the double-buffered native engine (docs/overlap.md):

* fill-while-on-wire: with a deterministic wire delay injected into the
  wire thread, the engine packs fused group N+1 while group N is still
  inside its wire window (span overlap), the pipeline-depth high-water
  hits 2 and slot-acquire stalls are charged to the stall counter;
* EF exactness under pipelining: the int8 error-feedback telescoping
  contract (round 10) holds unchanged through the pipelined engine,
  including the fused-group residual slicing path;
* priority-bucket-first: on a real 2-rank engine a priority-1 tensor
  enqueued LAST in a cycle completes while lower-priority peers are
  still on the wire — and every result is still exactly right (priority
  reorders completion, never values);
* wire=none byte-identity: the same burst through HOROVOD_PIPELINE=1
  and =0 produces byte-identical results on every rank — the pipelined
  stream is a reordering of the serial one, not a different computation;
* eager scheduler reporting: the BucketScheduler's eager per-tensor
  launch mode (auto-on against a pipelined controller) tags the planned
  last bucket with priority 1 and reports well-formed bucket events
  (complete after the last member was produced — the open-bucket
  completion-stamp regression).
"""

import ctypes
import hashlib
import json
import os
import sys
import time

import numpy as np
import pytest

from horovod_tpu.core import bindings
from horovod_tpu.controller.bucket_scheduler import BucketScheduler
from mp_harness import run_script_ranks

QUANT_BLOCK = 4096  # kQuantBlock in ring.cc

pytestmark = pytest.mark.skipif(
    bindings.load() is None, reason="native core unavailable (no toolchain)")

# engine.cc Phase codes (the span ring's fixed vocabulary).
PH_FUSE, PH_EXECUTE = 2, 3


def _run_two_rank(scenario, extra_env=None):
    """Spawn 2 ranks of this file's __main__ scenarios over a real TCP
    ring (the test_wire_compression harness); returns each rank's RESULT
    json."""
    return run_script_ranks(os.path.abspath(__file__), scenario, 2,
                            extra_env=extra_env)


# ------------------------------------------------- fill-while-on-wire unit

def test_engine_packs_next_group_while_previous_on_wire():
    """Deterministic pipelining proof on the in-process size-1 engine:
    HOROVOD_PIPELINE_TEST_DELAY_US stretches every wire job to 30 ms, a
    long cycle batches six tensors into one negotiation where the 8 KiB
    fusion threshold pairs them into three 2-entry fused groups, and the
    span ring then shows a later group's PH_FUSE opening before an
    earlier group's PH_EXECUTE closes — the engine thread packed N+1
    while N sat on the (fake) wire. The counter block backs it up:
    pipeline-depth high-water >= 2, and with only two fusion slots the
    third group's slot-acquire wait landed in the stall counter
    (single-entry groups wire the user buffer directly and never touch
    the slots — only FUSED groups can stall on slot acquire)."""
    lib = bindings.load()
    lib.hvd_eng_shutdown()  # turn any previous test's engine into a husk
    os.environ["HOROVOD_PIPELINE_TEST_DELAY_US"] = "30000"
    try:
        key = (ctypes.c_uint8 * 4)(1, 2, 3, 4)
        # 200 ms cycle: all six enqueues land in ONE negotiation; the
        # 8192-byte fusion threshold packs the 4 KiB tensors two per
        # fused group (three groups -> the two slots saturate). Trailing
        # 1 = pipeline on.
        rc = lib.hvd_eng_init(0, 1, b"", key, 4, 200.0, 8192, 256,
                              0, 60.0, 0.0, b"", 0, 0, 0, 0, 1)
        assert rc == 0, lib.hvd_eng_last_error()
        lib.hvd_eng_trace_set(1, 4096)
        arrays = [np.full(1024, float(i + 1), np.float32) for i in range(6)]
        handles = []
        for i, a in enumerate(arrays):
            shape = (ctypes.c_longlong * 1)(a.size)
            h = lib.hvd_eng_enqueue(
                0, f"pipe.{i}".encode(), a.ctypes.data_as(ctypes.c_void_p),
                shape, 1, 0, -1, None, 0)
            assert h >= 0, h
            handles.append(h)
        for h in handles:
            assert lib.hvd_eng_wait(h) == 0
            lib.hvd_eng_release(h)
        # Size-1 allreduce is the identity: pipelining and the fused
        # slot copy-out must not have touched the payloads.
        for i, a in enumerate(arrays):
            np.testing.assert_array_equal(
                a, np.full(1024, float(i + 1), np.float32))
        fuse, execute = {}, {}
        for phase, seq, t0, t1, _tensors, _op in \
                bindings.drain_engine_spans():
            if phase == PH_FUSE:
                fuse[seq] = (t0, t1)
            elif phase == PH_EXECUTE:
                execute[seq] = (t0, t1)
        seqs = sorted(set(fuse) & set(execute))
        assert len(seqs) >= 3, (fuse, execute)
        overlapped = [
            (a, b) for a, b in zip(seqs, seqs[1:])
            if fuse[b][0] < execute[a][1]]
        assert overlapped, (
            "no group's pack window overlapped its predecessor's wire "
            f"window: fuse={fuse} execute={execute}")
        c = bindings.native_counters()
        assert c["pipeline_depth"] >= 2, c
        assert c["pipeline_stall_us"] > 0, c
    finally:
        lib.hvd_eng_shutdown()
        del os.environ["HOROVOD_PIPELINE_TEST_DELAY_US"]


# ------------------------------------------------------ 2-rank mp contracts

def test_ef_exact_mean_survives_pipelining():
    """The round-10 telescoping contract through the PIPELINED engine:
    repeated int8-wire allreduce of a constant gradient pair (two
    tensors per step, small enough to ride one fused group — the slot
    residual-slicing path) time-averages to the exact mean."""
    results = _run_two_rank(
        "ef_pipelined", extra_env={
            "HOROVOD_RING_WIRE_DTYPE": "int8",
            "HOROVOD_PIPELINE": "1",
        })
    for res in results:
        assert res["pipeline"] is True
        for t in ("a", "b"):
            assert res[f"avg_rel_err_{t}"] < 0.3 * res[f"single_rel_err_{t}"], res


def test_priority_tensor_completes_first_two_ranks():
    """Five same-cycle single-tensor groups with a 50 ms injected wire
    delay: the priority-1 tensor enqueued LAST completes while most
    priority-0 peers are still queued behind it, the coordinator counts
    the reorder, and every value is exactly the 2-rank mean — priority
    changes completion order, never results."""
    results = _run_two_rank(
        "priority_first", extra_env={
            "HOROVOD_CYCLE_TIME": "300",
            "HOROVOD_FUSION_THRESHOLD": "4096",
            "HOROVOD_PIPELINE_TEST_DELAY_US": "50000",
        })
    for res in results:
        assert res["hi_ok"] and res["low_ok"], res
        # At the moment the priority tensor's wait() returned, at least
        # two of the four priority-0 groups were still in flight behind
        # it (each holds the wire >= 50 ms).
        assert res["lows_pending_at_hi_done"] >= 2, res
    assert results[0]["priority_jumps"] >= 1, results[0]


def test_wire_none_pipelined_byte_identical_to_serial():
    """The same mixed-size burst through HOROVOD_PIPELINE=1 and =0:
    every rank's result bytes are identical across the two engines —
    the pipelined stream reorders the serial one, bit for bit."""
    digests = {}
    for pipeline in ("1", "0"):
        results = _run_two_rank(
            "burst_digest", extra_env={
                "HOROVOD_PIPELINE": pipeline,
                "HOROVOD_FUSION_THRESHOLD": str(64 * 1024),
            })
        assert results[0]["pipeline"] is (pipeline == "1")
        assert results[0]["digest"] == results[1]["digest"]
        digests[pipeline] = results[0]["digest"]
    assert digests["1"] == digests["0"], (
        "pipelined results are not byte-identical to the serial engine's")


# -------------------------------------------------- eager scheduler (unit)

class _PipelinedFakeController:
    """Async-surface fake advertising a pipelined data plane: every
    handle resolves ``comm_s`` after ITS OWN enqueue (the wire thread
    keeps groups moving independently), and launch priorities are
    recorded for inspection."""

    pipeline_enabled = True

    def __init__(self, comm_s):
        self.comm_s = comm_s
        self.calls = []

    def allreduce_async(self, array, average=True, name=None, priority=0):
        self.calls.append((name, priority))
        done_at = time.monotonic() + self.comm_s
        arr = np.asarray(array)

        class Handle:
            def done(self_inner):
                return time.monotonic() >= done_at

            def wait(self_inner):
                rem = done_at - time.monotonic()
                if rem > 0:
                    time.sleep(rem)
                return arr

        return Handle()


def test_eager_scheduler_events_and_priority_tags():
    """Eager mode auto-on against a pipelined controller: per-tensor
    launches, the planned last bucket's members carry priority 1, and
    every reporting bucket's completion is stamped AFTER its last
    member was produced — the open-bucket regression (a bucket must not
    read complete merely because its first members' handles resolved
    while it was still accepting tensors)."""
    ctl = _PipelinedFakeController(comm_s=0.005)
    sched = BucketScheduler(ctl, bucket_bytes=4 * 4000, average=False,
                            priority_names=["g6", "g7"])
    assert sched.eager
    sched.backward_started()
    for i in range(8):
        time.sleep(0.01)
        sched.grad_ready(f"g{i}", np.zeros(1000, np.float32))
    results, report = sched.finish()
    assert len(results) == 8
    assert report["eager"] is True
    assert report["buckets"] == 2  # 4 tensors x 4 KB per 16 KB bucket
    for e in report["events"]:
        assert e["launch_s"] <= e["ready_s"] <= e["complete_s"], e
    prio = dict(ctl.calls)
    assert prio["g6"] == 1 and prio["g7"] == 1
    assert all(p == 0 for n, p in ctl.calls if n not in ("g6", "g7"))
    # Per-tensor handles resolving 5 ms after enqueue keep something in
    # flight for most of the 80 ms window.
    assert report["overlap_efficiency"] > 0.3, report


def test_batched_mode_unchanged_without_pipeline():
    """A controller WITHOUT pipeline_enabled keeps the r12 batched
    launch path: no eager attribute flip, bucket-boundary launches.
    Five 4 KB tensors against an 8 KB bound: two full buckets launch
    at-bound during backward (priority 0) and the odd tail tensor is
    still pending at finish(), whose tail flush carries priority 1."""
    ctl = _PipelinedFakeController(comm_s=0.002)
    ctl.pipeline_enabled = False
    sched = BucketScheduler(ctl, bucket_bytes=2 * 4000, average=False)
    assert not sched.eager
    for i in range(5):
        sched.grad_ready(f"h{i}", np.zeros(1000, np.float32))
    results, report = sched.finish()
    assert len(results) == 5
    assert report["eager"] is False
    assert report["buckets"] == 3
    # The finish() tail bucket carries the priority-1 tag (last backward
    # bucket, first needed by the optimizer); at-bound launches don't.
    assert ctl.calls[-1] == ("h4", 1)
    assert all(p == 0 for _, p in ctl.calls[:-1])


# --------------------------------------------------- model + stall units

def test_pipelined_model_and_stall_split_units():
    from horovod_tpu.utils.scaling_model import (
        ControlPlaneCalibration,
        overlap_efficiency_from_events,
        pipelined_modeled_events,
        stall_split_report,
    )

    events = [
        {"launch_s": 0.00, "ready_s": 0.04, "complete_s": 0.05},
        {"launch_s": 0.05, "ready_s": 0.09, "complete_s": 0.11},
        {"launch_s": 0.10, "ready_s": 0.14, "complete_s": 0.17},
        {"launch_s": 0.15, "ready_s": 0.19, "complete_s": 0.22},
    ]
    modeled = pipelined_modeled_events(events, 0.2)
    assert len(modeled) == 4
    # Bucket i spans its production slice plus the median post-ready
    # tail (here the sorted tails are 10/20/30/30 ms -> median 30 ms).
    assert modeled[0].launch_s == pytest.approx(0.0)
    assert modeled[0].complete_s == pytest.approx(0.05 + 0.03)
    assert modeled[-1].complete_s == pytest.approx(0.2 + 0.03)
    # Pipelined launches blanket the window: efficiency ~1.
    assert overlap_efficiency_from_events(modeled, 0.0, 0.2) == \
        pytest.approx(1.0)
    assert pipelined_modeled_events([], 0.2) == []

    cal = ControlPlaneCalibration(
        negotiation_base_s=0.001, negotiation_per_rank_s=0.002,
        reshape_base_s=0, reshape_per_rank_s=0,
        heartbeat_base_s=0, heartbeat_per_rank_s=0, source="unit")
    split = stall_split_report(events, cal, n=2)
    # Budget 1+2*2 = 5 ms per bucket; stalls are 10/20/30/30 ms: 5 ms of
    # each is negotiation, the rest wire.
    assert split["negotiation_budget_per_bucket_s"] == pytest.approx(0.005)
    assert split["negotiation_stall_s"] == pytest.approx(0.02)
    assert split["wire_stall_s"] == pytest.approx(0.07)
    assert split["negotiation_frac"] == pytest.approx(0.02 / 0.09, abs=1e-3)
    assert split["calibration_source"] == "unit"


def test_python_controller_prioritize_responses_unit():
    """The python engine's parity shim: stable sort of a cycle's fused
    responses by max member priority, identity when nothing is tagged."""
    from types import SimpleNamespace

    from horovod_tpu.common.message import (
        Request,
        RequestType,
        Response,
        ResponseType,
    )
    from horovod_tpu.controller.controller import Controller

    def entry(p):
        return SimpleNamespace(request=Request(
            0, RequestType.ALLREDUCE, "t", "float32", (1,), priority=p))

    table = {"a": entry(0), "b": entry(1), "c": entry(0), "d": entry(1)}
    fake = SimpleNamespace(_table=table)

    def resp(*names):
        return Response(ResponseType.ALLREDUCE, list(names))

    out = Controller._prioritize_responses(
        fake, [resp("a"), resp("c", "b"), resp("d")])
    # Priority groups first, original order preserved within each tier.
    assert [r.tensor_names for r in out] == [["c", "b"], ["d"], ["a"]]
    # No tags -> the very same list (no metrics, no copy).
    plain = [resp("a"), resp("c")]
    assert Controller._prioritize_responses(fake, plain) is plain
    # Unknown names (already-completed members) default to priority 0.
    only = [resp("zz")]
    assert Controller._prioritize_responses(fake, only) is only


# ------------------------------------------------------- child scenarios

def _child_ef_pipelined(rank, size, addrs):
    os.environ["HOROVOD_RING_ADDRS"] = addrs
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.controller.native import NativeController

    topo = Topology(rank=rank, size=size, local_rank=rank, local_size=size,
                    cross_rank=0, cross_size=1)
    ctl = NativeController(Config.from_env(), topo)
    count = 2 * QUANT_BLOCK + 33
    gs = {t: np.random.RandomState(7 + i).randn(count).astype(np.float32)
          for i, t in enumerate(("a", "b"))}
    T = 40
    acc = {t: np.zeros(count, np.float64) for t in gs}
    single = {}
    for _ in range(T):
        # Both tensors in flight together: they ride one fused group
        # (64 MB default threshold), exercising the pipelined slot's
        # residual slicing.
        handles = {t: ctl.allreduce_async(g, average=True, name=f"efp.{t}")
                   for t, g in sorted(gs.items())}
        for t, h in sorted(handles.items()):
            y = np.asarray(h.wait())
            if t not in single:
                single[t] = float(
                    np.abs(y - gs[t]).max() / np.abs(gs[t]).max())
            acc[t] += y
    out = {"pipeline": bool(ctl.pipeline_enabled)}
    for t, g in sorted(gs.items()):
        avg = acc[t] / T
        out[f"avg_rel_err_{t}"] = float(
            np.abs(avg - g).max() / np.abs(g).max())
        out[f"single_rel_err_{t}"] = single[t]
    print("RESULT " + json.dumps(out), flush=True)
    ctl.shutdown()


def _child_priority_first(rank, size, addrs):
    os.environ["HOROVOD_RING_ADDRS"] = addrs
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.controller.native import NativeController

    topo = Topology(rank=rank, size=size, local_rank=rank, local_size=size,
                    cross_rank=0, cross_size=1)
    ctl = NativeController(Config.from_env(), topo)
    n = 2048  # 8 KiB > the 4 KiB fusion threshold: every tensor its own group
    # Names chosen so the coordinator's name-ordered negotiation table
    # (std::map) would wire the priority tensor LAST — "zz.hi" sorts
    # after every "a.{i}" — making the observed hi-first completion
    # attributable ONLY to the priority sort (which must then also count
    # the reorder it performed).
    lows = [np.full(n, float(i + 1) * (rank + 1), np.float32)
            for i in range(4)]
    hi = np.full(n, 100.0 * (rank + 1), np.float32)
    low_handles = [ctl.allreduce_async(a, average=True, name=f"a.{i}")
                   for i, a in enumerate(lows)]
    hi_handle = ctl.allreduce_async(hi, average=True, name="zz.hi",
                                    priority=1)
    hi_res = np.asarray(hi_handle.wait())
    lows_pending = sum(0 if h.done() else 1 for h in low_handles)
    low_res = [np.asarray(h.wait()) for h in low_handles]
    # 2-rank average of rank-scaled constants: (v*1 + v*2) / 2 = 1.5 v.
    hi_ok = bool(np.array_equal(hi_res, np.full(n, 150.0, np.float32)))
    low_ok = all(
        np.array_equal(r, np.full(n, float(i + 1) * 1.5, np.float32))
        for i, r in enumerate(low_res))
    c = bindings.native_counters()
    print("RESULT " + json.dumps({
        "hi_ok": hi_ok, "low_ok": low_ok,
        "lows_pending_at_hi_done": lows_pending,
        "priority_jumps": int(c["priority_jumps"]) if c else 0,
    }), flush=True)
    ctl.shutdown()


def _child_burst_digest(rank, size, addrs):
    os.environ["HOROVOD_RING_ADDRS"] = addrs
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.controller.native import NativeController

    topo = Topology(rank=rank, size=size, local_rank=rank, local_size=size,
                    cross_rank=0, cross_size=1)
    ctl = NativeController(Config.from_env(), topo)
    # Mixed sizes around the 64 KiB fusion threshold: small ones fuse,
    # big ones go single — both streams exercised. Values are seeded per
    # (rank, tensor) so both pipeline runs see identical inputs.
    sizes = [4000, 24000, 1000, 50000, 4000, 12000, 30000, 2000, 8000,
             16000, 6000, 40000]
    handles = []
    for i, sz in enumerate(sizes):
        x = np.random.RandomState(1000 * rank + i).randn(sz).astype(
            np.float32)
        handles.append((f"burst.{i}",
                        ctl.allreduce_async(x, average=True,
                                            name=f"burst.{i}")))
    out = {name: np.asarray(h.wait()) for name, h in handles}
    digest = hashlib.sha256()
    for name in sorted(out):
        digest.update(out[name].tobytes())
    print("RESULT " + json.dumps({
        "pipeline": bool(ctl.pipeline_enabled),
        "digest": digest.hexdigest(),
    }), flush=True)
    ctl.shutdown()


_CHILDREN = {
    "ef_pipelined": _child_ef_pipelined,
    "priority_first": _child_priority_first,
    "burst_digest": _child_burst_digest,
}


if __name__ == "__main__":
    _scenario, _rank, _size, _addrs = sys.argv[1:5]
    _CHILDREN[_scenario](int(_rank), int(_size), _addrs)
