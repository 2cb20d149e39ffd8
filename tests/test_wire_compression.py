"""Wire-level compression on the native ring (round 10, ROADMAP item 4).

Four contracts, each against a REAL multi-process TCP ring:

* bf16/fp16 wire paths equal a numpy-simulated cast-reduce-cast reference
  BITWISE on 2- and 3-rank rings (both converters are RNE, the schedule
  is deterministic, so exact equality is the right assertion) — and every
  rank ends with identical bytes (the owner ships exactly what it keeps).
* int8-EF: the residual returned by the ring is the exact quantization
  error this rank introduced, and carrying it into the next allreduce
  makes the time-average of a repeated constant-gradient allreduce
  converge to the exact mean (the error-feedback telescoping contract,
  docs/wire-compression.md) — asserted both at the RingBackend level and
  end-to-end through the native engine + controller residual plumbing.
* default path byte-identity: wire dtype 0 through the new entry point,
  the legacy hvd_ringh_allreduce entry point, and a numpy transcript of
  the pristine ring's deterministic reduction order all agree bitwise.
* ABI freshness: rebuild the native core from current sources and assert
  the new wire functions exist with C signatures whose arg counts match
  the ctypes declarations in bindings.py.
"""

import hashlib
import json
import os
import re
import sys

import ml_dtypes
import numpy as np
import pytest

from horovod_tpu.core import bindings
from mp_harness import free_port as _free_port
from mp_harness import run_ring_ranks, run_script_ranks

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
QUANT_BLOCK = 4096  # must match kQuantBlock in ring.cc

pytestmark = pytest.mark.skipif(
    bindings.load() is None, reason="native core unavailable (no toolchain)")


def _run_ring_job(scenario, size, extra_env=None):
    """Spawn ``size`` ranks of this file's __main__ scenarios over a real
    TCP ring; returns each rank's RESULT json."""
    return run_script_ranks(os.path.abspath(__file__), scenario, size,
                            extra_env=extra_env)


# --------------------------------------------------------------- reference

def _rank_input(rank, count):
    return np.random.RandomState(1000 + rank).randn(count).astype(np.float32)


def _int8_roundtrip(a):
    """quantize+dequantize exactly like ring.cc wire_compress WIRE_I8:
    per 4096-element block anchored at the segment start, f32 scale
    amax/127, RNE quantize with clamp, f32 dequant."""
    out = np.empty_like(a)
    for b in range(0, a.size, QUANT_BLOCK):
        blk = a[b:b + QUANT_BLOCK]
        amax = np.float32(np.max(np.abs(blk))) if blk.size else np.float32(0)
        scale = np.float32(amax / np.float32(127.0))
        if scale == 0:
            out[b:b + QUANT_BLOCK] = 0
            continue
        inv = np.float32(np.float32(1.0) / scale)
        v = np.clip(blk * inv, np.float32(-127.0), np.float32(127.0))
        q = np.rint(v).astype(np.int8)
        out[b:b + QUANT_BLOCK] = q.astype(np.float32) * scale
    return out


def _wire_roundtrip(a, wire):
    if wire == "bf16":
        return a.astype(ml_dtypes.bfloat16).astype(np.float32)
    if wire == "fp16":
        return a.astype(np.float16).astype(np.float32)
    if wire == "int8":
        return _int8_roundtrip(a)
    return a


def _simulate_ring(xs, wire):
    """Numpy transcript of ring.cc's schedule: segment s starts at rank s
    (step-0 sender), each hop adds the receiver's contribution to the
    wire-roundtripped partial in f32, and the final owner quantizes once
    more before the (verbatim-relay) allgather."""
    size = len(xs)
    count = xs[0].size
    base_len, rem = divmod(count, size)

    def seg(s):
        off = s * base_len + min(s, rem)
        return slice(off, off + base_len + (1 if s < rem else 0))

    out = np.empty(count, np.float32)
    for s in range(size):
        v = xs[s][seg(s)].copy()
        for t in range(1, size):
            v = xs[(s + t) % size][seg(s)] + _wire_roundtrip(v, wire)
        out[seg(s)] = _wire_roundtrip(v, wire)
    return out


# ------------------------------------------------------------------- tests

@pytest.mark.parametrize("size", [2, 3])
def test_wire_paths_match_reference_bitwise(size):
    # 50021 elements: uneven segments AND a partial int8 quant block.
    count = 50021
    results = _run_ring_job("wire_result", size,
                            extra_env={"HVD_TEST_COUNT": str(count)})
    xs = [_rank_input(r, count) for r in range(size)]
    for wire in ("none", "bf16", "fp16", "int8"):
        expect = _simulate_ring(xs, wire)
        want = hashlib.sha256(expect.tobytes()).hexdigest()
        for rank, res in enumerate(results):
            assert res[wire] == want, (
                f"{wire} rank {rank}: ring result != numpy-simulated "
                f"cast-reduce-cast reference")
    # All ranks bit-identical is implied by matching one reference hash.


def test_default_path_byte_identity_two_entry_points():
    """Wire dtype 0 through hvd_ringh_allreduce_wire, the legacy
    hvd_ringh_allreduce, and the pristine-ring numpy transcript agree
    bitwise — HOROVOD_RING_WIRE_DTYPE unset is today's ring exactly."""
    count = 50021
    results = _run_ring_job("wire_result", 2,
                            extra_env={"HVD_TEST_COUNT": str(count)})
    xs = [_rank_input(r, count) for r in range(2)]
    pristine = hashlib.sha256(
        _simulate_ring(xs, "none").tobytes()).hexdigest()
    for res in results:
        assert res["none"] == pristine
        assert res["legacy_entry"] == pristine


def test_int8_error_feedback_converges_to_exact_mean():
    results = _run_ring_job("wire_ef", 2)
    for res in results:
        # The carried residual makes the T-step average of a repeated
        # constant-gradient allreduce telescope to the exact mean:
        # error after T steps ~ initial quantization error / T.
        assert res["ef_rel_err"] < 3.0 * res["single_rel_err"] / res["T"], (
            res)
        # Without feedback the quantization bias is constant: no decay.
        assert res["noef_rel_err"] > 10 * res["ef_rel_err"], res
        # The residual really is x - dequant(quant(x)) of the bytes sent:
        # it is bounded by half a quant step of the largest block.
        assert res["residual_max"] <= res["quant_step_bound"], res


def test_native_engine_ef_end_to_end():
    """int8 EF through the full stack: HOROVOD_RING_WIRE_DTYPE=int8 ->
    NativeController -> engine enqueue residual plumbing -> ring. Also
    proves the wire savings surface in hvd.metrics.controller_health()."""
    results = _run_ring_job(
        "native_ef", 2,
        extra_env={"HOROVOD_RING_WIRE_DTYPE": "int8",
                   "HOROVOD_CYCLE_TIME": "1"})
    for res in results:
        assert res["avg_rel_err"] < 0.3 * res["single_rel_err"], res
        # int8 wire quarters the f32 bytes (+ ~0.1% scale headers).
        assert res["wire_savings_frac"] > 0.7, res
        assert res["wire_bytes_total"] > 0, res
        assert res["dup_rejected"], res
        assert res["dup_untouched"], res
        assert res["drop_completed"], res
        assert res["drop_ef_resumed"], res


def test_residual_zeroed_when_no_quantization():
    """A residual buffer handed to a non-quantizing call (bf16 wire, or
    wire none) must come back zeroed — stale error must never leak into
    the next round."""
    results = _run_ring_job("wire_residual_zero", 2)
    for res in results:
        assert res["bf16_residual_max"] == 0.0
        assert res["none_residual_max"] == 0.0


def test_single_rank_ring_zeroes_residual():
    ring = bindings.RingBackend(0, 1, f"127.0.0.1:{_free_port()}", b"solo")
    try:
        x = np.ones(QUANT_BLOCK + 5, np.float32)
        res = np.full(x.size, 9.0, np.float32)
        ring.allreduce_(x, False, wire_dtype=3, residual=res)
        assert np.all(res == 0.0)
        np.testing.assert_array_equal(x, np.ones(x.size, np.float32))
    finally:
        ring.shutdown()


def test_chunk_bytes_setter_clamps_and_rounds():
    lib = bindings.load()
    lib.hvd_ring_set_chunk_bytes(1)
    assert lib.hvd_ring_get_chunk_bytes() == 16 * 1024  # floor
    lib.hvd_ring_set_chunk_bytes(300 * 1024 + 3)
    assert lib.hvd_ring_get_chunk_bytes() % 8 == 0  # element-aligned
    lib.hvd_ring_set_chunk_bytes(1 << 40)
    assert lib.hvd_ring_get_chunk_bytes() == 64 * 1024 * 1024  # ceil
    lib.hvd_ring_set_chunk_bytes(256 * 1024)  # restore default
    assert bindings.wire_stats()["chunk_bytes"] == 256 * 1024


def _run_engine_job(scenario, size, extra_env):
    """Full-stack job (mp_worker scenarios) with the ring data plane:
    rendezvous star + HOROVOD_RING_ADDRS, engine picked by extra_env."""
    run_ring_ranks(scenario, size, timeout=120.0, extra_env=extra_env)


@pytest.mark.parametrize("engine,wire", [
    ("native", "bf16"), ("native", "fp16"), ("python", "bf16")])
def test_wire_exact_through_full_stack(engine, wire):
    """HOROVOD_RING_WIRE_DTYPE through hvd.init + controller + engine on
    exactly-representable values: compressed wire, exact results."""
    _run_engine_job("wire_exact", 2, {
        "HOROVOD_ENGINE": engine,
        "HOROVOD_RING_WIRE_DTYPE": wire,
    })


def test_python_engine_int8_downgrades_loudly():
    """int8 under the Python engine keeps the uncompressed wire (EF lives
    in the native controller) and says so once; results stay exact."""
    _run_engine_job("wire_exact", 2, {
        "HOROVOD_ENGINE": "python",
        "HOROVOD_RING_WIRE_DTYPE": "int8",
    })


# ----------------------------------------------------------- ABI freshness

def _c_arg_count(source, func):
    m = re.search(re.escape(func) + r"\s*\(([^)]*)\)", source, re.DOTALL)
    assert m, f"{func} not found in native sources"
    args = m.group(1).strip()
    return 0 if not args else args.count(",") + 1


@pytest.mark.slow
def test_build_freshness_and_abi_matches_bindings():
    """Recompile the native core from the CURRENT sources (build() is
    mtime-cached: stale .so -> real g++ run) and assert the wire ABI —
    the new wire-dtype/residual args included — matches what bindings.py
    declares, by symbol presence and by C-source arg count vs ctypes
    argtypes length. Catches the classic drift: editing ring.cc/engine.cc
    without updating the ctypes layer (or vice versa).

    @slow since the hvdabi round: tier-1 gets the same coverage (and
    more — per-arg ctype compatibility, restype, CoreApi fn-pointer
    types) from the static analyzer without the g++ seconds
    (tests/test_abicheck.py); this rebuild-and-diff variant stays as
    the ground-truth cross-check that the *compiled* .so agrees too."""
    path = bindings.build()  # recompiles iff any .cc/.h is newer
    assert os.path.exists(path)
    lib = bindings.load()
    src = ""
    src_dir = os.path.join(REPO, "horovod_tpu", "core", "src")
    for fname in sorted(os.listdir(src_dir)):
        if fname.endswith((".cc", ".h")):
            with open(os.path.join(src_dir, fname)) as f:
                src += f.read()
    # Flat-ring wire ABI (round 10), the hierarchical entry points
    # (round 12: per-link wire stats, link tagging, rate cap, the
    # handle-ring collectives the two-level plane is built from) AND the
    # round-14 telemetry plane (span drain, counters, trace flag, synced
    # bucket slot, overhead probe).
    for func in ("hvd_ring_allreduce_wire", "hvd_ringh_allreduce_wire",
                 "hvd_eng_init", "hvd_eng_enqueue",
                 "hvd_ring_get_wire_stats", "hvd_ring_get_wire_stats_link",
                 "hvd_ringh_set_link", "hvd_ringh_set_rate",
                 "hvd_ringh_allreduce", "hvd_ringh_allgather",
                 "hvd_ringh_broadcast", "hvd_ringh_create",
                 "hvd_eng_get_spans", "hvd_eng_get_counters",
                 "hvd_eng_trace_set", "hvd_eng_set_tuned_bucket",
                 "hvd_eng_span_probe", "hvd_eng_active"):
        assert hasattr(lib, func)
        declared = len(getattr(lib, func).argtypes)
        in_source = _c_arg_count(src, func)
        assert declared == in_source, (
            f"{func}: bindings.py declares {declared} args, native source "
            f"defines {in_source} — the ctypes ABI drifted")
    # The wire-dtype args specifically: hvd_eng_init grew to 14 args in
    # round 10, to 16 in round 12 (hierarchical local/cross wire dtypes)
    # and to 17 in round 16 (trailing pipeline-enable flag); enqueue grew
    # to 8 in round 10 and to 9 in round 16 (trailing launch priority).
    # Round 14 added telemetry as NEW entry points, so both stay pinned.
    assert len(lib.hvd_eng_init.argtypes) == 17
    assert len(lib.hvd_eng_enqueue.argtypes) == 9
    # Telemetry counter-slot layout: the C side's slot count must match
    # the bindings' mirror (engine.cc CounterSlot <-> NATIVE_COUNTER_*).
    # Round 16 grew the block by three scalars (pipeline depth/stall,
    # priority jumps) — 65 slots; re-pinned on BOTH sides so a one-sided
    # edit fails here, not as silently shifted histogram bins.
    assert bindings.N_NATIVE_COUNTER_SLOTS == 65
    import ctypes as _ct

    arr = (_ct.c_longlong * bindings.N_NATIVE_COUNTER_SLOTS)()
    assert (lib.hvd_eng_get_counters(arr, bindings.N_NATIVE_COUNTER_SLOTS)
            == bindings.N_NATIVE_COUNTER_SLOTS)


# ---------------------------------------------------------- hierarchical
# (round 12: per-link wire dtypes on the two-level plane)

def _hier_env(size=4):
    """local/cross ring addresses for a 2x2 layout (2 groups of 2), as
    the env the child scenarios (and the native engine) read."""
    assert size == 4
    local = ";".join(",".join(f"127.0.0.1:{_free_port()}" for _ in range(2))
                     for _ in range(2))
    cross = ",".join(f"127.0.0.1:{_free_port()}" for _ in range(2))
    return {"HVD_TEST_LOCAL_ADDRS": local, "HVD_TEST_CROSS_ADDRS": cross}


def _simulate_two_level(xs, cross_wire):
    """Numpy transcript of the 2x2 two-level plane: local sums are exact
    (a 2-rank f32 ring performs ONE addition per element — bitwise
    order-independent), the two group sums ride a 2-rank cross ring under
    ``cross_wire`` (the flat-ring transcript applies — same schedule),
    and the local broadcast copies bytes verbatim."""
    s0 = xs[0] + xs[1]
    s1 = xs[2] + xs[3]
    return _simulate_ring([s0, s1], cross_wire)


def test_hier_wire_bitwise_reference_and_ef_exact_mean():
    """ONE 4-rank 2x2 job (tier-1 pays per-child jax imports, so the two
    RingBackend-level contracts share a spawn): (a) all four cross wire
    dtypes pinned bitwise against the numpy-simulated two-level
    reference (the local hop stays f32 — its counters prove it in the
    native-engine test); (b) the telescoping EF contract through the two
    levels — cross errors recorded on the roots, carried into the next
    round, T-step average converging to the exact mean
    (docs/wire-compression.md)."""
    count = 20021  # uneven cross segments AND a partial int8 quant block
    results = _run_ring_job("hier_wire", 4, extra_env={
        **_hier_env(), "HVD_TEST_COUNT": str(count)})
    xs = [_rank_input(r, count) for r in range(4)]
    for wire in ("none", "bf16", "fp16", "int8"):
        expect = _simulate_two_level(xs, wire)
        want = hashlib.sha256(expect.tobytes()).hexdigest()
        for rank, res in enumerate(results):
            assert res[wire] == want, (
                f"hier cross={wire} rank {rank}: two-level ring result != "
                f"numpy-simulated reference")
    for res in results:
        assert res["ef_rel_err"] < 3.0 * res["single_rel_err"] / res["T"], (
            res)
        assert res["noef_rel_err"] > 10 * res["ef_rel_err"], res


def test_per_link_wire_dtype_default_selection(monkeypatch):
    """Link-class defaults (ici/local -> none, tcp/dcn -> int8), explicit
    env override, and garbage-env -> default for both the wire dtype and
    the link class."""
    from horovod_tpu.common import config as cfg

    for var in ("HOROVOD_RING_WIRE_DTYPE_LOCAL",
                "HOROVOD_RING_WIRE_DTYPE_CROSS",
                "HOROVOD_LOCAL_RING_LINK_CLASS",
                "HOROVOD_CROSS_RING_LINK_CLASS",
                "HOROVOD_LOCAL_RING_ADDRS", "HOROVOD_CROSS_RING_ADDRS"):
        monkeypatch.delenv(var, raising=False)
    # Loopback local ring -> link class local -> uncompressed by default.
    monkeypatch.setenv("HOROVOD_LOCAL_RING_ADDRS",
                       "127.0.0.1:1,127.0.0.1:2")
    assert cfg.local_ring_link_class() == "local"
    assert cfg.ring_wire_dtype_local() == "none"
    # Host-spanning cross ring -> tcp -> int8 by default.
    monkeypatch.setenv("HOROVOD_CROSS_RING_ADDRS",
                       "10.0.0.1:1,10.0.0.2:1")
    assert cfg.cross_ring_link_class() == "tcp"
    assert cfg.ring_wire_dtype_cross() == "int8"
    # Explicit link classes key the sibling table both ways.
    monkeypatch.setenv("HOROVOD_CROSS_RING_LINK_CLASS", "ici")
    assert cfg.ring_wire_dtype_cross() == "none"
    monkeypatch.setenv("HOROVOD_CROSS_RING_LINK_CLASS", "dcn")
    assert cfg.ring_wire_dtype_cross() == "int8"
    # Garbage wire dtype -> the link-class default, never a crash.
    monkeypatch.setenv("HOROVOD_RING_WIRE_DTYPE_CROSS", "int4")
    assert cfg.ring_wire_dtype_cross() == "int8"
    # An explicit valid value wins over the default.
    monkeypatch.setenv("HOROVOD_RING_WIRE_DTYPE_CROSS", "bf16")
    assert cfg.ring_wire_dtype_cross() == "bf16"
    # Garbage link class falls back to address inference (tcp here).
    monkeypatch.delenv("HOROVOD_RING_WIRE_DTYPE_CROSS")
    monkeypatch.setenv("HOROVOD_CROSS_RING_LINK_CLASS", "warp")
    assert cfg.cross_ring_link_class() == "tcp"
    assert cfg.ring_wire_dtype_cross() == "int8"
    # The table rows the defaults come from (docs/wire-compression.md).
    assert cfg.RING_WIRE_DTYPE_BY_LINK == {
        "local": "none", "ici": "none", "tcp": "int8", "dcn": "int8"}


def _run_hier_native_job(scenario, extra_env):
    """4-rank 2x2 full-stack job on the NATIVE engine's two-level plane:
    per-rank local/cross env + group-specific local ring addresses, the
    exact surface hvd_eng_init reads."""
    hier = _hier_env()
    locals_by_group = hier["HVD_TEST_LOCAL_ADDRS"].split(";")
    return run_script_ranks(
        os.path.abspath(__file__), scenario, 4,
        extra_env={"HOROVOD_SIZE": "4", "HOROVOD_LOCAL_SIZE": "2",
                   "HOROVOD_CROSS_SIZE": "2",
                   "HOROVOD_CROSS_RING_ADDRS": hier["HVD_TEST_CROSS_ADDRS"],
                   "HOROVOD_HIERARCHICAL_ALLREDUCE": "1", **extra_env},
        per_rank_env={rank: {
            "HOROVOD_RANK": str(rank),
            "HOROVOD_LOCAL_RANK": str(rank % 2),
            "HOROVOD_CROSS_RANK": str(rank // 2),
            "HOROVOD_LOCAL_RING_ADDRS": locals_by_group[rank // 2],
        } for rank in range(4)})


def _check_hier_native_results(results):
    for rank, res in enumerate(results):
        assert res["hier_active"], res
        # Exact through engine fusion: int-valued payloads whose every
        # 4096-block quantizes with a power-of-two scale survive the
        # cross int8 hop bit-exactly, fused or not.
        assert res["fused_exact"], res
        # EF convergence end-to-end (controller residuals -> engine ->
        # cross ring and back).
        assert res["avg_rel_err"] < 0.3 * res["single_rel_err"], res
        # The counters prove the split: the cross hop carries int8 bytes
        # ON THE ROOTS (local_rank 0 owns the cross ring; non-roots never
        # touch it), and the local hop stays f32 everywhere.
        if rank % 2 == 0:
            assert res["cross_int8_bytes"] > 0, res
            assert res["health_cross_savings"] > 0.5, res
        else:
            assert res["cross_int8_bytes"] == 0, res
        assert res["local_int8_bytes"] == 0, res
        assert res["health_local_savings"] == 0.0, res


def test_native_engine_hier_cross_int8_end_to_end():
    """Tier-1 sibling: TCP local ring (shm disabled), cross int8 —
    engine fusion exactness, EF convergence, per-link counter proof,
    controller_health surfacing."""
    results = _run_hier_native_job("hier_native", {
        "HOROVOD_RING_WIRE_DTYPE_CROSS": "int8",
        "HOROVOD_SHM_DISABLE": "1",
    })
    _check_hier_native_results(results)


@pytest.mark.slow
def test_native_engine_hier_cross_int8_shm_local_plane():
    """Heavy variant: the /dev/shm local plane under the compressed
    cross ring (the production same-host layout)."""
    results = _run_hier_native_job("hier_native", {
        "HOROVOD_RING_WIRE_DTYPE_CROSS": "int8",
        "HVD_TEST_COUNT": str(16 * QUANT_BLOCK + 77),
        "HVD_TEST_STEPS": "60",
    })
    _check_hier_native_results(results)


# ------------------------------------------------------------ child ranks

def _child_wire_result(rank, size, addrs):
    count = int(os.environ.get("HVD_TEST_COUNT", "50021"))
    ring = bindings.RingBackend(rank, size, addrs, b"wire-test")
    lib = bindings.load()
    bindings.set_chunk_bytes(64 * 1024)  # several chunks per segment
    x = _rank_input(rank, count)
    out = {}
    for wire, code in sorted(bindings.WIRE_DTYPE_CODES.items()):
        buf = x.copy()
        residual = np.zeros(count, np.float32) if wire == "int8" else None
        ring.allreduce_(buf, False, wire_dtype=code, residual=residual)
        out[wire] = hashlib.sha256(buf.tobytes()).hexdigest()
    # Legacy entry point (no wire args at all).
    buf = x.copy()
    import ctypes

    rc = lib.hvd_ringh_allreduce(
        ring._handle, buf.ctypes.data_as(ctypes.c_void_p), buf.size, 0, 0)
    assert rc == 0
    out["legacy_entry"] = hashlib.sha256(buf.tobytes()).hexdigest()
    print("RESULT " + json.dumps(out), flush=True)
    ring.shutdown()


def _child_wire_ef(rank, size, addrs):
    ring = bindings.RingBackend(rank, size, addrs, b"wire-test")
    count = 3 * QUANT_BLOCK + 117
    g = np.random.RandomState(42).randn(count).astype(np.float32)
    T = 48

    def run(feedback):
        residual = np.zeros(count, np.float32)
        acc = np.zeros(count, np.float64)
        first = None
        for _ in range(T):
            x = g + residual if feedback else g.copy()
            ring.allreduce_(x, False, wire_dtype=3, residual=residual)
            y = x / size
            if first is None:
                first = float(np.abs(y - g).max() / np.abs(g).max())
            acc += y
        avg = acc / T
        return float(np.abs(avg - g).max() / np.abs(g).max()), first, residual

    ef_err, single_err, residual = run(True)
    noef_err, _, _ = run(False)
    # Bound on |residual|: half a quant step of the worst block this rank
    # quantized; compensated inputs stay within ~2x of g's range.
    step = 2.0 * float(np.abs(g).max()) / 127.0
    print("RESULT " + json.dumps({
        "T": T, "ef_rel_err": ef_err, "noef_rel_err": noef_err,
        "single_rel_err": single_err,
        "residual_max": float(np.abs(residual).max()),
        "quant_step_bound": step,
    }), flush=True)
    ring.shutdown()


def _child_native_ef(rank, size, addrs):
    os.environ["HOROVOD_RING_ADDRS"] = addrs
    from horovod_tpu import metrics
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.controller.native import NativeController

    metrics.enable()
    topo = Topology(rank=rank, size=size, local_rank=rank, local_size=size,
                    cross_rank=0, cross_size=1)
    ctl = NativeController(Config.from_env(), topo)
    count = 2 * QUANT_BLOCK + 33
    g = np.random.RandomState(7).randn(count).astype(np.float32)
    T = 40
    acc = np.zeros(count, np.float64)
    single = None
    for _ in range(T):
        y = np.asarray(ctl.allreduce(g, average=True, name="ef.grad"))
        if single is None:
            single = float(np.abs(y - g).max() / np.abs(g).max())
        acc += y
    avg = acc / T
    health = metrics.controller_health()
    # Duplicate-name EF safety: while an op is in flight, a same-name
    # in-place enqueue must be rejected WITHOUT compensating the caller's
    # tensor or re-keying the residual the live op's ring thread writes.
    big = np.random.RandomState(9).randn(2_000_000).astype(np.float32)
    x2 = np.random.RandomState(11).randn(big.size).astype(np.float32)
    x2_orig = x2.copy()
    h1 = ctl.allreduce_async(big, average=True, name="ef.dup")
    h2 = ctl.allreduce_async(x2, average=True, name="ef.dup", inplace=True)
    dup_rejected = False
    try:
        h2.wait()
    except RuntimeError as exc:
        dup_rejected = "Duplicate" in str(exc)
    h1.wait()
    # Dropped-without-wait handle must not disable EF for its name
    # forever: the engine frees the name at completion; the controller's
    # in-flight mirror self-heals on the next same-name enqueue.
    import time

    h3 = ctl.allreduce_async(g, average=True, name="ef.drop")
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and not h3.done():
        time.sleep(0.01)
    drop_completed = h3.done()
    del h3  # never waited
    ctl.allreduce(g, average=True, name="ef.drop")  # must not be rejected
    print("RESULT " + json.dumps({
        "drop_completed": drop_completed,
        "drop_ef_resumed": "ef.drop" in ctl._residuals,
        "avg_rel_err": float(np.abs(avg - g).max() / np.abs(g).max()),
        "single_rel_err": single,
        "wire_savings_frac": health["wire_savings_frac"],
        "wire_bytes_total": health["wire_bytes_total"],
        "dup_rejected": dup_rejected,
        "dup_untouched": bool(np.array_equal(x2, x2_orig)),
    }), flush=True)
    ctl.shutdown()


def _child_wire_residual_zero(rank, size, addrs):
    ring = bindings.RingBackend(rank, size, addrs, b"wire-test")
    x = np.random.RandomState(rank).randn(QUANT_BLOCK + 11).astype(
        np.float32)
    out = {}
    for wire in ("bf16", "none"):
        residual = np.full(x.size, 5.0, np.float32)
        ring.allreduce_(x.copy(), False,
                        wire_dtype=bindings.WIRE_DTYPE_CODES[wire],
                        residual=residual)
        out[f"{wire}_residual_max"] = float(np.abs(residual).max())
    print("RESULT " + json.dumps(out), flush=True)
    ring.shutdown()


def _hier_rings(rank, secret=b"hier-test"):
    """local + (roots-only) cross RingBackends for the 2x2 layout, from
    the HVD_TEST_*_ADDRS env the parent allocated."""
    group, local = rank // 2, rank % 2
    local_ring = bindings.RingBackend(
        local, 2, os.environ["HVD_TEST_LOCAL_ADDRS"].split(";")[group],
        secret)
    local_ring.set_link("local")
    cross = None
    if local == 0:
        cross = bindings.RingBackend(
            group, 2, os.environ["HVD_TEST_CROSS_ADDRS"], secret)
        cross.set_link("cross")
    return local_ring, cross


def _child_hier_wire(rank, size, addrs):
    count = int(os.environ.get("HVD_TEST_COUNT", "20021"))
    local_ring, cross = _hier_rings(rank)
    x = _rank_input(rank, count)
    out = {}
    for wire, code in sorted(bindings.WIRE_DTYPE_CODES.items()):
        buf = x.copy()
        residual = np.zeros(count, np.float32) if wire == "int8" else None
        local_ring.allreduce_(buf, False)
        if cross is not None:
            cross.allreduce_(buf, False, wire_dtype=code, residual=residual)
        local_ring.broadcast_(buf, 0)
        out[wire] = hashlib.sha256(buf.tobytes()).hexdigest()

    # EF half of the contract (same rings, same spawn): telescoping
    # exact-mean convergence with the cross hop on int8.
    g = np.random.RandomState(500 + rank).randn(count).astype(np.float32)
    # The mean every round telescopes toward, in the two-level sum order
    # (local sums are exact single additions; the cross sum of two f32s
    # is order-independent).
    true = ((np.random.RandomState(500).randn(count).astype(np.float32)
             + np.random.RandomState(501).randn(count).astype(np.float32))
            + (np.random.RandomState(502).randn(count).astype(np.float32)
               + np.random.RandomState(503).randn(count).astype(np.float32))
            ) / np.float32(4)
    T = 28

    def run(feedback):
        residual = np.zeros(count, np.float32)
        acc = np.zeros(count, np.float64)
        first = None
        for _ in range(T):
            xx = g + residual if feedback else g.copy()
            local_ring.allreduce_(xx, False)
            if cross is not None:
                cross.allreduce_(xx, False, wire_dtype=3, residual=residual)
            local_ring.broadcast_(xx, 0)
            y = xx / 4
            if first is None:
                first = float(np.abs(y - true).max() / np.abs(true).max())
            acc += y
        avg = acc / T
        return (float(np.abs(avg - true).max() / np.abs(true).max()), first)

    ef_err, single_err = run(True)
    noef_err, _ = run(False)
    out.update({"T": T, "ef_rel_err": ef_err, "noef_rel_err": noef_err,
                "single_rel_err": single_err})
    print("RESULT " + json.dumps(out), flush=True)
    if cross is not None:
        cross.shutdown()
    local_ring.shutdown()


def _child_hier_native(rank, size, addrs):
    from horovod_tpu import metrics
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.controller.native import NativeController

    metrics.enable()
    topo = Topology(rank=rank, size=4, local_rank=rank % 2, local_size=2,
                    cross_rank=rank // 2, cross_size=2)
    ctl = NativeController(Config.from_env(), topo)
    # Read while no rank can have shut down: every rank still owes the
    # collectives below. After the last of them a rank that is done calls
    # ``ctl.shutdown()``, the engines agree to stop, and the loop's exit
    # takes the rings with it: a rank the box held up between its last
    # ``wait`` and this read then saw False (the flap under load:
    # CHANGES.md, PR 39).
    hier_active = bool(ctl.hierarchical_active)

    # Exact-through-fusion payload: every 4096-block is the same integer
    # pattern with amax exactly 127, so each two-level stage quantizes
    # with a power-of-two scale (2p -> scale 2, 4p -> scale 4) and int8
    # round-trips bit-exactly — fused or unfused, any fusion order.
    pat = (np.arange(QUANT_BLOCK) % 255 - 127).astype(np.float32)
    fused_exact = True
    handles = []
    for i, blocks in enumerate((1, 2, 1)):
        x = np.tile(pat, blocks)
        handles.append((x, ctl.allreduce_async(
            x, average=True, name=f"hx.{i}")))
    for x, h in handles:
        got = np.asarray(h.wait())
        fused_exact = fused_exact and bool(np.array_equal(got, x))

    # EF convergence end-to-end (residuals live on the controller, the
    # engine threads them through the cross hop).
    count = int(os.environ.get("HVD_TEST_COUNT", str(2 * QUANT_BLOCK + 33)))
    T = int(os.environ.get("HVD_TEST_STEPS", "20"))
    g = np.random.RandomState(700 + rank).randn(count).astype(np.float32)
    true = sum(np.random.RandomState(700 + r).randn(count).astype(np.float32)
               for r in range(4)) / 4.0
    acc = np.zeros(count, np.float64)
    single = None
    for _ in range(T):
        y = np.asarray(ctl.allreduce(g, average=True, name="hef.grad"))
        if single is None:
            single = float(np.abs(y - true).max() / np.abs(true).max())
        acc += y
    avg = acc / T
    health = metrics.controller_health()
    stats = bindings.wire_stats()
    print("RESULT " + json.dumps({
        "hier_active": hier_active,
        "fused_exact": fused_exact,
        "avg_rel_err": float(np.abs(avg - true).max() / np.abs(true).max()),
        "single_rel_err": single,
        "cross_int8_bytes": stats["by_link"]["cross"]["tx_bytes"]["int8"],
        "local_int8_bytes": stats["by_link"]["local"]["tx_bytes"]["int8"],
        "health_cross_savings": health["wire_savings_by_link"]["cross"],
        "health_local_savings": health["wire_savings_by_link"]["local"],
    }), flush=True)
    ctl.shutdown()


_CHILDREN = {
    "wire_result": _child_wire_result,
    "wire_ef": _child_wire_ef,
    "native_ef": _child_native_ef,
    "wire_residual_zero": _child_wire_residual_zero,
    "hier_wire": _child_hier_wire,
    "hier_native": _child_hier_native,
}

if __name__ == "__main__":
    _scenario, _rank, _size, _addrs = sys.argv[1:5]
    _CHILDREN[_scenario](int(_rank), int(_size), _addrs)
