"""Subprocess worker for multi-process eager-tier tests.

Run as: python mp_worker.py <scenario>, with HOROVOD_RANK/SIZE/CONTROLLER_ADDR
set by the parent (tests/test_multiprocess.py). Equivalent of the reference's
mpirun-launched test bodies (SURVEY.md §4: "2 MPI ranks on one container").
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.compression import Compression  # noqa: E402


def expect(cond, msg):
    if not cond:
        raise AssertionError(msg)


def scenario_allreduce(rank, size):
    x = np.arange(8, dtype=np.float32) + rank
    avg = np.asarray(hvd.allreduce(x, average=True, name="t.avg"))
    want = np.arange(8, dtype=np.float32) + (size - 1) / 2.0
    np.testing.assert_allclose(avg, want, rtol=1e-6)

    tot = np.asarray(hvd.allreduce(x, average=False, name="t.sum"))
    want_sum = size * np.arange(8, dtype=np.float32) + sum(range(size))
    np.testing.assert_allclose(tot, want_sum, rtol=1e-6)

    xi = (np.arange(6) + rank).astype(np.int32)
    ti = np.asarray(hvd.allreduce(xi, average=False, name="t.int"))
    np.testing.assert_array_equal(
        ti, size * np.arange(6) + sum(range(size)))

    # fp16 wire compression round trip (reference Compression.fp16).
    xc = np.linspace(-2, 2, 16, dtype=np.float32) * (rank + 1)
    tc = np.asarray(hvd.allreduce(xc, average=True, name="t.fp16",
                                  compression=Compression.fp16))
    scale = sum(r + 1 for r in range(size)) / size
    np.testing.assert_allclose(tc, np.linspace(-2, 2, 16) * scale, atol=1e-2)

    # Full reference dtype matrix (test_torch.py runs ByteTensor ...
    # DoubleTensor): small ints sum exactly; bool reduces as logical OR.
    for dt in (np.uint8, np.int8, np.int16, np.uint16, np.int64,
               np.float16, np.float64):
        xd = (np.arange(5) % 3 + rank).astype(dt)
        td = np.asarray(hvd.allreduce(xd, average=False,
                                      name=f"t.{np.dtype(dt).name}"))
        expect(td.dtype == np.dtype(dt),
               f"dtype changed: {td.dtype} != {np.dtype(dt)}")
        want_d = (size * (np.arange(5) % 3) + sum(range(size))).astype(dt)
        np.testing.assert_array_equal(td, want_d)

    xb = np.zeros(4, dtype=bool)
    xb[rank % 4] = True
    tb = np.asarray(hvd.allreduce(xb, average=False, name="t.bool"))
    expect(tb.dtype == np.dtype(bool), f"bool became {tb.dtype}")
    want_b = np.zeros(4, bool)
    for r in range(size):
        want_b[r % 4] = True
    np.testing.assert_array_equal(tb, want_b)


def scenario_fusion(rank, size):
    # Many small tensors in flight at once: the controller packs them into
    # one fused buffer per dtype (reference "multiple" tests stress fusion).
    handles = [
        hvd.allreduce_async((np.ones(32, np.float32) * (i + rank)),
                            average=False, name=f"fuse.{i}")
        for i in range(12)
    ]
    for i, h in enumerate(handles):
        out = np.asarray(hvd.synchronize(h))
        want = np.ones(32) * (size * i + sum(range(size)))
        np.testing.assert_allclose(out, want, rtol=1e-6)

    # Mixed dtypes interleaved: fusion must look AHEAD past a mismatched
    # dtype and still pack the same-dtype tensors (reference FuseResponses
    # look-ahead, operations.cc:483-499) — and every tensor must come back
    # with its own dtype and the right value.
    mixed = []
    for i in range(8):
        dtype = [np.float32, np.float64, np.int32][i % 3]
        mixed.append((dtype, hvd.allreduce_async(
            (np.ones(16, dtype) * (i + 1)), average=False,
            name=f"fuse.mixed.{i}")))
    for i, (dtype, h) in enumerate(mixed):
        out = np.asarray(hvd.synchronize(h))
        expect(out.dtype == dtype, f"dtype changed: {out.dtype} != {dtype}")
        np.testing.assert_allclose(out, np.ones(16) * (i + 1) * size,
                                   rtol=1e-6)


def scenario_grouped(rank, size):
    # grouped_allreduce: whole list enqueued before any join — one fusion
    # group; results in order; torch grouped + in-place variants.
    outs = hvd.grouped_allreduce(
        [np.ones(8, np.float32) * (i + rank) for i in range(6)],
        average=False, name="grp")
    for i, out in enumerate(outs):
        np.testing.assert_allclose(
            np.asarray(out), np.ones(8) * (size * i + sum(range(size))),
            rtol=1e-6)

    outs = hvd.grouped_allreduce(
        [np.full(4, float(rank)), np.full(2, float(rank * 2))],
        average=True)
    mean_r = (size - 1) / 2
    np.testing.assert_allclose(np.asarray(outs[0]), np.full(4, mean_r))
    np.testing.assert_allclose(np.asarray(outs[1]), np.full(2, 2 * mean_r))

    import torch

    import horovod_tpu.torch as thvd

    ts = [torch.ones(5) * (i + rank) for i in range(4)]
    res = thvd.grouped_allreduce(ts, average=False, name="grp.t")
    for i, r in enumerate(res):
        np.testing.assert_allclose(
            r.numpy(), np.ones(5) * (size * i + sum(range(size))), rtol=1e-6)
    got = thvd.grouped_allreduce_(ts, average=False, name="grp.ti")
    for i, (t, g) in enumerate(zip(ts, got)):
        expect(g is t, "grouped_allreduce_ returned new tensors")
        np.testing.assert_allclose(
            t.numpy(), np.ones(5) * (size * i + sum(range(size))), rtol=1e-6)

    import tensorflow as tf

    import horovod_tpu.tensorflow as tfhvd

    tf_outs = tfhvd.grouped_allreduce(
        [tf.constant([1.0, 2.0]) * (rank + 1), tf.constant([3.0])],
        average=False, name="grp.tf")
    scale_t = sum(r + 1 for r in range(size))
    np.testing.assert_allclose(tf_outs[0].numpy(), [scale_t, 2 * scale_t])
    np.testing.assert_allclose(tf_outs[1].numpy(), [3 * size])

    # TF grouped + fp16 wire compression (compressed at the TF level, the
    # controller sees plain f16 numpy).
    tf_c = tfhvd.grouped_allreduce(
        [tf.constant([0.5, -1.5]) * (rank + 1)], average=True,
        name="grp.tfc", compression=tfhvd.Compression.fp16)
    mean_scale = sum(r + 1 for r in range(size)) / size
    np.testing.assert_allclose(tf_c[0].numpy(),
                               [0.5 * mean_scale, -1.5 * mean_scale],
                               atol=1e-2)
    import pytest

    with pytest.raises(ValueError, match="IndexedSlices"):
        tfhvd.grouped_allreduce([tf.IndexedSlices(
            values=tf.constant([[1.0]]), indices=tf.constant([0]),
            dense_shape=tf.constant([2, 1]))])


def scenario_reducescatter_alltoall(rank, size):
    # Composed eager reducescatter/alltoall (controller.composed_*): the
    # SPMD tier's collectives, made available on the host tier.
    # reducescatter: sum then keep this rank's dim-0 block; 5 rows over
    # size ranks exercises the uneven array_split boundaries.
    x = np.arange(10, dtype=np.float32).reshape(5, 2) + rank
    out = np.asarray(hvd.reducescatter(x, average=False))
    full = size * (np.arange(10, dtype=np.float32).reshape(5, 2)) \
        + sum(range(size))
    base, rem = divmod(5, size)
    counts = [base + (1 if r < rem else 0) for r in range(size)]
    off = sum(counts[:rank])
    np.testing.assert_allclose(out, full[off:off + counts[rank]])
    # average=True divides by size.
    out = np.asarray(hvd.reducescatter(x, average=True))
    np.testing.assert_allclose(out, full[off:off + counts[rank]] / size)

    # alltoall: rank r receives every rank's r-th block, in rank order.
    # Rank j sends blocks of j+1 rows (per-rank dims may differ).
    rows = size * (rank + 1)
    x = np.full((rows, 3), float(rank), np.float32)
    x[:, 1] = np.repeat(np.arange(size), rank + 1)  # block id in col 1
    out = np.asarray(hvd.alltoall(x))
    expect(out.shape == (sum(r + 1 for r in range(size)), 3),
           f"alltoall shape {out.shape}")
    want = np.concatenate([
        np.stack([np.full(j + 1, float(j)),
                  np.full(j + 1, float(rank)),
                  np.full(j + 1, float(j))], axis=1)
        for j in range(size)
    ])
    np.testing.assert_allclose(out, want)

    # Indivisible first dim raises the SAME error on every rank (agreed via
    # the dims gather) instead of hanging the data phase.
    try:
        hvd.alltoall(np.zeros((size + 1, 2), np.float32))
        expect(False, "indivisible alltoall must raise")
    except ValueError as exc:
        expect("divisible" in str(exc), str(exc))
    # Scalars are rejected up front.
    try:
        hvd.reducescatter(np.float32(3.0))
        expect(False, "scalar reducescatter must raise")
    except ValueError:
        pass
    # The job keeps serving afterwards.
    ok = np.asarray(hvd.allreduce(np.ones(2, np.float32), average=False))
    np.testing.assert_allclose(ok, size * np.ones(2))


def scenario_objects(rank, size):
    # broadcast_object / allgather_object (later-Horovod API): arbitrary
    # picklable payloads of rank-dependent size over the eager tier.
    obj = {"rank": rank, "data": list(range(rank + 1)), "tag": "x" * rank}
    got = hvd.broadcast_object(obj if rank == 1 % size else None,
                               root_rank=1 % size, name="obj.bc")
    expect(got["rank"] == 1 % size, f"wrong root object: {got}")
    gathered = hvd.allgather_object(obj, name="obj.ag")
    expect(len(gathered) == size, f"expected {size} objects")
    for r, o in enumerate(gathered):
        expect(o["rank"] == r and o["data"] == list(range(r + 1)),
               f"rank {r} object corrupted: {o}")
    # barrier: all ranks must pass through together; a second barrier with
    # a fresh name verifies reusability.
    hvd.barrier()
    hvd.barrier(name="obj.barrier2")
    # Out-of-range root fails FAST on every rank (it would pass the
    # cross-rank validation — all ranks agree — and hang the data phase).
    try:
        hvd.broadcast_object(obj, root_rank=size + 3, name="obj.badroot")
        raise AssertionError("out-of-range root did not raise")
    except ValueError as exc:
        expect("out of range" in str(exc), f"wrong error: {exc}")


def scenario_allgather(rank, size):
    # Rank-dependent first dims (reference allgather variable-dim tests).
    x = np.full((rank + 1, 3), rank, dtype=np.float32)
    out = np.asarray(hvd.allgather(x, name="gather.var"))
    want = np.concatenate(
        [np.full((r + 1, 3), r, dtype=np.float32) for r in range(size)])
    np.testing.assert_array_equal(out, want)


def scenario_broadcast(rank, size):
    x = np.full(5, rank, dtype=np.float32)
    out0 = np.asarray(hvd.broadcast(x, root_rank=0, name="bc.0"))
    np.testing.assert_array_equal(out0, np.zeros(5))
    out1 = np.asarray(hvd.broadcast(x, root_rank=size - 1, name="bc.last"))
    np.testing.assert_array_equal(out1, np.full(5, size - 1))


def scenario_cache(rank, size):
    # Same named op repeatedly: after the first negotiation the response
    # cache's bypass path executes it (reference RunBypass).
    for it in range(6):
        x = np.arange(4, dtype=np.float32) * (it + 1) + rank
        out = np.asarray(hvd.allreduce(x, average=False, name="cached.t"))
        want = size * np.arange(4, dtype=np.float32) * (it + 1) + sum(range(size))
        np.testing.assert_allclose(out, want, rtol=1e-6)
    # Shape change for the same name: invalidation + renegotiation.
    y = np.ones((2, 2), np.float32) * rank
    out = np.asarray(hvd.allreduce(y, average=False, name="cached.t"))
    np.testing.assert_allclose(out, np.ones((2, 2)) * sum(range(size)))


def scenario_error_mismatch(rank, size):
    # Reference error-path test: mismatched shapes across ranks must raise
    # on every rank (test/test_torch.py test_horovod_allreduce_error).
    x = np.ones(2 + rank, dtype=np.float32)
    try:
        hvd.allreduce(x, name="bad.shape")
    except RuntimeError as exc:
        expect("Mismatched allreduce tensor shapes" in str(exc),
               f"wrong error: {exc}")
    else:
        raise AssertionError("mismatched shapes did not raise")

    # dtype mismatch
    x2 = np.ones(4, dtype=np.float32 if rank == 0 else np.float64)
    try:
        hvd.allreduce(x2, name="bad.dtype")
    except RuntimeError as exc:
        expect("Mismatched data types" in str(exc), f"wrong error: {exc}")
    else:
        raise AssertionError("mismatched dtypes did not raise")

    # broadcast root mismatch (reference test_horovod_broadcast_rank_error).
    try:
        hvd.broadcast(np.ones(3, np.float32), root_rank=rank % size,
                      name="bad.root")
    except RuntimeError as exc:
        expect("Mismatched broadcast root ranks" in str(exc),
               f"wrong error: {exc}")
    else:
        raise AssertionError("mismatched roots did not raise")

    # allgather rank (ndim) mismatch.
    xg = np.ones((2,) * (rank + 1), dtype=np.float32)
    try:
        hvd.allgather(xg, name="bad.gather.rank")
    except RuntimeError as exc:
        expect("Mismatched allgather tensor ranks" in str(exc),
               f"wrong error: {exc}")
    else:
        raise AssertionError("mismatched allgather ndims did not raise")

    # allgather trailing-dim mismatch.
    xg2 = np.ones((2, 2 + rank), dtype=np.float32)
    try:
        hvd.allgather(xg2, name="bad.gather.shape")
    except RuntimeError as exc:
        expect("Mismatched allgather tensor shapes" in str(exc),
               f"wrong error: {exc}")
    else:
        raise AssertionError("mismatched allgather dims did not raise")

    # op-type mismatch: same name enqueued as different collectives
    # (reference ConstructResponse "Mismatched MPI operations",
    # operations.cc:209-240).
    try:
        if rank == 0:
            hvd.allreduce(np.ones(3, np.float32), name="bad.op")
        else:
            hvd.allgather(np.ones(3, np.float32), name="bad.op")
    except RuntimeError as exc:
        expect("Mismatched" in str(exc), f"wrong error: {exc}")
    else:
        raise AssertionError("mismatched op types did not raise")

    # After errors, the controller must still work.
    ok = np.asarray(hvd.allreduce(np.ones(3, np.float32), average=False,
                                  name="good.after"))
    np.testing.assert_allclose(ok, np.full(3, size))


def scenario_duplicate_name(rank, size):
    h1 = hvd.allreduce_async(np.ones(4, np.float32), name="dup", average=False)
    h2 = hvd.allreduce_async(np.ones(4, np.float32), name="dup", average=False)
    # Exactly one of them must fail with the duplicate-name error; the
    # first completes normally.
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h1)), 1.0 * size)
    try:
        hvd.synchronize(h2)
    except RuntimeError as exc:
        expect("Duplicate tensor name" in str(exc), f"wrong error: {exc}")
    else:
        raise AssertionError("duplicate name did not raise")


def scenario_autotune(rank, size):
    # Autotuner keeps results correct while retuning fusion/cycle params
    # (reference HOROVOD_AUTOTUNE, operations.cc:1040-1078).
    for it in range(60):
        x = np.ones(256, np.float32) * (rank + it)
        out = np.asarray(hvd.allreduce(x, average=False, name=f"at.{it}"))
        want = np.ones(256) * (size * it + sum(range(size)))
        np.testing.assert_allclose(out, want, rtol=1e-6)
    # Repeated name: the response cache serves bypass hits while the
    # autotuner may flip cache_enabled mid-run (reference SetCacheEnabled
    # categorical) — hits, misses, and the toggle must all stay correct
    # and rank-synchronized.
    for it in range(40):
        x = np.ones(128, np.float32) * (rank + 2 * it)
        out = np.asarray(hvd.allreduce(x, average=False, name="at.cached"))
        want = np.ones(128) * (2 * size * it + sum(range(size)))
        np.testing.assert_allclose(out, want, rtol=1e-6)
    # Variable-dim allgathers while the hierarchical-ALLGATHER categorical
    # may flip mid-run (two-level vs flat gather must agree bit-for-bit).
    for it in range(12):
        g = np.full((rank + 1, 2), rank * 10 + it, dtype=np.float32)
        out = np.asarray(hvd.allgather(g, name=f"at.gather.{it}"))
        want = np.concatenate(
            [np.full((r + 1, 2), r * 10 + it, dtype=np.float32)
             for r in range(size)])
        np.testing.assert_array_equal(out, want)


def scenario_peer_death(rank, size):
    # A rank DYING (SIGKILL, no shutdown message) mid-job must surface as
    # an engine error on its peers within the stall/ring timeout, not an
    # unbounded hang — the contract shm.cc:19-23 documents for the local
    # plane, here exercised end-to-end by actually killing a process.
    import signal as _signal

    out = np.asarray(hvd.allreduce(np.ones(4, np.float32), average=False,
                                   name="pd.warm"))
    np.testing.assert_allclose(out, float(size))
    if rank == 1:
        os.kill(os.getpid(), _signal.SIGKILL)  # die without cleanup
    try:
        hvd.allreduce(np.ones(4, np.float32), name="pd.after")
    except RuntimeError as exc:
        print(f"peer-death error surfaced: {exc}", flush=True)
    else:
        raise AssertionError("allreduce with a dead peer did not raise")


def scenario_fault_survivor(rank, size):
    # Chaos harness (tests/test_fault_tolerance.py): generate steady
    # eager traffic until the injected fault (kill-rank-at-cycle-N /
    # dropped frames, HOROVOD_FAULT_PLAN) fails the job. Survivors must
    # get a DESCRIPTIVE engine error — which rank died, what was in
    # flight — within the comm timeout; the killed rank never gets here.
    try:
        for i in range(100000):
            out = np.asarray(hvd.allreduce(np.ones(64, np.float32) * i,
                                           average=False, name=f"ft.{i}"))
            np.testing.assert_allclose(out, float(size) * i)
    except RuntimeError as exc:
        print(f"fault error surfaced: {exc}", flush=True)
    else:
        raise AssertionError("injected fault did not surface")


def scenario_fault_metrics(rank, size):
    # Telemetry acceptance (tests/test_metrics.py): steady eager traffic
    # until the injected fault (dropped frames, HOROVOD_FAULT_PLAN) kills
    # the job. Survivors print their registry snapshot — the parent
    # asserts the deadline-trip counter incremented and the flight
    # recorder (HOROVOD_FLIGHT_RECORDER) dumped a parseable JSONL whose
    # tail names the dead rank.
    import json as _json
    try:
        for i in range(100000):
            out = np.asarray(hvd.allreduce(np.ones(32, np.float32) * i,
                                           average=False, name=f"fm.{i}"))
            np.testing.assert_allclose(out, float(size) * i)
    except RuntimeError as exc:
        print(f"fault error surfaced: {exc}", flush=True)
        print("METRICS_SNAPSHOT " + _json.dumps(hvd.metrics.snapshot()),
              flush=True)
    else:
        raise AssertionError("injected fault did not surface")


def _elastic_summary(steps):
    # One parseable line per member + rank 0's registry (the parent
    # asserts the membership series off it).
    import json as _json

    print(f"ELASTIC size={hvd.size()} epoch={hvd.elastic.epoch()} "
          f"steps={steps}", flush=True)
    if hvd.rank() == 0:
        print("METRICS_SNAPSHOT " + _json.dumps(hvd.metrics.snapshot()),
              flush=True)
    # Nobody leaves before rank 0 has printed. A member that exits has
    # LEFT: rank 0's controller then re-forms, logs that into the middle
    # of the snapshot's line and bumps the epoch gauge the parent reads
    # (both seen with every core busy: CHANGES.md, PR 39).
    hvd.allgather_object(None, name="el.printed")


def _elastic_train(target_size, min_epoch=2, settle_steps=10,
                   max_steps=20000):
    """Shared elastic loop (docs/elastic.md): allreduce-driven steps under
    hvd.elastic.run until the world settles at ``target_size`` ranks and
    epoch >= ``min_epoch`` for ``settle_steps`` consecutive steps. Every
    sum must equal some plausible world size exactly — a reshape may
    change WHICH size, but never tear one collective."""
    state = hvd.elastic.State(step=0, weights=np.zeros(4, np.float32))

    @hvd.elastic.run
    def train(state):
        settled = 0
        while True:
            total = np.asarray(hvd.allreduce(
                np.ones(4, np.float32), average=False,
                name=f"el.{state.step}"))
            k = float(total[0])
            expect(k == int(k) and 1 <= k <= target_size + 1,
                   f"allreduce saw impossible world size {k}")
            expect(np.all(total == k), f"torn allreduce result {total}")
            state.weights = state.weights + total
            state.step += 1
            state.commit()
            if hvd.size() == target_size and \
                    hvd.elastic.epoch() >= min_epoch and k == target_size:
                settled += 1
                if settled >= settle_steps:
                    return state.step
            else:
                settled = 0
            expect(state.step < max_steps,
                   f"world never settled at size {target_size} / epoch "
                   f">= {min_epoch} (now size {hvd.size()}, epoch "
                   f"{hvd.elastic.epoch()})")

    steps = train(state)
    # With the disk tier on (HOROVOD_CKPT_DIR), the last committed step
    # must reach storage before the parent inspects the directory; a
    # no-op otherwise.
    state.flush_checkpoints(15.0)
    # Survivors and joiners must agree bit-for-bit on the restored state.
    gathered = hvd.allgather_object(
        (int(steps), state.weights.tolist()), name="el.final")
    expect(len(gathered) == target_size,
           f"expected {target_size} members, got {len(gathered)}")
    expect(all(g == gathered[0] for g in gathered),
           f"divergent state after reshape: {gathered}")
    return steps


def scenario_elastic_shrink(rank, size):
    # ISSUE 7 acceptance: 3-rank elastic job; a seeded FaultPlan takes
    # rank 2 out mid-run (SIGKILL or graceful leave — parent's env).
    # Survivors re-form at membership epoch 2 with size 2, keep
    # completing consistent allreduces, and rank 0's snapshot carries the
    # shrink transition. No job-level failure anywhere.
    steps = _elastic_train(target_size=2, min_epoch=2)
    expect(hvd.elastic.epoch() == 2,
           f"expected exactly one reshape; epoch {hvd.elastic.epoch()}")
    _elastic_summary(steps)


def scenario_elastic_join(rank, size):
    # A live 2-rank job absorbs a late 3rd worker (spawned by the parent
    # with HOROVOD_ELASTIC_JOIN=1): existing members see a grow reshape
    # at the next epoch boundary, the joiner syncs state from rank 0, and
    # all three train on in lockstep.
    steps = _elastic_train(target_size=3, min_epoch=2)
    _elastic_summary(steps)


def scenario_elastic_parked(rank, size):
    # Livelock guard (docs/elastic.md): with the world already at
    # --max-ranks, a parked joiner must WAIT — no reshape, no epoch bump,
    # no drained collectives — while the members train on undisturbed.
    # Wall-clock bounded so the joiner is provably parked DURING steps —
    # but the EXIT is agreed through the collective itself (element 1
    # carries "my deadline passed"; any rank's flag ends the loop for
    # every rank in the SAME step). Independent wall-clock exits would
    # let the faster member finish a step early and prompt-exit, which
    # elastic correctly treats as that member LEAVING — a reshape this
    # scenario exists to prove does NOT happen while everyone stays.
    deadline = time.monotonic() + 6.0
    step = 0
    while True:
        mine = np.array(
            [1.0, 1.0 if time.monotonic() >= deadline else 0.0],
            np.float32)
        total = np.asarray(hvd.allreduce(mine, average=False,
                                         name=f"pk.{step}"))
        expect(float(total[0]) == size,
               f"world changed under a parked joiner: {total}")
        expect(hvd.elastic.epoch() == 1,
               f"epoch bumped to {hvd.elastic.epoch()} with no churn")
        step += 1
        if total[1] > 0:  # synchronized: all ranks exit this same step
            break
        time.sleep(0.01)
    print(f"PARKED_OK size={hvd.size()} epoch={hvd.elastic.epoch()} "
          f"steps={step}", flush=True)


def scenario_elastic_storm(rank, size):
    # Kill+join storm, fully scripted by FaultPlan membership kinds:
    # rank 2 is SIGKILLed at its cycle 40 (shrink) and rank 1 spawns a
    # clone of itself as a joiner at its cycle 400 (grow). Whatever order
    # the boundaries land in, the job must settle back at 3 ranks with a
    # bumped epoch and bit-identical state on every member.
    steps = _elastic_train(target_size=3, min_epoch=2, max_steps=40000)
    _elastic_summary(steps)


def scenario_elastic_ckpt_chaos(rank, size):
    # ISSUE 15 chaos: the parent sets HOROVOD_CKPT_DIR (the async
    # sharded disk tier rides every commit) and SIGKILLs rank 2 INSIDE
    # its hvd-ckpt-writer thread via the ckpt_save fault site. The
    # survivors must re-form and p2p-restore exactly as for any crash,
    # and the shared directory must still hold a complete resumable
    # step.
    steps = _elastic_train(target_size=2, min_epoch=2)
    _elastic_summary(steps)


def scenario_elastic_ckpt_chaos_storm(rank, size):
    # Kill+join storm with the disk tier on: reshapes, the joiner's
    # p2p shard fetches, and delayed async writes all overlap. Fetch
    # counters are per-process (the joiner's live in ITS registry, which
    # shares rank 1's stdout), so every member prints its own.
    steps = _elastic_train(target_size=3, min_epoch=2, max_steps=40000)
    entry = hvd.metrics.snapshot().get(
        "hvd_elastic_shard_fetches_total") or {}
    total = sum(v for _, v in entry.get("values", []))
    print(f"SHARD_FETCHES {int(total)}", flush=True)
    _elastic_summary(steps)


def scenario_trace(rank, size):
    # Cluster-tracing acceptance (tests/test_trace.py): steady eager
    # traffic with HOROVOD_TRACE_DIR set. At the lockstep shutdown rank 0
    # collects every rank's span file, merges them through the clock
    # offset table, and writes merged_trace.json + straggler_report.json;
    # the parent asserts on the artifacts. Run with a FaultPlan delay on
    # one rank's wire_send, the report must name that rank.
    import json as _json

    for i in range(25):
        out = np.asarray(hvd.allreduce(np.ones(16, np.float32) * i,
                                       average=False, name=f"tr.{i}"))
        np.testing.assert_allclose(out, float(size) * i)
    # Repeated name: cache-bypass collectives must carry seq ids too.
    for i in range(5):
        out = np.asarray(hvd.allreduce(np.ones(4, np.float32) * (i + rank),
                                       average=False, name="tr.cached"))
        np.testing.assert_allclose(out,
                                   float(size) * i + sum(range(size)))
    hvd.shutdown()  # triggers the lockstep trace finalize on every rank
    if rank == 0:
        # Attribution fed the registry during finalize: straggler series
        # are now visible in the snapshot the parent parses.
        print("METRICS_SNAPSHOT " + _json.dumps(hvd.metrics.snapshot()),
              flush=True)


def scenario_metrics_cluster(rank, size):
    # Rank-0 cluster view: workers piggyback registry snapshots on ticks
    # (HOROVOD_METRICS_PUSH_CYCLES); rank 0's exporter must serve every
    # rank's series rank-labeled. The parent sets HOROVOD_METRICS_PORT, so
    # this also exercises the real HTTP endpoint (acceptance criterion).
    import time as _time
    import urllib.request

    for i in range(30):
        out = np.asarray(hvd.allreduce(np.ones(8, np.float32),
                                       average=False, name=f"mc.{i}"))
        np.testing.assert_allclose(out, float(size))
    if rank == 0:
        port = int(os.environ["HOROVOD_METRICS_PORT"])
        deadline = _time.monotonic() + 30
        body = ""
        while _time.monotonic() < deadline:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            ).read().decode()
            if all(f'rank="{r}"' in body for r in range(size)):
                break
            _time.sleep(0.2)  # workers keep ticking; pushes still landing
        else:
            raise AssertionError(
                "cluster view never showed every rank:\n" + body[-2000:])
        expect("hvd_wire_frames_sent_total" in body, "wire series missing")
        expect("hvd_controller_cycle_seconds_bucket" in body,
               "cycle histogram missing")
        expect("hvd_collective_ops_total" in body,
               "collective op series missing")
        expect("# TYPE hvd_controller_cycle_seconds histogram" in body,
               "TYPE line missing")
        print("CLUSTER_VIEW_OK", flush=True)
    # Final barrier keeps every worker's controller ticking until rank 0
    # has verified the view.
    out = np.asarray(hvd.allreduce(np.ones(2, np.float32), average=False,
                                   name="mc.done"))
    np.testing.assert_allclose(out, float(size))


def scenario_doctor(rank, size):
    # Cluster-doctor acceptance (tests/test_doctor.py): the parent sets a
    # FaultPlan delaying every wire_send on rank 1, plus HOROVOD_TRACE_DIR
    # and HOROVOD_METRICS_PORT. Rank 0 polls its own /doctor endpoint
    # until the persistent-straggler rule names rank 1 from the LIVE
    # evidence (the coordinator's tick-lateness histogram); the offline
    # half of the acceptance — python -m horovod_tpu.tools.doctor over
    # the artifact dir — runs in the parent after the lockstep shutdown
    # has written straggler_report.json.
    import json as _json
    import time as _time
    import urllib.request

    for i in range(30):
        out = np.asarray(hvd.allreduce(np.ones(16, np.float32) * i,
                                       average=False, name=f"dr.{i}"))
        np.testing.assert_allclose(out, float(size) * i)
    if rank == 0:
        port = int(os.environ["HOROVOD_METRICS_PORT"])
        deadline = _time.monotonic() + 60
        named = None
        while _time.monotonic() < deadline:
            try:
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/doctor", timeout=5
                ).read().decode()
            except OSError:
                # The exporter walks to the next free port on a bind
                # collision (start_exporter) — keep polling rather than
                # crash on a transient refusal; the 60s deadline still
                # produces the explicit failure message below.
                _time.sleep(0.5)
                continue
            report = _json.loads(body)
            hits = [f for f in report["findings"]
                    if f["rule"] == "persistent_straggler"
                    and f["rank"] == 1]
            if hits:
                named = hits[0]
                break
            _time.sleep(0.5)  # controllers keep ticking; evidence grows
        expect(named is not None,
               "live /doctor endpoint never produced a persistent-"
               "straggler finding naming rank 1")
        print("DOCTOR_HTTP " + _json.dumps(named), flush=True)
    # Barrier: every worker's controller keeps ticking (and rank 1 keeps
    # arriving late) until rank 0 has its live verdict.
    out = np.asarray(hvd.allreduce(np.ones(2, np.float32), average=False,
                                   name="dr.done"))
    np.testing.assert_allclose(out, float(size))
    hvd.shutdown()  # lockstep trace finalize -> straggler_report.json


def scenario_stall(rank, size):
    # Reference test/test_stall.py: one rank joins late; the coordinator must
    # warn (HOROVOD_STALL_CHECK_TIME_SECONDS=1 set by the parent) and the op
    # must still complete once the straggler arrives.
    import time as _time

    if rank != 0:
        _time.sleep(2.5)
    out = np.asarray(hvd.allreduce(np.ones(2, np.float32), average=False,
                                   name="stall.t"))
    np.testing.assert_allclose(out, float(size))


def scenario_stall_shutdown(rank, size):
    # With HOROVOD_STALL_SHUTDOWN_TIME_SECONDS set, a permanent straggler
    # aborts the job cooperatively (reference operations.cc:757-769).
    import time as _time

    if rank == 0:
        h = hvd.allreduce_async(np.ones(2, np.float32), name="never.t")
        try:
            hvd.synchronize(h)
        except RuntimeError as exc:
            expect("shut down" in str(exc), f"wrong error: {exc}")
        else:
            raise AssertionError("expected shutdown error on stalled op")
    else:
        # Never participate; just outlive the 2s shutdown threshold (+
        # warn interval + margin) the parent test configures. Was 8s —
        # pure wall time on the tier-1 budget.
        _time.sleep(6)


def scenario_torch(rank, size):
    # Reference test/test_torch.py core semantics across real ranks.
    import torch

    import horovod_tpu.torch as thvd

    x = torch.arange(8, dtype=torch.float32) + rank
    avg = thvd.allreduce(x, average=True, name="tt.avg")
    np.testing.assert_allclose(
        avg.numpy(), np.arange(8) + (size - 1) / 2, rtol=1e-6)

    y = x.clone()
    thvd.allreduce_(y, average=False, name="tt.sum")
    np.testing.assert_allclose(
        y.numpy(), size * np.arange(8) + sum(range(size)), rtol=1e-6)

    # Variable-dim allgather with autograd through it.
    g_in = torch.full((rank + 1, 2), float(rank), requires_grad=True)
    gathered = thvd.allgather(g_in, name="tt.gather")
    want = np.concatenate([np.full((r + 1, 2), r) for r in range(size)])
    np.testing.assert_array_equal(gathered.detach().numpy(), want)
    gathered.sum().backward()
    # d(sum of gathered)/d(own shard) summed over ranks = size.
    np.testing.assert_allclose(g_in.grad.numpy(),
                               np.full((rank + 1, 2), float(size)))

    # Exactly ONE collective per autograd allgather: backward's slice
    # offset comes from the negotiated Response's tensor_sizes on the
    # handle, not a second sizes-allgather (reference gets the sizes from
    # the response too, torch/adapter_v2.cc:91-102).
    import horovod_tpu.torch.mpi_ops as tops
    gather_calls = []
    orig_ag = tops.allgather_async
    tops.allgather_async = (
        lambda *a, **k: (gather_calls.append(1), orig_ag(*a, **k))[1])
    try:
        g_cnt = torch.full((rank + 1, 2), float(rank), requires_grad=True)
        out_cnt = thvd.allgather(g_cnt, name="tt.gather.count")
        expect(len(gather_calls) == 1,
               f"autograd allgather issued {len(gather_calls)} gathers")
        out_cnt.sum().backward()
        expect(len(gather_calls) == 1,
               f"backward issued {len(gather_calls) - 1} extra gathers")
    finally:
        tops.allgather_async = orig_ag
    np.testing.assert_allclose(g_cnt.grad.numpy(),
                               np.full((rank + 1, 2), float(size)))

    bc = thvd.broadcast(x, root_rank=size - 1, name="tt.bc")
    np.testing.assert_allclose(bc.numpy(), np.arange(8) + size - 1)

    # bf16 tensors ride the uint16-bit-view interop (numpy has no native
    # bf16); the ring reduces DT_BF16 with round-to-nearest-even, and the
    # in-place variant lands results directly in the tensor's storage.
    xb = (torch.arange(8, dtype=torch.float32) + rank).to(torch.bfloat16)
    sb = thvd.allreduce(xb, average=False, name="tt.bf16")
    expect(sb.dtype == torch.bfloat16, f"bf16 became {sb.dtype}")
    np.testing.assert_allclose(
        sb.float().numpy(), size * np.arange(8) + sum(range(size)),
        rtol=2e-2)
    yb = xb.clone()
    got_b = thvd.allreduce_(yb, average=True, name="tt.bf16.inp")
    expect(got_b is yb, "bf16 allreduce_ returned a new tensor")
    np.testing.assert_allclose(
        yb.float().numpy(), np.arange(8) + (size - 1) / 2, rtol=2e-2,
        atol=2e-2)
    zb = torch.full((6,), float(rank), dtype=torch.bfloat16)
    thvd.broadcast_(zb, root_rank=0, name="tt.bf16.bc")
    np.testing.assert_allclose(zb.float().numpy(), np.zeros(6))
    # Out-of-place bf16 broadcast + allgather exercise the _to_torch wrap
    # (size-1 tests short-circuit before any conversion runs).
    vb = torch.full((3,), float(rank + 1), dtype=torch.bfloat16)
    ob = thvd.broadcast(vb, root_rank=size - 1, name="tt.bf16.obc")
    expect(ob.dtype == torch.bfloat16, f"bf16 bcast became {ob.dtype}")
    np.testing.assert_allclose(ob.float().numpy(), np.full(3, float(size)))
    gb = thvd.allgather(torch.full((rank + 1, 2), float(rank),
                                   dtype=torch.bfloat16), name="tt.bf16.ag")
    expect(gb.dtype == torch.bfloat16, f"bf16 gather became {gb.dtype}")
    want_g = np.concatenate([np.full((r + 1, 2), float(r))
                             for r in range(size)])
    np.testing.assert_allclose(gb.float().numpy(), want_g)

    # DistributedOptimizer: averaged gradient step matches manual math.
    model = torch.nn.Linear(2, 1, bias=False)
    with torch.no_grad():
        model.weight.fill_(1.0)
    opt = torch.optim.SGD(model.parameters(), lr=1.0)
    opt = thvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())
    inp = torch.ones(1, 2) * (rank + 1)
    model(inp).sum().backward()
    opt.step()
    mean_grad = np.mean([r + 1 for r in range(size)])
    np.testing.assert_allclose(
        model.weight.detach().numpy(), 1.0 - mean_grad, rtol=1e-6)

    # broadcast_parameters / broadcast_optimizer_state consistency.
    model2 = torch.nn.Linear(2, 2)
    with torch.no_grad():
        for p in model2.parameters():
            p.fill_(float(rank + 7))
    thvd.broadcast_parameters(model2.state_dict(), root_rank=0)
    for p in model2.parameters():
        np.testing.assert_allclose(p.detach().numpy(), 7.0)
    opt2 = torch.optim.Adam(model2.parameters(), lr=0.01)
    thvd.broadcast_optimizer_state(opt2, root_rank=0)


def scenario_tensorflow(rank, size):
    # Reference test/test_tensorflow.py core semantics across real ranks.
    import tensorflow as tf

    import horovod_tpu.tensorflow as tfhvd

    x = tf.constant(np.arange(6, dtype=np.float32) + rank)
    out = tfhvd.allreduce(x, average=True)
    np.testing.assert_allclose(
        out.numpy(), np.arange(6) + (size - 1) / 2, rtol=1e-6)

    # Sparse gradients: IndexedSlices → allgather path
    # (reference tensorflow/__init__.py:62-78).
    slices = tf.IndexedSlices(
        values=tf.constant([[float(rank + 1), 0.0]]),
        indices=tf.constant([rank]), dense_shape=tf.constant([size, 2]))
    red = tfhvd.allreduce(slices, average=True)
    assert isinstance(red, tf.IndexedSlices)
    assert red.values.shape[0] == size
    np.testing.assert_allclose(red.values.numpy()[:, 0],
                               (np.arange(size) + 1) / size)

    v = tf.Variable(np.full(3, float(rank), np.float32))
    tfhvd.broadcast_variables([v], root_rank=0)
    np.testing.assert_array_equal(v.numpy(), np.zeros(3))

    w = tf.Variable([float(rank + 1)])
    with tfhvd.DistributedGradientTape() as tape:
        loss = w * w
    (grad,) = tape.gradient(loss, [w])
    want = np.mean([2.0 * (r + 1) for r in range(size)])
    np.testing.assert_allclose(grad.numpy(), [want], rtol=1e-6)

    # tf.function tracing: collective embedded via py_function.
    @tf.function
    def traced(t):
        return tfhvd.allreduce(t, average=False)

    tr = traced(tf.constant([1.0, 2.0]))
    np.testing.assert_allclose(tr.numpy(), [size, 2.0 * size])

    # Keras metric averaging callback.
    from horovod_tpu.keras.callbacks import MetricAverageCallback

    cb = MetricAverageCallback()
    logs = {"loss": float(rank)}
    cb.on_epoch_end(0, logs)
    np.testing.assert_allclose(logs["loss"], (size - 1) / 2)


def scenario_tf_custom_op(rank, size):
    # The native custom-op data path (tensorflow/src/tf_ops.cc): real graph
    # nodes enqueueing into the C++ engine — reference
    # tensorflow/mpi_ops.cc AsyncOpKernel semantics across real ranks.
    import tensorflow as tf

    import horovod_tpu.tensorflow as tfhvd
    from horovod_tpu.tensorflow import tf_ops

    # run_ranks exports HOROVOD_RING_ADDRS → native engine → fast path live.
    expect(tfhvd._custom_ops() is tf_ops,
           "custom-op path must be active under the native engine")

    # Eager average + sum.
    x = tf.constant(np.arange(6, dtype=np.float32) + rank)
    out = tfhvd.allreduce(x, average=True)
    np.testing.assert_allclose(
        out.numpy(), np.arange(6) + (size - 1) / 2, rtol=1e-6)
    out = tfhvd.allreduce(x, average=False)
    np.testing.assert_allclose(
        out.numpy(), size * np.arange(6) + size * (size - 1) / 2, rtol=1e-6)

    # bfloat16 rides the engine's native bf16 kernels; int32 average
    # truncates back to int (the controller post-divide contract).
    xb = tf.cast(tf.fill([8], float(rank + 1)), tf.bfloat16)
    ob = tfhvd.allreduce(xb, average=False)
    expect(ob.dtype == tf.bfloat16, "bf16 in, bf16 out")
    np.testing.assert_allclose(tf.cast(ob, tf.float32).numpy(),
                               sum(range(1, size + 1)))
    xi = tf.constant([1, 2, 5], dtype=tf.int32)
    oi = tfhvd.allreduce(xi, average=True)
    expect(oi.dtype == tf.int32, "int average keeps dtype")
    np.testing.assert_array_equal(oi.numpy(), [1, 2, 5])

    # Allgather with uneven first dims; broadcast from a non-zero root.
    rows = tf.fill([rank + 1, 2], float(rank))
    gathered = tfhvd.allgather(rows)
    expect(gathered.shape[0] == size * (size + 1) // 2,
           f"gathered {gathered.shape}")
    np.testing.assert_allclose(
        gathered.numpy()[:, 0],
        np.concatenate([np.full(r + 1, float(r)) for r in range(size)]))
    b = tfhvd.broadcast(tf.constant([float(rank)]), root_rank=size - 1)
    np.testing.assert_allclose(b.numpy(), [float(size - 1)])

    # tf.function: the collective is a REAL graph node (no EagerPyFunc), and
    # executes correctly.
    @tf.function
    def traced(t):
        return tfhvd.allreduce(t, average=False, name="tfop.mp.traced")

    cf = traced.get_concrete_function(tf.TensorSpec([2], tf.float32))
    op_types = {op.type for op in cf.graph.get_operations()}
    expect("HorovodTpuAllreduce" in op_types, f"graph ops: {op_types}")
    expect("EagerPyFunc" not in op_types, "py_function must not appear")
    tr = traced(tf.constant([1.0, 2.0]))
    np.testing.assert_allclose(tr.numpy(), [size, 2.0 * size])

    # Executor-concurrency burst: 32 independent collectives in one traced
    # step — TF schedules the AsyncOpKernels from its thread pool, so this
    # stresses concurrent ComputeAsync enqueue + engine fusion (the
    # reference's "multiple" fusion-stressing test, test_torch.py).
    @tf.function
    def burst(t):
        outs = [tfhvd.allreduce(t + float(i), average=False,
                                name=f"tfop.mp.burst.{i}")
                for i in range(32)]
        return tf.stack(outs)

    res = burst(tf.constant([float(rank)]))
    want = np.array([[size * (size - 1) / 2 + size * i] for i in range(32)])
    np.testing.assert_allclose(res.numpy(), want)

    # Gradients through the registered custom-op grads
    # (reference tensorflow/mpi_ops.py:82-171): d/dw sum_r mean_r(w^2).
    w = tf.Variable([float(rank + 1)])
    with tfhvd.DistributedGradientTape() as tape:
        loss = w * w
    (grad,) = tape.gradient(loss, [w])
    want = np.mean([2.0 * (r + 1) for r in range(size)])
    np.testing.assert_allclose(grad.numpy(), [want], rtol=1e-6)

    # Allgather gradient: rank's slice of the summed upstream grad.
    v = tf.Variable(tf.fill([rank + 1, 2], float(rank + 1)))
    with tf.GradientTape() as tape:
        g = tfhvd.allgather(v, name="tfop.mp.ag_grad")
        # Weight rows so each rank's slice has a distinct expected grad.
        loss = tf.reduce_sum(g) * float(size)
    gv = tape.gradient(loss, v)
    np.testing.assert_allclose(gv.numpy(),
                               np.full((rank + 1, 2), float(size) * size))

    # Broadcast gradient: all grads land on the root, zeros elsewhere.
    bv = tf.Variable([2.0])
    with tf.GradientTape() as tape:
        out = tfhvd.broadcast(bv, root_rank=0, name="tfop.mp.bc_grad")
        loss = tf.reduce_sum(out) * float(rank + 1)
    gbv = tape.gradient(loss, bv)
    want_root = float(sum(r + 1 for r in range(size)))
    np.testing.assert_allclose(
        gbv.numpy(), [want_root] if rank == 0 else [0.0])

    # Cross-rank validation error surfaces as a TF error: ndim mismatch is
    # rejected by the engine's construct_response matrix.
    try:
        bad = tf.zeros([2] if rank == 0 else [2, 2])
        tfhvd.allreduce(bad, name="tfop.mp.mismatch")
        expect(False, "mismatched ndim must raise")
    except tf.errors.OpError as exc:
        expect("mismatch" in str(exc).lower() or "rank" in str(exc).lower(),
               f"unexpected error text: {exc}")

    # The engine keeps serving after a rejected op.
    ok = tfhvd.allreduce(tf.constant([1.0]), average=False,
                         name="tfop.mp.after_error")
    np.testing.assert_allclose(ok.numpy(), [float(size)])

    # IndexedSlices sparse path rides the custom allgather.
    slices = tf.IndexedSlices(
        values=tf.constant([[float(rank + 1), 0.0]]),
        indices=tf.constant([rank]), dense_shape=tf.constant([size, 2]))
    red = tfhvd.allreduce(slices, average=True)
    expect(isinstance(red, tf.IndexedSlices), "sparse stays sparse")
    np.testing.assert_allclose(red.values.numpy()[:, 0],
                               (np.arange(size) + 1) / size)


def scenario_optimizer(rank, size):
    # End-to-end eager-tier DistributedOptimizer + broadcast_parameters
    # (reference examples/pytorch_mnist.py pattern).
    import jax.numpy as jnp
    import optax

    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones(3) * (rank + 1)}  # deliberately inconsistent
    params = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(params["w"]), 1.0)

    state = tx.init(params)
    grads = {"w": jnp.ones(3) * (rank + 1)}
    updates, state = tx.update(grads, state, params)
    want = -0.1 * np.mean([r + 1 for r in range(size)])
    np.testing.assert_allclose(np.asarray(updates["w"]), want, rtol=1e-6)


def scenario_mxnet(rank, size):
    """MXNet adapter across real ranks, via the in-tree fake mxnet
    (reference test/test_mxnet.py scope)."""
    import fake_mxnet
    mx = fake_mxnet.module()
    sys.modules.setdefault("mxnet", mx)
    import horovod_tpu.mxnet as hvd_mx

    # allreduce_ sum across ranks
    g = mx.nd.array(np.arange(4, dtype=np.float32) + rank)
    hvd_mx.allreduce_(g, average=False, name="mx.grad")
    np.testing.assert_allclose(
        g.asnumpy(), size * np.arange(4) + sum(range(size)))

    # broadcast_parameters: non-root ranks converge to root values
    d = {"w": mx.nd.array(np.full(3, float(rank), dtype=np.float32))}
    hvd_mx.broadcast_parameters(d, root_rank=0)
    np.testing.assert_allclose(d["w"].asnumpy(), 0.0)

    # DistributedOptimizer: identical updates on every rank
    opt = mx.optimizer.Optimizer(learning_rate=1.0)
    dopt = hvd_mx.DistributedOptimizer(opt)
    expect(abs(opt.rescale_grad - 1.0 / size) < 1e-12,
           "rescale_grad not folded by size")
    w = mx.nd.array(np.zeros(2, dtype=np.float32))
    grad = mx.nd.array(np.full(2, float(rank + 1), dtype=np.float32))
    dopt.update(0, w, grad, None)
    mean_grad = sum(r + 1 for r in range(size)) / size
    np.testing.assert_allclose(w.asnumpy(), -mean_grad, rtol=1e-6)

    # ResizeEvalDataIter pads every rank to the max batch count
    class FakeIter:
        def __init__(self, n):
            self.n = n

        def __iter__(self):
            return iter(range(self.n))

        def reset(self):
            pass

    resized = hvd_mx.ResizeEvalDataIter(FakeIter(3 + rank))
    expect(resized.size == 3 + size - 1,
           f"ResizeEvalDataIter got {resized.size}")

    # DistributedEvalMetric replays per-rank updates on rank 0
    Metric = hvd_mx.DistributedEvalMetric(fake_mxnet.EvalMetric)
    m = Metric()
    labels = [mx.nd.array(np.full((2 + rank,), float(rank)))]
    preds = [mx.nd.array(np.full((2 + rank,), float(rank) + 10))]
    m.update(labels, preds)
    if rank == 0:
        expect(m.num_updates == size, f"metric updates {m.num_updates}")
        for r in range(size):
            np.testing.assert_allclose(m.seen[r][0][0], float(r))
            np.testing.assert_allclose(m.seen[r][1][0], float(r) + 10)
    else:
        expect(m.num_updates == 0, "non-root rank must not update")
    # Edge case (reference test_mxnet.py eval-metric scope): a SECOND
    # batch with different per-rank sizes reuses the same collective names
    # — the stable-name response-cache path must not serve stale splits.
    m.update([mx.nd.array(np.full((1 + 2 * rank,), float(rank)))],
             [mx.nd.array(np.full((1 + 2 * rank,), float(rank) - 10))])
    if rank == 0:
        expect(m.num_updates == 2 * size, f"updates {m.num_updates}")
        for r in range(size):
            chunk = m.seen[size + r]
            expect(chunk[0][0].shape == (1 + 2 * r,),
                   f"stale split: {chunk[0][0].shape}")
            np.testing.assert_allclose(chunk[1][0], float(r) - 10)

    # --- reference test_mxnet.py ports (round-4 verdict item #7) ---

    # broadcast_parameters over the dtype x dims matrix at a non-zero root
    # (reference test_horovod_broadcast_grad, test/test_mxnet.py:344-380:
    # int/float dtypes, dims 1-3, root_rank=1).
    root_rank = 1 if size > 1 else 0
    matrix = {}
    for dt in ("int32", "int64", "float32", "float64"):
        for dim, shape in enumerate([(5,), (5, 3), (2, 3, 4)]):
            matrix[f"m.{dt}.{dim}"] = mx.nd.array(
                np.full(shape, rank).astype(dt))
    hvd_mx.broadcast_parameters(matrix, root_rank=root_rank)
    for key, tensor in matrix.items():
        dt = key.split(".")[1]
        expect(str(tensor.dtype) == dt, f"{key} became {tensor.dtype}")
        np.testing.assert_array_equal(
            tensor.asnumpy(), np.full(tensor.shape, root_rank).astype(dt))

    # Deferred-init broadcast TIMING (reference
    # test_horovod_broadcast_deferred_init_parameters:451-474): the hook is
    # installed while the parameter is still unmaterialized; each rank then
    # initializes with per-rank values (the reference's per-rank random
    # seed) and every rank must converge to the ROOT's initial values.
    pd = mx.gluon.parameter.ParameterDict()
    pd["ready"] = fake_mxnet.Parameter(
        "ready", data=mx.nd.array(np.full(3, float(rank), np.float32)))
    pd["late"] = fake_mxnet.Parameter("late")
    hvd_mx.broadcast_parameters(pd, root_rank=0)
    np.testing.assert_allclose(pd["ready"].data().asnumpy(), 0.0)
    pd["late"]._init_impl(np.full(4, 100.0 + rank, np.float32))
    np.testing.assert_allclose(pd["late"].data().asnumpy(), 100.0)

    # DistributedTrainer step across ranks: per-rank different grads must
    # produce IDENTICAL weights everywhere (trainer-rescale semantics:
    # w -= lr * rescale/(size*batch) * sum_r grad_r).
    tp = fake_mxnet.Parameter(
        "tw", data=mx.nd.array(np.ones(2, np.float32)),
        grad=mx.nd.array(np.full(2, float(rank + 1), np.float32)))
    topt = mx.optimizer.Optimizer(learning_rate=0.5, rescale_grad=1.0)
    trainer = hvd_mx.DistributedTrainer([tp], topt)
    trainer.step(batch_size=2)
    grad_sum = sum(r + 1 for r in range(size))
    want_w = 1.0 - 0.5 * (1.0 / (size * 2)) * grad_sum
    np.testing.assert_allclose(tp.data().asnumpy(), want_w, rtol=1e-6)
    all_w = np.asarray(hvd.allgather(
        tp.data().asnumpy().astype(np.float32), name="mx.trainer.w"))
    np.testing.assert_allclose(all_w, want_w, rtol=1e-6)


def scenario_hierarchical(rank, size):
    """Two-level data plane (local ring x cross ring of local roots), the
    NCCLHierarchicalAllreduce / MPIHierarchicalAllgather analogue. Launched
    with -H localhost:2,localhost:2 so 4 ranks form 2 simulated nodes."""
    from horovod_tpu.common import basics

    ctrl = basics.state().controller
    expect(ctrl is not None, "controller not active")
    if hasattr(ctrl, "_local_ring"):  # python engine exposes its rings
        expect(ctrl._local_ring is not None, "hierarchical rings not active")
        expect((ctrl._cross_ring is not None) == (hvd.local_rank() == 0),
               "cross ring must live on local roots only")
    else:  # native engine: C ABI introspection
        expect(ctrl.hierarchical_active,
               "native engine hierarchy not active")

    x = np.arange(8, dtype=np.float32) + rank
    avg = np.asarray(hvd.allreduce(x, average=True, name="h.avg"))
    np.testing.assert_allclose(
        avg, np.arange(8) + (size - 1) / 2.0, rtol=1e-6)
    tot = np.asarray(hvd.allreduce(x, average=False, name="h.sum"))
    np.testing.assert_allclose(
        tot, size * np.arange(8) + sum(range(size)), rtol=1e-6)

    # Variable-dim allgather through the two-level path.
    g = np.full((rank + 1, 3), rank, dtype=np.float32)
    out = np.asarray(hvd.allgather(g, name="h.gather"))
    want = np.concatenate(
        [np.full((r + 1, 3), r, dtype=np.float32) for r in range(size)])
    np.testing.assert_array_equal(out, want)

    # Fusion still applies above the hierarchical data plane.
    handles = [hvd.allreduce_async(np.full(4, float(i + rank)),
                                   average=False, name=f"h.fuse.{i}")
               for i in range(4)]
    for i, h in enumerate(handles):
        got = np.asarray(hvd.synchronize(h))
        np.testing.assert_allclose(
            got, np.full(4, size * i + sum(range(size))), rtol=1e-6)


def scenario_inplace(rank, size):
    from horovod_tpu.common import basics

    ctrl = basics.controller()

    # In-place allreduce: the resolved value IS the enqueued array (no
    # result copy), holding the averaged sum.
    x = np.arange(8, dtype=np.float32) + rank
    out = ctrl.allreduce_async(x, average=True, name="inp.avg",
                               inplace=True).wait()
    expect(out is x, "in-place allreduce returned a different object")
    np.testing.assert_allclose(
        x, np.arange(8, dtype=np.float32) + (size - 1) / 2.0, rtol=1e-6)

    # Value semantics must NOT mutate the caller's input (the zero-copy
    # engine works on a defensive copy).
    y = np.ones(8, np.float32) * rank
    y_before = y.copy()
    res = ctrl.allreduce_async(y, average=False, name="inp.value").wait()
    np.testing.assert_array_equal(y, y_before)
    expect(res is not y, "value allreduce aliased the input")
    np.testing.assert_allclose(res, np.ones(8) * sum(range(size)), rtol=1e-6)

    # Int average in place: float math, truncate-cast back (the reference's
    # output.div_ semantics).
    xi = np.full(4, 3, np.int32) if rank % 2 == 0 else np.full(4, 4, np.int32)
    ctrl.allreduce_async(xi, average=True, name="inp.int",
                         inplace=True).wait()
    vals = [3 if r % 2 == 0 else 4 for r in range(size)]
    expect(xi.dtype == np.int32, f"int buffer became {xi.dtype}")
    np.testing.assert_array_equal(xi, np.full(4, int(sum(vals) / size)))

    # Several in-flight in-place ops: the FUSED path must unpack straight
    # back into each caller buffer.
    bufs = [np.ones(32, np.float32) * (i + rank) for i in range(8)]
    handles = [ctrl.allreduce_async(b, average=False, name=f"inp.fuse.{i}",
                                    inplace=True)
               for i, b in enumerate(bufs)]
    for i, (b, h) in enumerate(zip(bufs, handles)):
        got = h.wait()
        expect(got is b, "fused in-place result is a different object")
        np.testing.assert_allclose(
            b, np.ones(32) * (size * i + sum(range(size))), rtol=1e-6)

    # In-place broadcast: non-roots receive into their own buffer.
    z = np.full(6, float(rank), np.float32)
    got = ctrl.broadcast_async(z, root_rank=1 % size, name="inp.bcast",
                               inplace=True).wait()
    expect(got is z, "in-place broadcast returned a different object")
    np.testing.assert_array_equal(z, np.full(6, float(1 % size)))

    # In-place + wire compression: the fp16 round-trip builds fresh arrays,
    # but the result must still land in the caller's buffer and resolve to
    # it (both engines honor the same contract).
    xc = (np.linspace(-2, 2, 16, dtype=np.float32) * (rank + 1)).copy()
    got = ctrl.allreduce_async(xc, average=True, name="inp.fp16",
                               compression=Compression.fp16,
                               inplace=True).wait()
    expect(got is xc, "in-place compressed allreduce returned a new object")
    scale_f = sum(r + 1 for r in range(size)) / size
    np.testing.assert_allclose(xc, np.linspace(-2, 2, 16) * scale_f,
                               atol=1e-2)

    # torch in-place rides a shared-memory numpy view: zero copies end to
    # end, the tensor's own storage holds the result.
    import torch

    import horovod_tpu.torch as hvd_torch

    t = torch.arange(10, dtype=torch.float32) + rank
    got = hvd_torch.allreduce_(t, average=False, name="inp.torch")
    expect(got is t, "torch allreduce_ returned a different tensor")
    np.testing.assert_allclose(
        t.numpy(), size * np.arange(10) + sum(range(size)), rtol=1e-6)

    # Non-contiguous torch tensor: no shared view exists, so the in-place
    # variant must fall back to the copy-back path — same semantics, same
    # object identity.
    tnc = (torch.arange(16, dtype=torch.float32).reshape(4, 4) + rank).t()
    expect(not tnc.is_contiguous(), "test setup: expected non-contiguous")
    got = hvd_torch.allreduce_(tnc, average=False, name="inp.torch.nc")
    expect(got is tnc, "non-contiguous allreduce_ returned a new tensor")
    want_nc = (size * np.arange(16).reshape(4, 4).T
               + sum(range(size)))
    np.testing.assert_allclose(tnc.numpy(), want_nc, rtol=1e-6)


def scenario_wire_exact(rank, size):
    # Wire-compression plumbing proof, engine-agnostic: constant inputs
    # whose every partial sum is exactly representable in bf16/fp16, so a
    # compressed wire (HOROVOD_RING_WIRE_DTYPE from the parent) must
    # produce EXACT results — any quantization slip shows as inequality.
    # 300k elements spans several transfer chunks.
    x = np.full(300_000, float(rank + 1), np.float32)
    tot = np.asarray(hvd.allreduce(x, average=False, name="wire.exact"))
    want = float(sum(range(1, size + 1)))
    np.testing.assert_array_equal(tot, np.full(300_000, want, np.float32))
    # Second round reuses the same name: pending-name uniqueness was
    # released, and wire scratch buffers are steady-state.
    tot2 = np.asarray(hvd.allreduce(x, average=False, name="wire.exact"))
    np.testing.assert_array_equal(tot2, tot)


def scenario_native_telemetry(rank, size):
    # Native-engine telemetry acceptance (tests/test_native_telemetry.py):
    # under HOROVOD_ENGINE=native with HOROVOD_METRICS=1, steady traffic
    # must light the hvd_native_* series, make controller_health() stop
    # reporting zeros, and carry rank 0's tuned-bucket push to EVERY rank
    # over the synced cycle reply.
    import json as _json

    from horovod_tpu.controller import bucket_scheduler
    from horovod_tpu.core import bindings as _bindings

    for i in range(30):
        out = np.asarray(hvd.allreduce(np.ones(2048, np.float32) * i,
                                       average=False, name=f"nt.{i}"))
        np.testing.assert_allclose(out, float(size) * i)
    # Repeated name: the response cache's bypass path must count hits.
    for _ in range(5):
        np.asarray(hvd.allreduce(np.ones(8, np.float32),
                                 average=False, name="nt.cached"))
    if rank == 0:
        # The synced token slot: the value rides the next cycle reply.
        _bindings.load().hvd_eng_set_tuned_bucket(7 << 20)
    deadline = time.monotonic() + 30.0
    while (bucket_scheduler.current_bucket_bytes() != 7 << 20
           and time.monotonic() < deadline):
        time.sleep(0.05)  # cycles keep ticking; the telemetry loop applies
    expect(bucket_scheduler.current_bucket_bytes() == 7 << 20,
           f"rank {rank}: tuned bucket never arrived over the cycle reply")
    health = hvd.metrics.controller_health()
    expect(health["cycle_seconds_p50"] > 0, f"health zeros: {health}")
    expect(health["fused_bytes_total"] > 0, f"health zeros: {health}")
    snap = hvd.metrics.snapshot()
    expect("hvd_native_cycles_total" in snap, sorted(snap))
    print("HEALTH " + _json.dumps(health), flush=True)
    print("METRICS_SNAPSHOT " + _json.dumps(snap), flush=True)


def scenario_copybench(rank, size):
    # Micro-bench: unfused large-buffer allreduce, value path (1 defensive
    # copy) vs in-place path (0 copies). Prints bytes/sec for the parent
    # test to compare — the in-place path must not be slower; before the
    # zero-copy engine it carried 4 staging copies.
    import time

    from horovod_tpu.common import basics

    ctrl = basics.controller()
    mb = int(os.environ.get("HOROVOD_COPYBENCH_MB", "32"))
    reps = int(os.environ.get("HOROVOD_COPYBENCH_REPS", "6"))
    x = np.ones(mb * (1 << 20) // 4, np.float32)

    def run(inplace):
        # Warmup outside the timed window (connection setup, fusion buffer).
        ctrl.allreduce_async(x, average=False, name=f"cb.warm.{inplace}",
                             inplace=inplace).wait()
        t0 = time.perf_counter()
        for i in range(reps):
            ctrl.allreduce_async(x, average=False,
                                 name=f"cb.{inplace}.{i}",
                                 inplace=inplace).wait()
        dt = time.perf_counter() - t0
        return reps * x.nbytes / dt

    value_bps = run(False)
    inplace_bps = run(True)
    print(f"copybench rank={rank} value={value_bps / 1e6:.1f}MB/s "
          f"inplace={inplace_bps / 1e6:.1f}MB/s "
          f"ratio={inplace_bps / value_bps:.3f}", flush=True)


def scenario_shmbench(rank, size):
    # Local-phase bandwidth probe: repeated hierarchical allreduce on a
    # large buffer. The parent runs this twice — /dev/shm local plane vs
    # HOROVOD_SHM_DISABLE=1 (TCP loopback local ring) — and compares the
    # printed bytes/sec.
    import time

    from horovod_tpu.common import basics

    ctrl = basics.state().controller
    if not getattr(ctrl, "hierarchical_active", False):
        raise AssertionError("hierarchical data plane not active")
    mb = int(os.environ.get("HOROVOD_SHMBENCH_MB", "16"))
    reps = int(os.environ.get("HOROVOD_SHMBENCH_REPS", "6"))
    x = np.ones(mb * (1 << 20) // 4, np.float32)
    ctrl.allreduce_async(x, average=False, name="shmb.warm",
                         inplace=True).wait()
    t0 = time.perf_counter()
    for i in range(reps):
        ctrl.allreduce_async(x, average=False, name=f"shmb.{i}",
                             inplace=True).wait()
    dt = time.perf_counter() - t0
    print(f"shmbench rank={rank} rate={reps * x.nbytes / dt / 1e6:.1f}MB/s",
          flush=True)


def scenario_shmgather(rank, size):
    # Variable-count hierarchical allgather with per-rank blocks LARGER
    # than the shm slot: exercises hvd_shm_allgather_g's multi-pass loop
    # (each pass moves up to slot_bytes of each rank's block). Run with
    # HOROVOD_SHM_SLOT_BYTES=4096 by the parent test.
    from horovod_tpu.common import basics

    ctrl = basics.state().controller
    expect(getattr(ctrl, "hierarchical_active", False),
           "hierarchical data plane not active")
    n = (rank + 1) * 1500  # 6..24 KB of f32 per rank, uneven
    x = (np.arange(n, dtype=np.float32) % 97) + rank
    out = np.asarray(hvd.allgather(x, name="shg.var"))
    parts = [(np.arange((r + 1) * 1500, dtype=np.float32) % 97) + r
             for r in range(size)]
    np.testing.assert_array_equal(out, np.concatenate(parts))
    # And an allreduce larger than the slot through the same group.
    big = np.ones(3000, np.float32) * (rank + 1)
    tot = np.asarray(hvd.allreduce(big, average=False, name="shg.sum"))
    np.testing.assert_allclose(tot, np.ones(3000) * sum(
        r + 1 for r in range(size)), rtol=1e-6)


SCENARIOS = {
    "inplace": scenario_inplace,
    "grouped": scenario_grouped,
    "shmgather": scenario_shmgather,
    "objects": scenario_objects,
    "reducescatter_alltoall": scenario_reducescatter_alltoall,
    "wire_exact": scenario_wire_exact,
    "copybench": scenario_copybench,
    "shmbench": scenario_shmbench,
    "hierarchical": scenario_hierarchical,
    "mxnet": scenario_mxnet,
    "autotune": scenario_autotune,
    "tensorflow": scenario_tensorflow,
    "tf_custom_op": scenario_tf_custom_op,
    "torch": scenario_torch,
    "optimizer": scenario_optimizer,
    "stall": scenario_stall,
    "stall_shutdown": scenario_stall_shutdown,
    "peer_death": scenario_peer_death,
    "fault_survivor": scenario_fault_survivor,
    "fault_metrics": scenario_fault_metrics,
    "elastic_shrink": scenario_elastic_shrink,
    "elastic_join": scenario_elastic_join,
    "elastic_parked": scenario_elastic_parked,
    "elastic_storm": scenario_elastic_storm,
    "elastic_ckpt_chaos": scenario_elastic_ckpt_chaos,
    "elastic_ckpt_chaos_storm": scenario_elastic_ckpt_chaos_storm,
    "metrics_cluster": scenario_metrics_cluster,
    "native_telemetry": scenario_native_telemetry,
    "trace": scenario_trace,
    "doctor": scenario_doctor,
    "allreduce": scenario_allreduce,
    "fusion": scenario_fusion,
    "allgather": scenario_allgather,
    "broadcast": scenario_broadcast,
    "cache": scenario_cache,
    "error_mismatch": scenario_error_mismatch,
    "duplicate_name": scenario_duplicate_name,
}


def main():
    scenario = sys.argv[1]
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    try:
        SCENARIOS[scenario](rank, size)
    finally:
        hvd.shutdown()
    print(f"worker rank={rank} scenario={scenario}: OK", flush=True)


if __name__ == "__main__":
    main()
