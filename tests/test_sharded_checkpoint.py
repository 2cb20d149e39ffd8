"""Fast elastic recovery (ISSUE 15, docs/sharded-checkpoint.md): the
sharded-checkpoint layout + async writer, the SHARD_FETCH/SHARD_DATA
wire plane, digest-addressed p2p restore with peer/disk fallback, the
ckpt_save fault site, and the simcluster joiner-restore scenarios that
stand tier-1 sibling to the @slow mp chaos matrix.
"""

import copy
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from mp_harness import child_env, run_cmd, run_ranks

import horovod_tpu.elastic as elastic_mod
from horovod_tpu.analysis import protocol
from horovod_tpu.analysis.protocol import ProtocolMonitor
from horovod_tpu.common.wire import AuthError, Wire
from horovod_tpu.elastic.shards import (
    ShardExchange,
    ShardFetchError,
    fetch_shard,
    make_memory_provider,
)
from horovod_tpu.fault import FaultInjected, FaultPlan, FaultRule
from horovod_tpu.utils.checkpoint import (
    AsyncShardWriter,
    latest_sharded_checkpoint,
    load_shard,
    pack_objects,
    pack_shard,
    restore_latest_sharded,
    save_shard,
    shard_digest,
    shard_layout,
    shard_path,
    unpack_shard,
    write_manifest,
)


SECRET = b"x" * 32


def _wire_pair():
    a, b = socket.socketpair()
    return Wire(a, secret=SECRET), Wire(b, secret=SECRET)


# ---------------------------------------------------------------------------
# Layout + digest units


def test_shard_layout_deterministic_and_balanced():
    sizes = [100, 1, 1, 50, 50, 100]
    layout = shard_layout(sizes, 3)
    assert layout == shard_layout(sizes, 3)  # pure function
    assert sorted(i for ids in layout for i in ids) == list(range(6))
    weights = [sum(sizes[i] for i in ids) for ids in layout]
    # The greedy lightest-shard walk keeps the spread under the largest
    # single leaf.
    assert max(weights) - min(weights) <= max(sizes)
    # Degenerate worlds still shard.
    assert shard_layout(sizes, 1) == [list(range(6))]
    assert shard_layout([], 2) == [[], []]
    with pytest.raises(ValueError):
        shard_layout(sizes, 0)


def test_shard_digest_keys_on_dtype_shape_and_bytes():
    a = np.arange(6, dtype=np.float32)
    assert shard_digest([a]) == shard_digest([a.copy()])
    assert shard_digest([a]) != shard_digest([a.astype(np.float64)])
    assert shard_digest([a]) != shard_digest([a.reshape(2, 3)])
    b = a.copy()
    b[0] += 1
    assert shard_digest([a]) != shard_digest([b])
    # The empty shard has a digest too (a rank whose layout slot holds
    # no leaves still matches trivially).
    assert shard_digest([]) == shard_digest([])


def test_pack_unpack_validates_digest():
    arrays = [np.arange(4.0), np.ones((2, 2), np.int32)]
    blob = pack_shard(arrays)
    out = unpack_shard(blob, expect_digest=shard_digest(arrays))
    for x, y in zip(arrays, out):
        np.testing.assert_array_equal(x, y)
    with pytest.raises(ValueError, match="digest mismatch"):
        unpack_shard(blob, expect_digest="deadbeef")


# ---------------------------------------------------------------------------
# On-disk layout + torn-save matrix (extends the r12 atomic-ckpt matrix)


def _write_step(directory, step, world, leaves, prefix="sharded_"):
    """One complete sharded step: leaves round-robined over ``world``
    shards + the rank-0 manifest."""
    layout = shard_layout([a.nbytes for a in leaves], world)
    digests = []
    for k in range(world):
        arrays = [leaves[i] for i in layout[k]]
        digests.append(shard_digest(arrays))
        save_shard(directory, step, k, world, arrays, prefix=prefix)
    write_manifest(directory, step, {
        "step": step, "epoch": 1, "world_size": world, "layout": layout,
        "digests": digests, "objects_hex": pack_objects({}),
    }, prefix=prefix)
    return layout, digests


def test_sharded_roundtrip_and_latest(tmp_path):
    leaves = [np.arange(8, dtype=np.float32),
              np.full((3, 3), 7, np.int64), np.ones(1, np.float32)]
    _write_step(str(tmp_path), 1, 2, leaves)
    step, manifest = latest_sharded_checkpoint(str(tmp_path))
    assert step == 1 and manifest["world_size"] == 2
    like = [np.zeros_like(a) for a in leaves]
    step, tree = restore_latest_sharded(str(tmp_path), like)
    assert step == 1
    for x, y in zip(leaves, tree):
        np.testing.assert_array_equal(x, y)


def test_torn_save_matrix_every_rename_point_resumes_whole(tmp_path):
    """The sharded twin of the r12 torn-save matrix: a kill at EVERY
    rename point of shard + manifest leaves a world restore_latest can
    still resume whole — the previous complete step wins until the last
    rename of the new one lands."""
    d = str(tmp_path)
    leaves_v1 = [np.arange(6, dtype=np.float32), np.ones(2, np.float32)]
    _write_step(d, 1, 2, leaves_v1)
    leaves_v2 = [a + 10 for a in leaves_v1]
    layout = shard_layout([a.nbytes for a in leaves_v2], 2)
    digests = [shard_digest([leaves_v2[i] for i in layout[k]])
               for k in range(2)]
    manifest = {"step": 2, "epoch": 2, "world_size": 2, "layout": layout,
                "digests": digests, "objects_hex": pack_objects({})}

    def check_resumes_v1():
        step, tree = restore_latest_sharded(d, list(leaves_v1))
        assert step == 1, f"torn step 2 must not win (got {step})"
        for x, y in zip(leaves_v1, tree):
            np.testing.assert_array_equal(x, y)

    # Kill point 1: shard 0's write died before its rename (tmp only).
    os.makedirs(tmp_path / "sharded_2.shard0of2.tmp.999")
    check_resumes_v1()
    # Kill point 2: shard 0 renamed whole, shard 1 + manifest missing.
    save_shard(d, 2, 0, 2, [leaves_v2[i] for i in layout[0]])
    check_resumes_v1()
    # Kill point 3: both shards whole, manifest died mid-write.
    save_shard(d, 2, 1, 2, [leaves_v2[i] for i in layout[1]])
    os.makedirs(tmp_path / "sharded_2.manifest.tmp.999")
    check_resumes_v1()
    # Kill point 4: manifest renamed BEFORE a shard landed (a writer
    # ordering no process produces alone, but two ranks' async writers
    # race): completeness still gates on every shard's presence.
    import shutil
    shutil.rmtree(tmp_path / "sharded_2.shard1of2")
    write_manifest(d, 2, manifest)
    check_resumes_v1()
    # Final rename lands: step 2 becomes the resume point.
    save_shard(d, 2, 1, 2, [leaves_v2[i] for i in layout[1]])
    step, tree = restore_latest_sharded(d, list(leaves_v1))
    assert step == 2
    for x, y in zip(leaves_v2, tree):
        np.testing.assert_array_equal(x, y)


def test_corrupt_shard_bytes_fall_back_to_previous_step(tmp_path):
    d = str(tmp_path)
    leaves = [np.arange(4, dtype=np.float32)]
    _write_step(d, 1, 1, leaves)
    _write_step(d, 2, 1, [leaves[0] + 5])
    # Bit-rot / torn write inside step 2's shard payload: the manifest
    # digest no longer matches, so restore must fall back to step 1.
    with open(os.path.join(shard_path(d, 2, 0, 1), "shard.bin"),
              "r+b") as f:
        f.seek(40)
        f.write(b"\xff\xff\xff")
    step, tree = restore_latest_sharded(d, list(leaves))
    assert step == 1
    np.testing.assert_array_equal(tree[0], leaves[0])


# ---------------------------------------------------------------------------
# Async writer


def test_async_writer_persists_and_prunes(tmp_path):
    w = AsyncShardWriter(str(tmp_path), keep=2)
    leaves = [np.arange(5, dtype=np.float32)]
    for step in (1, 2, 3, 4):
        arrays = [leaves[0] + step]
        w.submit(step, 0, 1, arrays,
                 manifest={"step": step, "epoch": 1, "world_size": 1,
                           "layout": [[0]],
                           "digests": [shard_digest(arrays)],
                           "objects_hex": pack_objects({})})
        assert w.flush(10.0), "writer never drained"
    names = sorted(os.listdir(tmp_path))
    assert not any(".tmp." in n for n in names)
    steps_on_disk = {n.split(".")[0] for n in names}
    assert steps_on_disk == {"sharded_3", "sharded_4"}, names
    step, tree = restore_latest_sharded(str(tmp_path), list(leaves))
    assert step == 4
    np.testing.assert_array_equal(tree[0], leaves[0] + 4)
    # A restarted writer never shadows the persisted history.
    w2 = AsyncShardWriter(str(tmp_path), keep=2)
    assert w2.next_step() == 5
    w.close()


def test_prune_never_deletes_the_newest_complete_step(tmp_path):
    """Review fix pin: the latest-wins buffers drop different steps on
    different ranks, so raw step-age pruning could delete the only step
    every rank finished. The prune cutoff must stop at the newest
    COMPLETE step no matter how far the current step has run ahead."""
    d = str(tmp_path)
    leaves = [np.arange(4, dtype=np.float32), np.ones(2, np.float32)]
    _write_step(d, 1, 2, leaves)        # complete
    layout = shard_layout([a.nbytes for a in leaves], 2)
    # Steps 2..4: this rank persisted its shard 0, the slow peer dropped
    # its shard 1 — all incomplete.
    for step in (2, 3, 4):
        save_shard(d, step, 0, 2, [leaves[i] for i in layout[0]])
    w = AsyncShardWriter(d, keep=2)
    w._prune(4)
    assert latest_sharded_checkpoint(d)[0] == 1, sorted(os.listdir(d))
    assert os.path.isdir(tmp_path / "sharded_1.shard1of2")
    # Once a newer step completes, ordinary keep-2 retention resumes.
    _write_step(d, 5, 2, [a + 1 for a in leaves])
    w._prune(5)
    steps_left = {n.split(".")[0] for n in os.listdir(d)}
    assert "sharded_1" not in steps_left
    assert latest_sharded_checkpoint(d)[0] == 5
    w.close()


def test_async_writer_latest_wins_drops_intermediate(tmp_path,
                                                     monkeypatch):
    w = AsyncShardWriter(str(tmp_path), keep=2)
    gate = threading.Event()
    persisted = []
    orig = AsyncShardWriter._persist

    def slow_persist(self, snap):
        gate.wait(10.0)
        persisted.append(snap["step"])
        orig(self, snap)

    monkeypatch.setattr(AsyncShardWriter, "_persist", slow_persist)
    arr = [np.ones(3, np.float32)]
    w.submit(1, 0, 1, arr)
    time.sleep(0.1)  # writer thread is blocked inside persist(step 1)
    w.submit(2, 0, 1, arr)
    w.submit(3, 0, 1, arr)  # overwrites pending step 2
    gate.set()
    assert w.flush(10.0)
    assert w.dropped == 1
    assert persisted == [1, 3], persisted
    w.close()


def test_ckpt_save_fault_site_validation_and_raise(tmp_path):
    # r7 site-validation pattern: wrong action/site combos fail AT LOAD.
    FaultRule(site="ckpt_save", action="kill", at=1)
    FaultRule(site="ckpt_save", action="delay", at=1, seconds=0.01)
    with pytest.raises(ValueError, match="wedge"):
        FaultRule(site="ckpt_save", action="wedge")
    with pytest.raises(ValueError, match="drop"):
        FaultRule(site="ckpt_save", action="drop", at=1)
    with pytest.raises(ValueError, match="cycle"):
        FaultRule(site="ckpt_save", action="leave", at=1)
    plan = FaultPlan.from_json(
        '{"faults": [{"site": "ckpt_save", "action": "raise", "at": 1}]}')
    with pytest.raises(FaultInjected):
        plan.fire("ckpt_save")


def test_async_writer_survives_injected_raise(tmp_path):
    """An injected failure INSIDE the writer thread (chaos action
    "raise") is logged + recorded, never raised into the step loop; the
    next snapshot persists normally."""
    from horovod_tpu import fault

    fault.install_plan(FaultPlan.from_json(
        '{"faults": [{"site": "ckpt_save", "action": "raise", "at": 1}]}'))
    try:
        w = AsyncShardWriter(str(tmp_path), keep=2)
        arr = [np.ones(2, np.float32)]
        w.submit(1, 0, 1, arr)
        assert w.flush(10.0)
        assert isinstance(w.last_error, FaultInjected)
        assert w.written_steps == 0
        w.submit(2, 0, 1, arr)
        assert w.flush(10.0)
        assert w.written_steps == 1
        w.close()
    finally:
        fault.reset()


# ---------------------------------------------------------------------------
# Wire plane


def test_shard_frames_are_invisible_to_the_data_stream():
    a, b = _wire_pair()
    seen = []
    b.set_shard_callback(lambda event, info: seen.append((event, info)))
    blob = pack_shard([np.arange(3.0)])
    a.send_shard_fetch({"shard": 0, "digest": "d", "leaves": [0],
                        "req": 2, "owner": 1})
    a.send_shard_data({"shard": 0, "digest": "d", "req": 2, "found": True,
                       "data": blob})
    a.send_obj({"tick": 1})  # the lockstep frame the reader wants
    assert b.recv_obj() == {"tick": 1}
    assert [e for e, _ in seen] == ["fetch", "data"]
    assert seen[1][1]["data"] == blob
    a.close(), b.close()


def test_shard_frame_without_callback_is_dropped_not_fatal():
    a, b = _wire_pair()
    a.send_shard_data({"shard": 0, "digest": "d", "req": 1,
                       "found": False, "data": None})
    a.send_obj({"after": True})
    assert b.recv_obj() == {"after": True}
    a.close(), b.close()


def test_shard_frame_during_hello_is_auth_error():
    a, b = _wire_pair()
    a.send_shard_fetch({"shard": 0, "digest": "d", "leaves": [],
                        "req": 1, "owner": 2})
    with pytest.raises(AuthError, match="shard_fetch frame during hello"):
        b.recv_hello()
    a.close(), b.close()


def test_reshape_ack_drain_discards_shard_traffic():
    a, b = _wire_pair()
    a.send_shard_fetch({"shard": 0, "digest": "d", "leaves": [],
                        "req": 1, "owner": 2})
    a.send_shard_data({"shard": 0, "digest": "d", "req": 1,
                       "found": False, "data": None})
    a.send_join({"ack": 2})
    b.recv_reshape_ack(2)  # shard frames are dead-epoch traffic
    a.send_obj({"fresh": True})
    assert b.recv_obj() == {"fresh": True}
    a.close(), b.close()


def test_monitor_shard_kinds_legal_in_steady_violation_when_parked():
    rec = protocol._Recorder()
    m = ProtocolMonitor("worker", recorder_=rec)
    m.observe("send", "data")  # hello -> steady
    m.observe("send", "shard_fetch", {"shard": 0})
    m.observe("recv", "shard_data", {"shard": 0})
    m.observe("recv", "shard_fetch", {"shard": 1})
    m.observe("send", "shard_data", {"shard": 1})
    assert m.state == "steady" and rec.report()["ok"]
    rec2 = protocol._Recorder()
    j = ProtocolMonitor("joiner", recorder_=rec2)
    j.observe("send", "join", {"join": True})
    j.observe("send", "shard_fetch", {"shard": 0})
    report = rec2.report()
    assert not report["ok"]
    assert "parked joiner sent shard traffic" in \
        report["violations"][0]["detail"]


# ---------------------------------------------------------------------------
# Fallback chain: dead owner -> disk (manifest-validated) -> loud error


def test_fetch_shard_falls_back_to_disk_when_no_holder(tmp_path):
    d = str(tmp_path)
    leaves = [np.arange(7, dtype=np.float32), np.ones(2, np.float32)]
    layout, digests = _write_step(d, 3, 2, leaves)
    ex = ShardExchange()  # no controller: every peer attempt is moot
    arrays, source = fetch_shard(ex, 0, digests[0], layout[0],
                                 holders=[], disk_dir=d)
    assert source == "disk"
    for i, arr in zip(layout[0], arrays):
        np.testing.assert_array_equal(arr, leaves[i])


def test_fetch_shard_error_names_every_source_tried(tmp_path):
    ex = ShardExchange()
    with pytest.raises(ShardFetchError) as exc_info:
        fetch_shard(ex, 1, "feedface", [0], holders=[],
                    disk_dir=str(tmp_path))
    msg = str(exc_info.value)
    assert "disk" in msg and "feedface" in msg


def test_fetch_wait_torn_by_reshape_fence_raises_retryable():
    """Kill-mid-shard-fetch contract: a reshape landing while the
    restore thread waits on a fetch raises the SAME retryable
    RanksChangedError as any in-flight collective — hvd.elastic.run
    then retries the whole restore at the new epoch."""
    import threading as _threading
    from types import SimpleNamespace

    from horovod_tpu.common.wire import RanksChangedError
    from horovod_tpu.elastic.shards import _Fetch

    fence = RanksChangedError("membership changed", rank=1, size=2,
                              epoch=3)
    ctl = SimpleNamespace(_reshape_fence=None,
                          _closed=_threading.Event(),
                          topo=SimpleNamespace(rank=1))
    ex = ShardExchange()
    ex._ctl = ctl
    fetch = _Fetch(0, "d")

    def tear():
        time.sleep(0.05)
        ctl._reshape_fence = fence

    t = threading.Thread(target=tear, name="test-tear", daemon=True)
    t.start()
    with pytest.raises(RanksChangedError) as exc_info:
        ex.wait(fetch, timeout=5.0)
    assert exc_info.value is fence
    t.join(timeout=5)
    # A shut-down controller aborts the wait loudly too.
    ctl._reshape_fence = None
    ctl._closed.set()
    with pytest.raises(RuntimeError, match="shut down"):
        ex.wait(_Fetch(1, "e"), timeout=5.0)


def test_memory_provider_serves_only_matching_digest():
    flat = [np.arange(4.0), np.ones(3, np.float32)]
    provider = make_memory_provider(lambda: flat)
    digest = shard_digest([np.ascontiguousarray(flat[0])])
    blob = provider(0, digest, [0])
    assert blob is not None
    np.testing.assert_array_equal(unpack_shard(blob, digest)[0], flat[0])
    assert provider(0, "wrong", [0]) is None  # racing commit shape
    assert provider(0, digest, [7]) is None   # out-of-range leaf


# ---------------------------------------------------------------------------
# State restore semantics (single process)


def test_restore_is_one_materialization_per_value(monkeypatch):
    """The r12 path deep-copied every tracked value TWICE per restore
    (once into the live attribute, once re-committing). Pin the new
    contract: one deepcopy per value, and the restore point stays
    independent of the live attribute."""
    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.State(step=1, weights=np.arange(4.0))
    calls = []
    orig = copy.deepcopy
    monkeypatch.setattr(elastic_mod.copy, "deepcopy",
                        lambda x, *a: (calls.append(1), orig(x, *a))[1])
    state.restore()
    assert len(calls) == 2, f"expected 1 deepcopy per value, saw {calls}"
    # Independence: mutating the live value must not corrupt the
    # restore point.
    state.weights[0] = 99.0
    state.restore()
    assert state.weights[0] == 0.0


def test_state_construction_before_init_stays_local(tmp_path):
    """Review fix pin: commit() is purely local by contract — building
    (and committing) a State BEFORE hvd.init() must keep working, as it
    did pre-r15; only restore() needs the runtime."""
    import sys

    code = (
        "import numpy as np\n"
        "import horovod_tpu as hvd\n"
        "state = hvd.elastic.State(step=0, weights=np.zeros(4))\n"
        "state.step = 5\n"
        "state.commit()\n"
        "print('PREINIT_OK', state._commit_world)\n")
    env = child_env()
    for scrub in ("HOROVOD_RANK", "HOROVOD_SIZE", "HOROVOD_CKPT_DIR",
                  "HOROVOD_CONTROLLER_ADDR"):
        env.pop(scrub, None)
    res = run_cmd([sys.executable, "-c", code], timeout=120, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PREINIT_OK 1" in res.stdout


def test_state_commit_feeds_async_writer(tmp_path, monkeypatch):
    import horovod_tpu as hvd

    hvd.init()
    monkeypatch.setenv("HOROVOD_CKPT_DIR", str(tmp_path))
    state = hvd.elastic.State(step=0, weights=np.arange(6, dtype=np.float32))
    for s in range(1, 4):
        state.step = s
        state.weights = state.weights + 1
        state.commit()
    assert state.flush_checkpoints(15.0)
    latest = latest_sharded_checkpoint(str(tmp_path))
    assert latest is not None
    step, manifest = latest
    assert manifest["world_size"] == 1
    leaves = load_shard(
        shard_path(str(tmp_path), step, 0, 1),
        expect_digest=manifest["digests"][0])
    np.testing.assert_array_equal(
        leaves[0], np.arange(6, dtype=np.float32) + 3)
    # The step counter is an OBJECT leaf riding the manifest — its
    # Python type survives a disk roundtrip.
    from horovod_tpu.utils.checkpoint import unpack_objects

    objs = unpack_objects(manifest)
    values = sorted(objs.values())
    assert 3 in values and all(isinstance(v, int) for v in values)


# ---------------------------------------------------------------------------
# simcluster: the p2p restore plane at fleet scale, in-process (tier-1
# siblings of the @slow mp chaos below; docs/simcluster.md)


def _sim_committed_model(n_leaves=128, seed=15):
    rng = np.random.default_rng(seed)
    flat = [rng.standard_normal(int(rng.integers(16, 512)))
            .astype(np.float32) for _ in range(n_leaves)]
    return flat


def _sim_shard_plane(flat, world):
    layout = shard_layout([a.nbytes for a in flat], world)
    digests, blobs = [], {}
    for k in range(world):
        arrays = [flat[i] for i in layout[k]]
        d = shard_digest(arrays)
        digests.append(d)
        blobs[d] = pack_shard(arrays)
    return layout, digests, blobs


def _drive_until_replied(cluster, joiner, keys, max_steps=80):
    for _ in range(max_steps):
        if not (keys - set(joiner.shard_replies)):
            return
        cluster.run_step([])
    missing = sorted(keys - set(joiner.shard_replies))
    raise AssertionError(
        f"shard replies never arrived for {missing[:5]} "
        f"(+{max(0, len(missing) - 5)} more)")


def test_sim_64rank_joiner_restores_via_peer_shards():
    """ISSUE 15 acceptance: a 64-logical-rank elastic job loses a rank,
    admits a joiner, and the joiner restores the whole committed model
    by pulling every shard from SPREAD surviving owners through the
    coordinator star — bit-identical bytes, zero protocol violations,
    and the doctor naming nothing unhealthy."""
    from horovod_tpu.elastic.shards import ShardExchange
    from horovod_tpu.sim import SimCluster, allreduce_spec

    flat = _sim_committed_model()
    with SimCluster(ranks=64, elastic=True) as c:
        c.run_step([allreduce_spec("warm",
                                   lambda r: np.ones(1, np.float32))])
        c.kill(5)
        res = c.run_step([allreduce_spec(
            "shrunk", lambda r: np.ones(1, np.float32))])
        assert float(res.results0["shrunk"][0]) == 63.0
        joiner = c.spawn_joiner()
        res = c.run_step([allreduce_spec(
            "regrown", lambda r: np.ones(1, np.float32))])
        assert c.size == 64 and float(res.results0["regrown"][0]) == 64.0

        world = c.controller.topo.size
        layout, digests, blobs = _sim_shard_plane(flat, world)
        # Rank 0 = the real controller: the production exchange serves
        # and relays; survivors serve from their stores; the joiner's is
        # empty — it must fetch everything.
        ex = ShardExchange()
        ex.install(c.controller)
        ex.set_provider(lambda shard, digest, leaves: blobs.get(digest))
        for rank in c.alive_worker_ranks:
            w = c.workers[rank]
            w.enable_shards({} if w is joiner else dict(blobs))
        holders = [r for r in [0] + c.alive_worker_ranks
                   if c.workers.get(r) is not joiner]
        keys = set()
        for k in range(world):
            owner = holders[k % len(holders)]
            joiner.send_shard_fetch(k, digests[k], owner)
            keys.add((k, digests[k]))
        _drive_until_replied(c, joiner, keys)
        rebuilt = [None] * len(flat)
        for k in range(world):
            info = joiner.shard_replies[(k, digests[k])]
            assert info["found"], f"shard {k} not served"
            for i, arr in zip(layout[k],
                              unpack_shard(info["data"], digests[k])):
                rebuilt[i] = arr
        for orig, got in zip(flat, rebuilt):
            np.testing.assert_array_equal(orig, got)
        report = c.doctor_report()
        assert report["counts"]["critical"] == 0 \
            and report["counts"]["warning"] == 0, report["findings"]
    assert c.protocheck_report["ok"], \
        c.protocheck_report["violations"][:5]
    assert c.protocheck_report["transitions"] > 1000


def test_sim_dead_owner_and_stale_copy_fall_back(tmp_path):
    """The fallback chain, deterministically: a fetch toward an owner
    whose wire is GONE answers found=False immediately (the coordinator
    relay, not a timeout); an owner whose memory copy no longer matches
    declines the same way; a real holder serves; and a shard NO live
    member holds comes back from the manifest-validated disk step."""
    from horovod_tpu.elastic.shards import ShardExchange, _disk_shard
    from horovod_tpu.sim import SimCluster

    flat = [np.arange(32, dtype=np.float32),
            np.full(16, 3.0, np.float32)]
    with SimCluster(ranks=8, elastic=True) as c:
        world = 8
        layout, digests, blobs = _sim_shard_plane(flat, world)
        ex = ShardExchange()
        ex.install(c.controller)
        ex.set_provider(lambda shard, digest, leaves: None)  # rank 0 stale
        for rank in c.alive_worker_ranks:
            c.workers[rank].enable_shards(
                dict(blobs) if rank == 3 else {})
        requester = c.workers[1]
        requester.enable_shards({})
        # Dead owner: rank 99 has no wire — relay answers at once.
        requester.send_shard_fetch(0, digests[0], 99)
        # Stale copy: rank 2's store is empty (its commit moved on).
        requester.send_shard_fetch(1, digests[1], 2)
        _drive_until_replied(c, requester,
                             {(0, digests[0]), (1, digests[1])})
        assert requester.shard_replies[(0, digests[0])]["found"] is False
        assert requester.shard_replies[(1, digests[1])]["found"] is False
        # Next holder in the chain (rank 3) serves both.
        requester.shard_replies.clear()
        requester.send_shard_fetch(0, digests[0], 3)
        requester.send_shard_fetch(1, digests[1], 3)
        _drive_until_replied(c, requester,
                             {(0, digests[0]), (1, digests[1])})
        for k in (0, 1):
            info = requester.shard_replies[(k, digests[k])]
            assert info["found"]
            for i, arr in zip(layout[k],
                              unpack_shard(info["data"], digests[k])):
                np.testing.assert_array_equal(arr, flat[i])
    assert c.protocheck_report["ok"]
    # Memory copies all gone entirely: the on-disk step (written by the
    # async tier) still resumes the shard, manifest-validated.
    d = str(tmp_path)
    disk_layout, disk_digests = _write_step(d, 7, 2, flat)
    arrays = _disk_shard(d, 1, disk_digests[1], "sharded_")
    assert arrays is not None
    for i, arr in zip(disk_layout[1], arrays):
        np.testing.assert_array_equal(arr, flat[i])


# ---------------------------------------------------------------------------
# mp acceptance (chaos): writer-kill + storm with the disk tier on.
# Heavy multi-process runs stay @slow (tier-1 budget); their in-process
# siblings are the simcluster tests below.


@pytest.mark.slow  # tier-1 sibling: test_sim_64rank_joiner_restores_via_peer_shards
def test_elastic_ckpt_writer_kill_survives(tmp_path):
    """Chaos: rank 2 is SIGKILLed INSIDE its async shard writer (the
    ckpt_save site) mid-save. The survivors re-form, p2p-restore, train
    on, and the shared checkpoint directory still holds a complete
    resumable step — the torn write is invisible to restore_latest."""
    plan = json.dumps({"faults": [
        {"site": "ckpt_save", "action": "kill", "at": 3, "rank": 2}]})
    outputs = run_ranks(
        "elastic_ckpt_chaos", size=3, timeout=150.0,
        extra_env={"HOROVOD_ELASTIC": "1", "HOROVOD_METRICS": "1",
                   "HOROVOD_CKPT_DIR": str(tmp_path)},
        per_rank_env={2: {"HOROVOD_FAULT_PLAN": plan}},
        allowed_exit={2: (-9,)})
    for rank in (0, 1):
        assert "ELASTIC size=2 epoch=2" in outputs[rank], outputs[rank]
    snap_line = [ln for ln in outputs[0].splitlines()
                 if ln.startswith("METRICS_SNAPSHOT ")][-1]
    snap = json.loads(snap_line.split(" ", 1)[1])
    commits = snap.get("hvd_ckpt_commits_total", {}).get("values")
    assert commits and commits[0][1] > 0, snap.get("hvd_ckpt_commits_total")
    latest = latest_sharded_checkpoint(str(tmp_path))
    assert latest is not None, sorted(os.listdir(tmp_path))


@pytest.mark.slow  # tier-1 sibling: test_sim_dead_owner_mid_fetch_falls_back
def test_elastic_ckpt_storm_with_slow_writer(tmp_path):
    """Kill+join storm with the disk tier on and rank 1's writer delayed
    (ckpt_save delay): reshapes, p2p restores, a joiner's shard fetches
    and the async writer all overlap — the world still settles at 3
    ranks with bit-identical state."""
    kill = json.dumps({"faults": [
        {"site": "cycle", "action": "kill", "at": 40, "rank": 2}]})
    join = json.dumps({"faults": [
        {"site": "cycle", "action": "join", "at": 400, "rank": 1},
        {"site": "ckpt_save", "action": "delay", "at": 1, "times": 5,
         "seconds": 0.05, "rank": 1}]})
    outputs = run_ranks(
        "elastic_ckpt_chaos_storm", size=3, timeout=180.0,
        extra_env={"HOROVOD_ELASTIC": "1", "HOROVOD_METRICS": "1",
                   "HOROVOD_CKPT_DIR": str(tmp_path)},
        per_rank_env={1: {"HOROVOD_FAULT_PLAN": join},
                      2: {"HOROVOD_FAULT_PLAN": kill}},
        allowed_exit={2: (-9,)})
    for rank in (0, 1):
        assert "ELASTIC size=3" in outputs[rank], outputs[rank]
    # The joiner (clone in rank 1's stream, which interleaves with its
    # parent's — hence regex, not line parsing) pulled shards from
    # peers: some member's per-process counter is non-zero.
    import re

    fetches = [int(m) for out in outputs
               for m in re.findall(r"SHARD_FETCHES (\d+)", out)]
    assert fetches and max(fetches) >= 1, (fetches, outputs[1][-2000:])
    # Review fix pin: the joiner adopts rank 0's save-step at restore,
    # so the POST-JOIN world keeps completing steps — the newest
    # complete step on disk must be a 3-shard one, not a pre-join relic.
    latest = latest_sharded_checkpoint(str(tmp_path))
    assert latest is not None, sorted(os.listdir(tmp_path))
    assert latest[1]["world_size"] == 3, latest
