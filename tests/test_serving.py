"""Serving tier: paged KV blocks, continuous-batching scheduler, engine
parity vs bare ``generate()``, admission control, preemption-by-
recompute, serving metrics, and the doctor's saturation rules
(docs/serving.md).

The parity contract under test is the acceptance bar: a mixed-length
workload through the continuous batcher produces, per request, EXACTLY
the tokens that request gets from ``generate()`` alone — in f32, where
greedy argmax is reproducible across decode paths (the
``tp_decode_profile`` convention). The heavy 32-request TP acceptance
run is @slow; a light sibling covers both paths in tier-1.
"""

import dataclasses
import importlib.util
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models.llama import (
    LLAMA_TINY,
    LlamaLM,
    generate,
    llama_tp_param_specs,
)
from horovod_tpu.ops.decode_attention import (
    decode_attention,
    paged_cache_write,
    paged_decode_attention,
    paged_gather_attention,
)
from horovod_tpu.serving import (
    NULL_BLOCK,
    BlockPool,
    CancelledError,
    OutOfBlocks,
    RejectedError,
    Request,
    Scheduler,
    ServingConfig,
    zero_stats,
)
from horovod_tpu.serving.engine import ServingEngine
from horovod_tpu.serving.kv_blocks import padded_table

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# f32 end to end: greedy argmax is then exactly reproducible across the
# contiguous, paged, and TP decode paths (bf16 reduction order flips
# argmax ties — examples/tp_decode_profile.py documents the same).
CFG = dataclasses.replace(LLAMA_TINY, dtype=jnp.float32, max_seq_len=64)
MODEL = LlamaLM(CFG)
# One config shared by the parity tests so the decode step compiles once
# for the whole file.
SCFG = ServingConfig(max_batch=4, block_size=8, num_blocks=0,
                     queue_depth=64, max_seq_len=64)


@pytest.fixture(scope="module")
def tiny_variables():
    return jax.jit(MODEL.init)(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def tp_setup(tiny_variables):
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    specs = llama_tp_param_specs(tiny_variables["params"], axis="model")
    sharded = {"params": jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tiny_variables["params"], specs)}
    return mesh, sharded


def _mixed_workload(rng, n, prompt_lens, new_tokens):
    prompts = [rng.randint(0, CFG.vocab_size,
                           (prompt_lens[i % len(prompt_lens)],)
                           ).astype(np.int32) for i in range(n)]
    news = [new_tokens[i % len(new_tokens)] for i in range(n)]
    return prompts, news


def _assert_parity(engine, variables, prompts, news, handles, mesh=None):
    for i, (prompt, n, handle) in enumerate(zip(prompts, news, handles)):
        got = handle.result(timeout=0)
        if mesh is not None:
            with mesh:
                ref = generate(MODEL, variables, jnp.asarray(prompt[None]),
                               max_new_tokens=n)
        else:
            ref = generate(MODEL, variables, jnp.asarray(prompt[None]),
                           max_new_tokens=n)
        want = list(np.asarray(ref)[0, len(prompt):])
        assert got == want, (
            f"request {i} (prompt {len(prompt)}, {n} new) diverged from "
            f"bare generate():\n got={got}\nwant={want}")


# ---------------------------------------------------------------------------
# Block pool


def test_block_pool_alloc_free_reuse():
    pool = BlockPool(4, block_size=8)
    assert pool.blocks_for(0) == 0
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(8) == 1
    assert pool.blocks_for(9) == 2
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {1, 2} and NULL_BLOCK not in (a, b)
    assert pool.blocks_in_use == 2 and pool.free_blocks == 2
    pool.free([a])
    # The freed block is reusable immediately; accounting stays exact.
    c = pool.alloc()
    assert c == a
    assert pool.peak_in_use == 2
    assert pool.stats()["block_allocs"] == 3
    assert pool.stats()["block_frees"] == 1
    assert pool.utilization() == 0.5
    pool.free([b, c])
    assert pool.blocks_in_use == 0 and pool.free_blocks == 4


def test_block_pool_exhaustion_and_all_or_nothing():
    pool = BlockPool(3, block_size=4)
    held = pool.alloc_many(2)
    with pytest.raises(OutOfBlocks):
        pool.alloc_many(2)           # only 1 free: must not half-allocate
    assert pool.blocks_in_use == 2   # the failed alloc_many took nothing
    pool.alloc()
    with pytest.raises(OutOfBlocks):
        pool.alloc()
    pool.free(held)
    assert pool.can_fit(2)


def test_block_pool_free_validation():
    pool = BlockPool(2, block_size=4)
    a = pool.alloc()
    pool.free([a])
    with pytest.raises(ValueError, match="double free"):
        pool.free([a])
    with pytest.raises(ValueError, match="null block"):
        pool.free([NULL_BLOCK])
    with pytest.raises(ValueError, match="not allocated"):
        pool.free([2])


def test_padded_table():
    assert padded_table([3, 1], 4) == [3, 1, NULL_BLOCK, NULL_BLOCK]
    with pytest.raises(ValueError):
        padded_table([1, 2, 3], 2)


# ---------------------------------------------------------------------------
# Scheduler (pure bookkeeping)


def _req(rid, prompt_len, max_new):
    return Request(rid=rid, prompt=np.zeros((prompt_len,), np.int32),
                   max_new_tokens=max_new)


def test_scheduler_admission_and_rejects():
    sched = Scheduler(BlockPool(8, 4), max_batch=2, queue_depth=2,
                      max_seq_len=16)
    with pytest.raises(RejectedError, match="max_seq_len"):
        sched.check_admissible(10, 10)           # window overflow
    with pytest.raises(ValueError):
        sched.check_admissible(0, 4)             # malformed
    big = Scheduler(BlockPool(2, 4), max_batch=2, queue_depth=2,
                    max_seq_len=64)
    with pytest.raises(RejectedError, match="KV blocks"):
        big.check_admissible(8, 16)              # can never fit the pool
    sched.enqueue(_req(0, 4, 4))
    sched.enqueue(_req(1, 4, 4))
    with pytest.raises(RejectedError, match="queue is full"):
        sched.check_admissible(4, 4)
    assert sched.rejected == 2                   # never-fit + queue-full
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [0, 1]   # FIFO
    assert sorted(r.slot for r in admitted) == [0, 1]
    assert all(len(r.blocks) == 1 for r in admitted)


def test_scheduler_preempts_youngest_and_requeues_front():
    pool = BlockPool(4, 4)
    sched = Scheduler(pool, max_batch=2, queue_depth=4, max_seq_len=16)
    r0, r1 = _req(0, 6, 8), _req(1, 6, 8)
    sched.enqueue(r0)
    sched.enqueue(r1)
    assert len(sched.admit()) == 2               # 2 blocks each: pool full
    r0.tokens.extend([5, 5, 5])                  # r0 grows to 9 positions
    preempted = sched.ensure_decode_capacity()
    assert preempted == [r1]                     # youngest loses its blocks
    assert r1.state == "waiting" and r1.blocks == [] and r1.slot is None
    assert sched.waiting[0] is r1                # front of the queue
    assert sched.preempted == 1 and r1.preemptions == 1
    assert len(r0.blocks) == 3                   # the freed block moved over
    # r1 readmits once r0 retires.
    sched.retire(r0, "finished")
    assert [r.rid for r in sched.admit()] == [1]


# ---------------------------------------------------------------------------
# Paged decode attention (ops)


def _reference(q, k_win, v_win, lens, hkv):
    b, s, h, d = q.shape
    L = k_win.shape[1]
    k4 = k_win.reshape(b, L, hkv, d)
    v4 = v_win.reshape(b, L, hkv, d)
    qg = q.reshape(b, s, hkv, h // hkv, d)
    logits = jnp.einsum("bshgd,blhd->bshgl", qg, k4).astype(
        jnp.float32) / np.sqrt(d)
    mask = jnp.arange(L)[None, :] <= jnp.asarray(lens)[:, None]
    logits = jnp.where(mask[:, None, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bshgl,blhd->bshgd", probs, v4).reshape(b, s, h, d)


def _paged_fixture(seed, b, hkv, h, d, bs, nb_per_seq, lens, scramble=True):
    """Build (q, pools, tables, windows): logically contiguous per-seq
    windows scattered into a (optionally scrambled) physical pool."""
    rng = np.random.RandomState(seed)
    f = hkv * d
    window = nb_per_seq * bs
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32)) * 0.4
    k_win = rng.randn(b, window, f).astype(np.float32) * 0.4
    v_win = rng.randn(b, window, f).astype(np.float32) * 0.4
    n_phys = b * nb_per_seq
    order = (rng.permutation(n_phys) if scramble
             else np.arange(n_phys)) + 1
    tables = order.reshape(b, nb_per_seq).astype(np.int32)
    k_pool = np.zeros((n_phys + 1, bs, f), np.float32)
    v_pool = np.zeros((n_phys + 1, bs, f), np.float32)
    for i in range(b):
        for t in range(nb_per_seq):
            k_pool[tables[i, t]] = k_win[i, t * bs:(t + 1) * bs]
            v_pool[tables[i, t]] = v_win[i, t * bs:(t + 1) * bs]
    return (q, jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(k_win), jnp.asarray(v_win))


@pytest.mark.parametrize("hkv,h", [(2, 4), (1, 8), (4, 16)])
def test_paged_matches_reference(hkv, h):
    b, d, bs, nb = 3, 16, 8, 4
    lens = jnp.asarray([5, 17, 30], jnp.int32)
    q, kp, vp, tables, k_win, v_win = _paged_fixture(0, b, hkv, h, d, bs,
                                                     nb, lens)
    out = jax.jit(paged_decode_attention, static_argnums=5)(
        q, kp, vp, tables, lens, hkv)
    ref = jax.jit(_reference, static_argnums=4)(q, k_win, v_win, lens, hkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_paged_block_table_indirection_bit_identical():
    """Block-table correctness: the SAME logical windows through a
    scrambled pool and through an identity-layout pool produce
    bit-identical output — the indirection changes where bytes live,
    never what the kernel computes."""
    b, hkv, h, d, bs, nb = 3, 2, 4, 16, 8, 4
    lens = jnp.asarray([7, 12, 31], jnp.int32)
    q, kp_s, vp_s, tbl_s, _, _ = _paged_fixture(1, b, hkv, h, d, bs, nb,
                                                lens, scramble=True)
    q2, kp_i, vp_i, tbl_i, _, _ = _paged_fixture(1, b, hkv, h, d, bs, nb,
                                                 lens, scramble=False)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    out_s = paged_decode_attention(q, kp_s, vp_s, tbl_s, lens, hkv)
    out_i = paged_decode_attention(q, kp_i, vp_i, tbl_i, lens, hkv)
    assert bool(jnp.all(out_s == out_i))


def test_paged_single_block_bitwise_matches_contiguous_kernel():
    """With one block spanning the whole window, the paged kernel and
    the contiguous decode kernel run the same single-tile accumulation —
    outputs must agree to the bit, per sequence at its own position."""
    b, hkv, h, d, bs = 2, 2, 4, 16, 32
    lens_val = [9, 25]
    q, kp, vp, tables, k_win, v_win = _paged_fixture(2, b, hkv, h, d, bs,
                                                     1, lens_val)
    lens = jnp.asarray(lens_val, jnp.int32)
    out_paged = paged_decode_attention(q, kp, vp, tables, lens, hkv)
    for i in range(b):
        out_contig = decode_attention(q[i:i + 1], k_win[i:i + 1],
                                      v_win[i:i + 1], lens_val[i], hkv)
        assert bool(jnp.all(out_paged[i] == out_contig[0])), f"seq {i}"


def test_paged_gather_fallback_matches_kernel():
    b, hkv, h, d, bs, nb = 2, 2, 8, 16, 8, 3
    lens = jnp.asarray([3, 20], jnp.int32)
    q, kp, vp, tables, _, _ = _paged_fixture(3, b, hkv, h, d, bs, nb, lens)
    out_k = jax.jit(paged_decode_attention, static_argnums=5)(
        q, kp, vp, tables, lens, hkv)
    out_g = jax.jit(paged_gather_attention, static_argnums=5)(
        q, kp, vp, tables, lens, hkv)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_g),
                               atol=2e-5, rtol=1e-4)


def test_paged_cache_write_lands_in_the_right_page():
    b, hkv, d, bs, nb = 2, 2, 4, 4, 3
    f = hkv * d
    kp = jnp.zeros((b * nb + 1, bs, f), jnp.float32)
    vp = jnp.zeros_like(kp)
    tables = jnp.asarray(np.arange(b * nb).reshape(b, nb) + 1, jnp.int32)
    lens = jnp.asarray([5, 8], jnp.int32)    # page 1 offset 1 / page 2 off 0
    k_new = jnp.asarray(np.random.RandomState(0).randn(b, 1, hkv, d),
                        jnp.float32)
    v_new = -k_new
    kp2, vp2 = paged_cache_write(kp, vp, k_new, v_new, tables, lens)
    for i, pos in enumerate([5, 8]):
        blk = int(tables[i, pos // bs])
        row = np.asarray(kp2)[blk, pos % bs]
        np.testing.assert_array_equal(row,
                                      np.asarray(k_new)[i].reshape(f))
    # Exactly two rows written per pool.
    assert int(jnp.sum(jnp.any(kp2 != 0, axis=-1))) == 2


# ---------------------------------------------------------------------------
# Engine parity (tier-1 siblings; the 32-request acceptance is @slow)


def test_engine_parity_single_device(tiny_variables):
    engine = ServingEngine(MODEL, tiny_variables, config=SCFG)
    assert engine.decode_path.path == "kernel"
    rng = np.random.RandomState(0)
    prompts, news = _mixed_workload(rng, 6, [5, 9, 16, 3], [6, 4, 8])
    handles = [engine.submit(p, n) for p, n in zip(prompts, news)]
    engine.run_until_idle()
    _assert_parity(engine, tiny_variables, prompts, news, handles)
    stats = engine.stats()
    assert stats["requests_finished"] == 6
    assert stats["tokens_generated"] == sum(news)
    # 6 requests through 4 slots: continuous batching actually cycled.
    assert stats["steps"] < sum(news)


def test_engine_parity_tp_light(tp_setup):
    mesh, sharded = tp_setup
    engine = ServingEngine(MODEL, sharded, config=SCFG)
    assert engine.decode_path.path == "kernel_tp", engine.decode_path
    rng = np.random.RandomState(1)
    prompts, news = _mixed_workload(rng, 4, [5, 12], [5, 7])
    handles = [engine.submit(p, n) for p, n in zip(prompts, news)]
    engine.run_until_idle()
    _assert_parity(engine, sharded, prompts, news, handles, mesh=mesh)


@pytest.mark.slow
def test_engine_acceptance_mixed_length_tp(tp_setup):
    """The round-9 acceptance bar: >=32 mixed-length requests (prompt
    span 4x) through the continuous batcher on the TP-sharded decode
    path, bit-identical per-request tokens vs bare generate(), with the
    paged pool's peak block usage strictly below per-slot contiguous
    max-length allocation."""
    mesh, sharded = tp_setup
    engine = ServingEngine(MODEL, sharded, config=SCFG)
    assert engine.decode_path.path == "kernel_tp"
    rng = np.random.RandomState(9)
    prompts, news = _mixed_workload(rng, 32, [8, 12, 16, 32],
                                    [4, 8, 12, 16])
    assert max(len(p) for p in prompts) / min(len(p) for p in prompts) >= 4
    handles = [engine.submit(p, n) for p, n in zip(prompts, news)]
    engine.run_until_idle()
    _assert_parity(engine, sharded, prompts, news, handles, mesh=mesh)
    stats = engine.stats()
    assert stats["requests_finished"] == 32
    contiguous = SCFG.max_batch * (
        (SCFG.max_seq_len + SCFG.block_size - 1) // SCFG.block_size)
    assert stats["blocks_peak"] < contiguous, (
        f"paged peak {stats['blocks_peak']} did not beat contiguous "
        f"per-slot allocation {contiguous}")


def test_engine_preemption_recompute_parity(tiny_variables):
    """Capacity exhaustion: an undersized pool forces preemption; the
    preempted sequence recomputes and still finishes with exactly the
    bare-generate tokens."""
    scfg = ServingConfig(max_batch=3, block_size=4, num_blocks=7,
                         queue_depth=32, max_seq_len=28)
    engine = ServingEngine(MODEL, tiny_variables, config=scfg)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, CFG.vocab_size, (8,)).astype(np.int32)
               for _ in range(3)]
    news = [12, 12, 12]
    handles = [engine.submit(p, n) for p, n in zip(prompts, news)]
    engine.run_until_idle()
    stats = engine.stats()
    assert stats["preemptions"] > 0, "pool sizing did not force preemption"
    _assert_parity(engine, tiny_variables, prompts, news, handles)
    assert stats["blocks_peak"] <= 7
    # Everything LIVE freed; pages may stay in the prefix index (one
    # cache reference each — warm spare capacity, released on demand).
    stats = engine.stats()
    assert stats["blocks_live"] == 0
    assert stats["blocks_in_use"] == stats["prefix_cached_blocks"]


def test_engine_reject_when_queue_full(tiny_variables):
    scfg = dataclasses.replace(SCFG, queue_depth=2)
    engine = ServingEngine(MODEL, tiny_variables, config=scfg)
    prompt = np.zeros((4,), np.int32)
    engine.submit(prompt, 4)
    engine.submit(prompt, 4)
    with pytest.raises(RejectedError, match="queue is full"):
        engine.submit(prompt, 4)
    assert engine.stats()["requests_rejected"] == 1
    engine.run_until_idle()   # the two admitted requests still finish
    assert engine.stats()["requests_finished"] == 2


def test_engine_cancel_waiting_and_running(tiny_variables):
    scfg = dataclasses.replace(SCFG, max_batch=1)
    engine = ServingEngine(MODEL, tiny_variables, config=scfg)
    prompt = np.arange(4, dtype=np.int32)
    run = engine.submit(prompt, 8)
    parked = engine.submit(prompt, 8)   # max_batch=1: stays WAITING
    engine.step()                       # admits + prefills `run`
    parked.cancel()                     # cancel before admission
    run.cancel()                        # cancel mid-flight
    engine.run_until_idle()
    for handle in (run, parked):
        with pytest.raises(CancelledError):
            handle.result(timeout=0)
    stats = engine.stats()
    assert stats["requests_cancelled"] == 2
    assert stats["blocks_in_use"] == 0 and stats["active_sequences"] == 0


def test_engine_stream_threaded(tiny_variables):
    engine = ServingEngine(MODEL, tiny_variables, config=SCFG).start()
    try:
        prompt = np.arange(6, dtype=np.int32)
        handle = engine.submit(prompt, 5)
        streamed = list(handle.stream(timeout=60))
        assert streamed == handle.result(timeout=60)
        assert len(streamed) == 5
    finally:
        engine.shutdown()
    # Shutdown leaves no engine thread behind.
    assert not any(t.name == "hvd-serving-engine"
                   for t in threading.enumerate())


# ---------------------------------------------------------------------------
# Zero-state stats, metrics, doctor


def test_serving_stats_zero_state_before_any_engine():
    """hvd.serving.stats() is a well-formed all-zeros dict before the
    first request/engine — the controller_health() convention, pinned."""
    import horovod_tpu.serving as serving

    prev = serving._default_engine
    serving._default_engine = None
    try:
        stats = serving.stats()
        assert stats == zero_stats()
        assert all(isinstance(stats[k], (int, float))
                   for k in sorted(stats))
        # The catalog is pinned: renaming a key must touch this test.
        assert set(stats) == {
            "queue_depth", "queue_limit", "active_sequences",
            "blocks_total", "blocks_in_use", "blocks_peak",
            "block_utilization", "requests_submitted",
            "requests_finished", "requests_rejected",
            "requests_cancelled", "preemptions", "tokens_generated",
            "steps", "ttft_p50_seconds", "ttft_p99_seconds",
            "tpot_p50_seconds", "tpot_p99_seconds",
            # Prefix sharing (round 11).
            "blocks_live", "blocks_live_peak", "blocks_shared",
            "cow_copies", "prefix_hits", "prefix_misses",
            "prefix_hit_rate", "prefix_cached_blocks", "prefix_inserts",
            "prefix_evictions",
            # Fleet router (round 11).
            "router_replicas", "router_requests", "router_reroutes",
            "router_replica_departures",
        }
    finally:
        serving._default_engine = prev


def test_engine_emits_serving_metrics(tiny_variables):
    metrics.reset_for_tests()
    metrics.enable()
    try:
        engine = ServingEngine(MODEL, tiny_variables, config=SCFG)
        prompts = [np.arange(5, dtype=np.int32)] * 2
        handles = [engine.submit(p, 4) for p in prompts]
        engine.run_until_idle()
        for handle in handles:
            handle.result(timeout=0)
        snap = metrics.snapshot()
        assert snap["hvd_serving_tokens_generated_total"][
            "values"][0][1] == 8.0
        assert snap["hvd_serving_steps_total"]["values"][0][1] >= 3
        finished = {tuple(k): v for k, v in
                    snap["hvd_serving_requests_total"]["values"]}
        assert finished[("finished",)] == 2.0
        assert snap["hvd_serving_blocks_total"]["values"][0][1] == 32.0
        assert snap["hvd_serving_ttft_seconds"]["values"][0][1][
            "count"] == 2
    finally:
        metrics.reset_for_tests()


def test_doctor_serving_rules_synthetic():
    from horovod_tpu.doctor import Evidence, diagnose

    def gauge(v):
        return {"type": "gauge", "values": [[[], v]]}

    snap = {
        "hvd_serving_queue_depth": gauge(15),
        "hvd_serving_queue_limit": gauge(16),
        "hvd_serving_requests_total": {
            "type": "counter", "values": [[["finished"], 40.0],
                                          [["rejected"], 12.0]]},
        "hvd_serving_preemptions_total": {
            "type": "counter", "values": [[[], 4.0]]},
        "hvd_serving_blocks_total": gauge(64),
    }
    findings = {d.rule: d for d in diagnose(Evidence(snapshots={0: snap}))}
    sat = findings["serving_queue_saturation"]
    assert sat.severity == "critical"          # >= 10 rejects
    assert "shedding load" in sat.hint
    assert sat.evidence["rejected"] == 12
    exh = findings["serving_block_exhaustion"]
    assert exh.severity == "warning"
    assert "HOROVOD_SERVING_NUM_BLOCKS" in exh.hint
    # Healthy snapshot: neither rule fires.
    healthy = {"hvd_serving_queue_depth": gauge(1),
               "hvd_serving_queue_limit": gauge(16)}
    assert not [d for d in diagnose(Evidence(snapshots={0: healthy}))
                if d.rule.startswith("serving_")]


def test_doctor_names_queue_saturation_past_admission(tiny_variables):
    """The acceptance bullet: drive the engine past admission capacity
    with the load generator and the LIVE doctor names queue
    saturation."""
    from horovod_tpu import doctor as hvd_doctor

    loadgen = _load_example("serving_loadgen")
    metrics.reset_for_tests()
    metrics.enable()
    try:
        scfg = ServingConfig(max_batch=2, block_size=8, num_blocks=0,
                             queue_depth=2, max_seq_len=64)
        engine = ServingEngine(MODEL, tiny_variables, config=scfg).start()
        trace = loadgen.build_trace(seed=9, requests=12, rate=0.0,
                                    min_prompt=8, max_prompt=32,
                                    min_new=8, max_new=16,
                                    vocab_size=CFG.vocab_size)
        _, rejected, _, _ = loadgen.run_workload(engine, trace,
                                                 timeout_s=300.0)
        engine.shutdown()
        assert rejected > 0, "workload did not exceed admission capacity"
        report = hvd_doctor.report()
        rules = {f["rule"] for f in report["findings"]}
        assert "serving_queue_saturation" in rules, report
    finally:
        metrics.reset_for_tests()


# ---------------------------------------------------------------------------
# Load generator + serving trace file


def _load_example(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "examples", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_loadgen_trace_is_seed_deterministic():
    loadgen = _load_example("serving_loadgen")
    kw = dict(requests=8, rate=4.0, min_prompt=8, max_prompt=32,
              min_new=4, max_new=8, vocab_size=512)
    a = loadgen.build_trace(seed=9, **kw)
    b = loadgen.build_trace(seed=9, **kw)
    c = loadgen.build_trace(seed=10, **kw)
    assert len(a) == 8
    for (ta, pa, na), (tb, pb, nb) in zip(a, b):
        assert ta == tb and na == nb
        np.testing.assert_array_equal(pa, pb)
    assert any(not np.array_equal(pa, pc) or ta != tc
               for (ta, pa, _), (tc, pc, _) in zip(a, c))
    # Prompt lengths genuinely mixed (the heterogeneity paging is for).
    lens = {len(p) for _, p, _ in a}
    assert len(lens) > 1


def test_engine_writes_serving_trace(tiny_variables, tmp_path,
                                     monkeypatch):
    from horovod_tpu.trace import SERVING_PHASES, rank_trace_files

    monkeypatch.setenv("HOROVOD_TRACE_DIR", str(tmp_path))
    engine = ServingEngine(MODEL, tiny_variables, config=SCFG)
    handle = engine.submit(np.arange(5, dtype=np.int32), 4)
    engine.run_until_idle()
    handle.result(timeout=0)
    engine.shutdown()
    path = tmp_path / "trace.serving.rank0.json"
    assert path.exists()
    events = json.loads(path.read_text())
    phases = {e["name"] for e in events if e.get("ph") == "X"}
    assert phases == set(SERVING_PHASES)
    # The serving trace must NOT be picked up as a collective rank trace
    # (it would pollute the merge's straggler attribution).
    assert rank_trace_files(str(tmp_path)) == {}


def test_serving_env_knobs_parse(monkeypatch):
    from horovod_tpu.common import config as hvd_config

    monkeypatch.setenv("HOROVOD_SERVING_MAX_BATCH", "32")
    monkeypatch.setenv("HOROVOD_SERVING_BLOCK_SIZE", "garbage")
    monkeypatch.setenv("HOROVOD_SERVING_NUM_BLOCKS", "-3")
    monkeypatch.setenv("HOROVOD_SERVING_QUEUE_DEPTH", "0")
    monkeypatch.setenv("HOROVOD_SERVING_MAX_SEQ_LEN", "4096")
    cfg = ServingConfig.from_env()
    assert cfg.max_batch == 32
    assert cfg.block_size == 16          # garbage -> default
    assert cfg.num_blocks == 0           # negative clamps to derived
    assert cfg.queue_depth == 128        # non-positive -> default
    assert cfg.max_seq_len == 4096
    assert hvd_config.serving_max_batch() == 32


def test_prefix_env_knobs_parse(monkeypatch):
    from horovod_tpu.common import config as hvd_config

    monkeypatch.setenv("HOROVOD_SERVING_PREFIX_CACHE", "0")
    monkeypatch.setenv("HOROVOD_SERVING_PREFIX_CAPACITY", "-5")
    cfg = ServingConfig.from_env()
    assert cfg.prefix_cache is False
    assert cfg.prefix_capacity == 0      # negative clamps
    monkeypatch.setenv("HOROVOD_SERVING_PREFIX_CACHE", "1")
    monkeypatch.setenv("HOROVOD_SERVING_PREFIX_CAPACITY", "16")
    cfg = ServingConfig.from_env()
    assert cfg.prefix_cache is True and cfg.prefix_capacity == 16
    assert hvd_config.serving_prefix_cache() is True


# ---------------------------------------------------------------------------
# Ref-counted block pool (round 11) — the sharing edge cases, loud.


def test_block_pool_share_and_release_semantics():
    pool = BlockPool(4, block_size=8)
    a = pool.alloc()
    assert pool.refcount(a) == 1 and not pool.is_shared(a)
    pool.share(a)
    assert pool.refcount(a) == 2 and pool.is_shared(a)
    assert pool.blocks_shared == 1
    # Free-while-shared: the donor's release does NOT return the block
    # (the other holder keeps the data); accounting stays exact.
    pool.free([a])
    assert pool.refcount(a) == 1 and pool.blocks_in_use == 1
    assert a not in [pool.alloc() for _ in range(pool.free_blocks)], (
        "a still-referenced block was handed out again")
    # Eviction of the LAST reference returns the block to the pool.
    pool.free([a])
    assert pool.refcount(a) == 0
    b = pool.alloc()
    assert b == a                        # reusable again (LIFO free list)


def test_block_pool_double_free_of_shared_block_is_loud():
    pool = BlockPool(2, block_size=4)
    a = pool.alloc()
    pool.share(a)                        # two references
    pool.free([a])
    pool.free([a])                       # both released: legal
    with pytest.raises(ValueError, match="double free"):
        pool.free([a])                   # one more: a bookkeeping bug
    with pytest.raises(ValueError, match="cannot share"):
        pool.share(a)                    # sharing a free block is stale
    with pytest.raises(ValueError, match="null block"):
        pool.share(NULL_BLOCK)


def test_block_pool_stats_count_shares():
    pool = BlockPool(4, block_size=8)
    a = pool.alloc()
    pool.share(a)
    s = pool.stats()
    assert s["block_shares"] == 1 and s["blocks_shared"] == 1
    pool.free([a])
    assert pool.stats()["blocks_shared"] == 0   # one holder left


# ---------------------------------------------------------------------------
# Prefix cache (pure bookkeeping)


def test_page_hashes_chain_commits_to_whole_prefix():
    from horovod_tpu.serving import page_hashes

    toks = np.arange(32, dtype=np.int32)
    h = page_hashes(toks, 8)
    assert len(h) == 4                   # whole pages only
    assert len(page_hashes(toks[:31], 8)) == 3
    # Same page-2 tokens after an EARLIER divergence: every digest from
    # the divergence on must change (chained, not per-page).
    other = toks.copy()
    other[0] += 1
    h2 = page_hashes(other, 8)
    assert h[0] != h2[0] and h[2] != h2[2] and h[3] != h2[3]
    # Determinism.
    assert page_hashes(toks, 8) == h


def test_prefix_cache_lookup_insert_and_cap():
    from horovod_tpu.serving import PrefixCache, page_hashes

    pool = BlockPool(8, block_size=4)
    cache = PrefixCache(pool)
    toks = np.arange(12, dtype=np.int32)         # 3 whole pages
    hashes = page_hashes(toks, 4)
    blocks = pool.alloc_many(3)
    for digest, block in zip(hashes, blocks):
        assert cache.insert(digest, block)
        assert not cache.insert(digest, block)   # refresh, not re-add
    assert pool.refcount(blocks[0]) == 2         # cache holds one ref
    # An unaligned prompt past the cached pages maps them all warm.
    warm, got_hashes = cache.lookup(np.arange(13, dtype=np.int32))
    assert got_hashes == hashes
    assert warm == blocks
    # Page-aligned prompt: the warm run is capped one page short so the
    # prefill keeps >= 1 real token (fully-warm aligned prompts
    # recompute exactly their last page).
    warm_aligned, _ = cache.lookup(toks)         # 12 = exactly 3 pages
    assert warm_aligned == blocks[:2]
    warm_aligned, _ = cache.lookup(toks[:8])
    assert warm_aligned == blocks[:1]
    # A cold middle page breaks the run (later isolated hits are
    # useless: their KV assumes a different history).
    cache.release(8, for_capacity=True)
    for digest, block in ((hashes[0], blocks[0]), (hashes[2], blocks[2])):
        cache.insert(digest, block)
    warm_broken, _ = cache.lookup(toks)
    assert warm_broken == [blocks[0]]


def test_prefix_cache_release_skips_live_and_frees_cold():
    from horovod_tpu.serving import PrefixCache, page_hashes

    pool = BlockPool(4, block_size=4)
    cache = PrefixCache(pool)
    toks = np.arange(8, dtype=np.int32)
    hashes = page_hashes(toks, 4)
    blocks = pool.alloc_many(2)
    for digest, block in zip(hashes, blocks):
        cache.insert(digest, block)
    # Simulate the donor retiring: pages become cache-only.
    pool.free([blocks[1]])
    assert cache.cache_only_blocks() == 1
    # blocks[0] still has a live holder: release must skip it and free
    # only the cache-only page.
    freed = cache.release(2)
    assert freed == 1
    assert pool.refcount(blocks[1]) == 0         # returned to the pool
    assert pool.refcount(blocks[0]) == 2         # untouched (live + cache)
    assert cache.evictions == 1


def test_prefix_cache_capacity_lru():
    from horovod_tpu.serving import PrefixCache, page_hashes

    pool = BlockPool(8, block_size=4)
    cache = PrefixCache(pool, capacity_blocks=2)
    toks = np.arange(16, dtype=np.int32)
    hashes = page_hashes(toks, 4)
    blocks = pool.alloc_many(4)
    for digest, block in zip(hashes[:2], blocks[:2]):
        cache.insert(digest, block)
    assert len(cache) == 2
    cache.lookup(toks[:5])               # refreshes page 0's LRU slot
    cache.insert(hashes[2], blocks[2])   # evicts LRU = page 1
    assert len(cache) == 2
    warm, _ = cache.lookup(toks)
    assert warm == [blocks[0]]           # page 1 gone -> run stops there
    assert cache.evictions == 1


# ---------------------------------------------------------------------------
# Scheduler: warm admission + copy-on-write


def test_scheduler_warm_admission_maps_shared_blocks():
    from horovod_tpu.serving import PrefixCache, page_hashes

    pool = BlockPool(8, 4)
    cache = PrefixCache(pool)
    sched = Scheduler(pool, max_batch=2, queue_depth=4, max_seq_len=32,
                      prefix_cache=cache)
    donor = pool.alloc_many(2)
    toks = np.arange(10, dtype=np.int32)          # 2 whole pages + tail
    for digest, block in zip(page_hashes(toks, 4), donor):
        cache.insert(digest, block)
    req = Request(rid=0, prompt=toks, max_new_tokens=4)
    sched.enqueue(req)
    [admitted] = sched.admit()
    assert admitted.warm_pages == 2
    assert admitted.blocks[:2] == donor           # mapped, not copied
    assert pool.refcount(donor[0]) == 3           # donor + cache + req
    assert cache.hits == 2 and cache.misses == 0  # no 3rd whole page
    # The donor freeing its pages keeps them live for the request.
    pool.free(donor)
    assert pool.refcount(donor[0]) == 2
    sched.retire(req, "finished")
    assert pool.refcount(donor[0]) == 1           # cache only now


def test_scheduler_cow_private_copy_before_shared_write():
    """A sequence whose next KV write targets a shared page gets a
    private copy first: fresh block swapped into its table, the (src,
    dst) pair queued for the engine, and its reference on the shared
    original released."""
    pool = BlockPool(8, 4)
    sched = Scheduler(pool, max_batch=2, queue_depth=4, max_seq_len=32)
    req = Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                  max_new_tokens=8)
    sched.enqueue(req)
    [r] = sched.admit()
    # Another holder appears on the write-target block (position
    # total_len()-1 = 5 -> block index 1).
    src = r.blocks[1]
    pool.share(src)
    sched.ensure_decode_capacity()
    assert sched.cow_copies == 1
    assert r.blocks[1] != src
    assert sched.pending_copies == [(src, r.blocks[1])]
    assert pool.refcount(src) == 1               # our release went through
    assert pool.refcount(r.blocks[1]) == 1
    # Already-private target: no further copies.
    sched.pending_copies.clear()
    sched.ensure_decode_capacity()
    assert sched.cow_copies == 1


def test_scheduler_cow_under_preemption_pressure():
    """COW with a dry pool: the fresh private block comes from
    preempting the youngest sequence, and the victim's own queued
    copies die with it (its blocks return to the pool)."""
    pool = BlockPool(4, 4)
    sched = Scheduler(pool, max_batch=2, queue_depth=4, max_seq_len=16)
    r0 = Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                 max_new_tokens=8)
    r1 = Request(rid=1, prompt=np.arange(6, dtype=np.int32),
                 max_new_tokens=8)
    sched.enqueue(r0)
    sched.enqueue(r1)
    assert len(sched.admit()) == 2               # 2 blocks each: pool full
    src = r0.blocks[1]
    pool.share(src)                              # external holder
    preempted = sched.ensure_decode_capacity()
    assert preempted == [r1]                     # youngest paid for the copy
    assert r1.blocks == [] and r1.state == "waiting"
    assert sched.cow_copies == 1
    assert sched.pending_copies == [(src, r0.blocks[1])]
    assert r0.blocks[1] != src
    assert pool.refcount(src) == 1               # the external holder


# ---------------------------------------------------------------------------
# Engine: sharing parity (the round-11 acceptance bar)


def _shared_prefix_workload(rng, n, prefix_len, tail_lens, new_tokens):
    shared = rng.randint(0, CFG.vocab_size, (prefix_len,)).astype(np.int32)
    prompts = [np.concatenate(
        [shared, rng.randint(0, CFG.vocab_size,
                             (tail_lens[i % len(tail_lens)],)
                             ).astype(np.int32)]) for i in range(n)]
    news = [new_tokens[i % len(new_tokens)] for i in range(n)]
    return prompts, news


def test_engine_parity_sharing_on_off_single_device(tiny_variables):
    """Per-request tokens with prefix sharing ON are bit-identical to
    sharing OFF and to bare generate() — and the warm path genuinely
    engaged (prefix hits, shared blocks)."""
    rng = np.random.RandomState(7)
    prompts, news = _shared_prefix_workload(rng, 8, 16, [3, 5, 9, 17],
                                            [4, 6, 8])
    on = ServingEngine(MODEL, tiny_variables, config=SCFG)
    handles_on = [on.submit(p, n) for p, n in zip(prompts, news)]
    on.run_until_idle()
    _assert_parity(on, tiny_variables, prompts, news, handles_on)
    stats = on.stats()
    assert stats["prefix_hits"] > 0, "warm path never engaged"
    assert any(h.warm_pages > 0 for h in handles_on)
    off = ServingEngine(MODEL, tiny_variables,
                        config=dataclasses.replace(SCFG,
                                                   prefix_cache=False))
    handles_off = [off.submit(p, n) for p, n in zip(prompts, news)]
    off.run_until_idle()
    assert off.stats()["prefix_hits"] == 0
    for a, b in zip(handles_on, handles_off):
        assert a.result(timeout=0) == b.result(timeout=0)


def test_engine_parity_sharing_tp(tp_setup):
    """The same sharing-on parity on the TP-sharded decode path (the
    warm prefill's gather + tail-run must be bit-exact under
    shard_map/GSPMD too)."""
    mesh, sharded = tp_setup
    engine = ServingEngine(MODEL, sharded, config=SCFG)
    assert engine.decode_path.path == "kernel_tp"
    rng = np.random.RandomState(8)
    prompts, news = _shared_prefix_workload(rng, 6, 16, [4, 7, 12],
                                            [5, 7])
    handles = [engine.submit(p, n) for p, n in zip(prompts, news)]
    engine.run_until_idle()
    assert engine.stats()["prefix_hits"] > 0
    _assert_parity(engine, sharded, prompts, news, handles, mesh=mesh)


def test_engine_parity_sharing_across_preemption_and_donor_eviction(
        tiny_variables):
    """The hard corner pinned by the acceptance criteria: an undersized
    pool forces preemption while requests share warm pages; donors
    retire (and their pages get evicted under pressure) while sharers
    still run. Every request must still match bare generate()."""
    scfg = ServingConfig(max_batch=3, block_size=4, num_blocks=10,
                         queue_depth=32, max_seq_len=28)
    engine = ServingEngine(MODEL, tiny_variables, config=scfg)
    rng = np.random.RandomState(5)
    prompts, news = _shared_prefix_workload(rng, 6, 8, [2, 3, 5],
                                            [10, 12])
    handles = [engine.submit(p, n) for p, n in zip(prompts, news)]
    engine.run_until_idle()
    stats = engine.stats()
    assert stats["preemptions"] > 0, "pool sizing did not force preemption"
    assert stats["prefix_hits"] > 0, "sharing never engaged"
    assert stats["prefix_evictions"] > 0, "pressure never evicted a donor"
    _assert_parity(engine, tiny_variables, prompts, news, handles)
    assert engine.stats()["blocks_live"] == 0


def test_engine_cow_copy_is_content_correct(tiny_variables):
    """Force a COW on a live decode write: an external reference lands
    on the write-target block mid-generation; the engine must copy the
    page on-device before writing, and the final tokens still match
    bare generate() (proof the copy carried the right bytes)."""
    engine = ServingEngine(MODEL, tiny_variables, config=SCFG)
    prompt = np.random.RandomState(6).randint(
        0, CFG.vocab_size, (9,)).astype(np.int32)
    handle = engine.submit(prompt, 8)
    engine.step()                        # prefill + first decode step
    with engine._cond:
        req = engine._sched.running[handle._req.slot]
        widx = (req.total_len() - 1) // SCFG.block_size
        shared_block = req.blocks[widx]
        engine._sched.pool.share(shared_block)   # external holder appears
    engine.run_until_idle()
    assert engine.stats()["cow_copies"] >= 1
    ref = generate(MODEL, tiny_variables, jnp.asarray(prompt[None]),
                   max_new_tokens=8)
    assert handle.result(timeout=0) == list(np.asarray(ref)[0, 9:])
    # The shared original still belongs to its external holder.
    assert engine._sched.pool.refcount(shared_block) == 1


def test_engine_recompute_readmits_warm_from_own_pages(tiny_variables):
    """Preemption with the cache on is CHEAP: the preempted sequence's
    pages survive in the index (free-while-shared), so its recompute
    prefill maps them warm instead of replaying the whole prefix."""
    scfg = ServingConfig(max_batch=2, block_size=4, num_blocks=8,
                         queue_depth=8, max_seq_len=32)
    engine = ServingEngine(MODEL, tiny_variables, config=scfg)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, CFG.vocab_size, (8,)).astype(np.int32)
               for _ in range(2)]
    handles = [engine.submit(p, 12) for p in prompts]
    engine.run_until_idle()
    stats = engine.stats()
    assert stats["preemptions"] > 0
    # The preempted request's readmission found its own pages warm.
    assert any(h.warm_pages > 0 for h in handles)
    _assert_parity(engine, tiny_variables, prompts, [12, 12], handles)


def test_loadgen_prefix_share_trace_is_seeded_and_shared():
    loadgen = _load_example("serving_loadgen")
    kw = dict(requests=12, rate=0.0, min_prompt=40, max_prompt=64,
              min_new=4, max_new=8, vocab_size=512, prefix_share=3,
              prefix_len=32)
    a = loadgen.build_trace(seed=11, **kw)
    b = loadgen.build_trace(seed=11, **kw)
    for (ta, pa, na), (tb, pb, nb) in zip(a, b):
        assert ta == tb and na == nb
        np.testing.assert_array_equal(pa, pb)
    # Exactly 3 distinct shared prefixes, cycling round-robin.
    firsts = [tuple(p[:32]) for _, p, _ in a]
    assert len(set(firsts)) == 3
    assert firsts[0] == firsts[3] == firsts[6]
    # Tails unique and totals within bounds.
    assert len({tuple(p[32:]) for _, p, _ in a}) == 12
    assert all(40 <= len(p) <= 64 for _, p, _ in a)
