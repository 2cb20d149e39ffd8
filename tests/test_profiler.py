"""SPMD-tier observability (common/profiler.py): traced collectives must
carry hvd.<op>[.<name>] named scopes into lowered HLO metadata — the
jit-tier counterpart of the eager timeline's activity names — and the
trace wrappers must be env-gated no-ops when unconfigured."""

import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import make_mesh

N_DEV = 8


def _lowered_text(fn, *args):
    # debug_info=True prints the location metadata (name-stack scopes);
    # the same names survive into compiled HLO op metadata (verified) and
    # that's what the profiler's trace viewer displays.
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_collective_scope_names_in_hlo():
    mesh = make_mesh({"data": N_DEV})
    x = jnp.arange(float(N_DEV * 4)).reshape(N_DEV * 4, 1)

    def body(x):
        r = hvd.allreduce(x, name="grads")
        g = hvd.allgather(jnp.mean(x, keepdims=True), name="stats")
        b = hvd.broadcast(x, root_rank=0, name="params")
        return r.sum() + g.sum() + b.sum()

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      check_vma=False)
    text = _lowered_text(f, x)
    assert "hvd.allreduce.grads" in text
    assert "hvd.allgather.stats" in text
    assert "hvd.broadcast.params" in text


def test_distributed_optimizer_scopes_in_hlo():
    # The DistributedOptimizer's per-leaf reductions are named — a trace
    # shows which parameter's allreduce a span belongs to.
    mesh = make_mesh({"data": N_DEV})
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="data")
    x = jnp.ones((N_DEV, 4))

    def body(p, x):
        def loss(p):
            return ((x @ p["w"] + p["b"]) ** 2).mean()
        g = jax.grad(loss)(p)
        u, _ = tx.update(g, tx.init(p), p)
        # Consume EVERY leaf — an unused update's allreduce is DCE'd.
        return sum(a.sum() for a in jax.tree.leaves(
            optax.apply_updates(p, u)))

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(), P("data")),
                      out_specs=P(), check_vma=False)
    text = _lowered_text(f, params, x)
    assert "hvd.allreduce.DistributedOptimizer.0" in text
    assert "hvd.allreduce.DistributedOptimizer.1" in text


def test_ext_collective_scopes_in_hlo():
    mesh = make_mesh({"data": N_DEV})
    # Local shard dim0 = 8: divisible by the axis size, as reducescatter
    # (tiled) and alltoall both require.
    x = jnp.arange(float(N_DEV * N_DEV)).reshape(N_DEV * N_DEV, 1)

    def body(x):
        return hvd.reducescatter(x).sum() + hvd.alltoall(x).sum()

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      check_vma=False)
    text = _lowered_text(f, x)
    assert "hvd.reducescatter" in text
    assert "hvd.alltoall" in text


def test_trace_noop_without_config(tmp_path):
    os.environ.pop(hvd.profiler.PROFILE_DIR_ENV, None)
    with hvd.profiler.trace():      # no dir, no env: must be a no-op
        y = jnp.ones(3).sum()
    assert float(y) == 3.0
    with pytest.raises(ValueError, match="HOROVOD_PROFILE_DIR"):
        hvd.profiler.start_trace()


def test_trace_writes_profile(tmp_path):
    d = str(tmp_path / "prof")
    with hvd.profiler.trace(d):
        with hvd.profiler.step(0):
            y = jax.jit(lambda x: (x * 2).sum())(jnp.ones(8))
        jax.block_until_ready(y)
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert found, "profiler trace produced no files"


def test_named_scope_reexport():
    def f(x):
        with hvd.profiler.named_scope("hvd.custom.region"):
            return x * 2

    assert "hvd.custom.region" in jax.jit(f).lower(
        jnp.ones(4)).as_text(debug_info=True)


# ------------------------------------------------- spans (host side)

def _since(mark):
    return hvd.profiler.spans()[mark:]


def test_span_records_name_interval_and_parent():
    mark = len(hvd.profiler.spans())
    with hvd.profiler.span("outer"):
        with hvd.profiler.span("inner"):
            pass
        with hvd.profiler.span("second"):
            pass
    with hvd.profiler.span("alone"):
        pass
    got = {s.name: s for s in _since(mark)}
    assert [s.name for s in _since(mark)] == ["inner", "second", "outer",
                                              "alone"]
    for s in _since(mark):
        assert 0 < s.start_ns <= s.end_ns
    assert got["outer"].parent is None and got["alone"].parent is None
    assert got["inner"].parent == "outer" == got["second"].parent
    assert (got["outer"].start_ns <= got["inner"].start_ns
            and got["second"].end_ns <= got["outer"].end_ns)


def test_span_is_recorded_when_the_block_raises():
    mark = len(hvd.profiler.spans())
    with pytest.raises(KeyError):
        with hvd.profiler.span("failing"):
            raise KeyError("x")
    assert [s.name for s in _since(mark)] == ["failing"]
    with hvd.profiler.span("after"):
        pass
    assert _since(mark)[-1].parent is None     # the stack was unwound


def test_init_spans_nest_and_import_is_stamped():
    mark = len(hvd.profiler.spans())
    hvd.init()
    got = {s.name: s for s in _since(mark)}
    assert got["init"].parent is None
    for child in ("init.distributed", "init.backend", "init.topology",
                  "init.controller"):
        assert got[child].parent == "init"
        assert got["init"].start_ns <= got[child].start_ns
        assert got[child].end_ns <= got["init"].end_ns
    # The package's import was stamped once, before anything else here.
    imports = [s for s in hvd.profiler.spans() if s.name == "import"]
    assert len(imports) == 1 and imports[0].end_ns <= got["init"].start_ns
    assert imports[0].end_ns > imports[0].start_ns


def test_mesh_and_placement_spans():
    mark = len(hvd.profiler.spans())
    mesh = make_mesh({"data": N_DEV})
    hvd.parallel.replicate({"w": np.ones((2, 2), np.float32)}, mesh)
    hvd.parallel.shard_batch(np.ones((N_DEV, 2), np.float32), mesh)
    assert [s.name for s in _since(mark)] == ["make_mesh", "replicate",
                                              "shard_batch"]


def test_span_log_is_bounded_and_counts_drops(monkeypatch):
    import collections

    from horovod_tpu.common import profiler

    monkeypatch.setattr(profiler, "_spans", collections.deque(maxlen=3))
    before = profiler.spans_dropped()
    for i in range(5):
        with profiler.span(f"s{i}"):
            pass
    assert [s.name for s in profiler.spans()] == ["s2", "s3", "s4"]
    assert profiler.spans_dropped() - before == 2


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    d = str(tmp_path / "prof")
    with hvd.profiler.trace(d):
        with hvd.profiler.span("under_trace"):
            jax.block_until_ready(jnp.ones(8) * 2)
    files = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
             if f.endswith(".xplane.pb")]
    assert files
    data = ProfileData.from_file(files[0])
    names = {ev.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert "hvd.under_trace" in names


# ------------------------------------------------- compile records

def test_compile_events_name_the_function_and_catch_a_recompile():
    hvd.init()      # registers the listeners (once a process)
    hvd.init()
    from horovod_tpu.common import profiler

    def hvd_probe_fn(x):
        return (x * 3).sum()

    f = jax.jit(hvd_probe_fn)
    mark = len(profiler.compile_events())
    f(jnp.ones(4)).block_until_ready()
    # jax calls the function "hvd_probe_fn" while it traces and
    # "jit(hvd_probe_fn)" from lowering on.
    first = [e for e in profiler.compile_events()[mark:]
             if "hvd_probe_fn" in e.fun_name]
    kinds = [e.event.rsplit("/", 1)[-1] for e in first]
    assert kinds == ["jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                     "backend_compile_duration"]
    assert all(e.seconds >= 0 and e.at_ns > 0 for e in first)
    # jax also times the inner jits (``multiply``, ``_reduce_sum``) inside
    # the probe's tracing; only the outermost region is kept.
    assert not [e for e in profiler.compile_events()[mark:]
                if e.fun_name in ("multiply", "_reduce_sum")]
    # The same shape again compiles nothing; another shape does.
    mark = len(profiler.compile_events())
    f(jnp.ones(4)).block_until_ready()
    assert not [e for e in profiler.compile_events()[mark:]
                if "hvd_probe_fn" in e.fun_name]
    f(jnp.ones(5)).block_until_ready()
    again = [e for e in profiler.compile_events()[mark:]
             if e.fun_name == "jit(hvd_probe_fn)"
             and e.event.endswith("backend_compile_duration")]
    assert len(again) == 1 and again[0].at_ns > first[-1].at_ns
    assert {e.event for e in profiler.compile_events()} <= set(
        profiler.COMPILE_EVENTS)


# ------------------------------------------------- scopes (device side)

def _optimizer_step_text(wrap):
    mesh = make_mesh({"data": N_DEV})
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    x = jnp.ones((N_DEV, 4))

    def loss(p, x):
        return ((x @ p["w"] + p["b"]) ** 2).mean()

    f = jax.shard_map(lambda p, x: wrap(loss, p, x), mesh=mesh,
                      in_specs=(P(), P("data")), out_specs=P(),
                      check_vma=False)
    return _lowered_text(f, params, x)


def _scope_paths(text, needle):
    import re

    return [m for m in re.findall(r'"([^"]*)"', text) if needle in m]


def test_exchange_and_update_scopes_in_hlo():
    tx = hvd.DistributedOptimizer(optax.adam(0.1), axis_name="data")

    def wrap(loss, p, x):
        g = jax.grad(loss)(p, x)
        u, _ = tx.update(g, tx.init(p), p)
        return sum(a.sum() for a in jax.tree.leaves(
            optax.apply_updates(p, u)))

    text = _optimizer_step_text(wrap)
    P_ = hvd.profiler
    assert P_.SCOPE_EXCHANGE == "hvd.exchange"
    assert P_.SCOPE_UPDATE == "hvd.update"
    # The per-leaf scopes stay, inside the exchange and never the update.
    for i in range(2):
        leaf = P_.collective_scope("allreduce", f"DistributedOptimizer.{i}")
        paths = _scope_paths(text, leaf)
        assert paths and all("hvd.exchange/" + leaf in p for p in paths)
    assert _scope_paths(text, "hvd.update")
    assert not [p for p in _scope_paths(text, "hvd.update")
                if "hvd.exchange" in p or "hvd.allreduce" in p]


def test_distributed_value_and_grad_exchange_scope_in_hlo():
    def wrap(loss, p, x):
        value, g = hvd.distributed_value_and_grad(
            loss, axis_name="data")(p, x)
        return value + sum(a.sum() for a in jax.tree.leaves(g))

    text = _optimizer_step_text(wrap)
    paths = _scope_paths(text, "hvd.allreduce.DistributedGrad.0")
    assert paths and all("hvd.exchange/" in p for p in paths)


# ------------------------------------------------- exchange records

EXCHANGE_DEVICES = 4
EXCHANGE_PARAMS = {"w": (4, 4), "b": (4,)}      # 20 float32: 80 bytes


def _traced_exchange(wrap, bound=True):
    """Lower (never run) a step that exchanges ``EXCHANGE_PARAMS``'
    gradients through ``wrap(loss, p, x)``; the records it left."""
    mesh = make_mesh({"data": EXCHANGE_DEVICES},
                     devices=jax.devices()[:EXCHANGE_DEVICES])
    params = {k: jnp.ones(shape) for k, shape in sorted(EXCHANGE_PARAMS.items())}
    x = jnp.ones((EXCHANGE_DEVICES, 4))

    def loss(p, x):
        return ((x @ p["w"] + p["b"]) ** 2).mean()

    def body(p, x):
        return wrap(loss, p, x)

    if bound:
        body = jax.shard_map(body, mesh=mesh, in_specs=(P(), P("data")),
                             out_specs=P(), check_vma=False)
    mark = len(hvd.profiler.exchanges())
    before = time.perf_counter_ns()
    jax.jit(body).lower(params, x)
    records = hvd.profiler.exchanges()[mark:]
    assert all(before <= r.at_ns <= time.perf_counter_ns() for r in records)
    return records


def _optimizer_wrap(**options):
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="data",
                                  **options)

    def wrap(loss, p, x):
        u, _ = tx.update(jax.grad(loss)(p, x), tx.init(p), p)
        return sum(a.sum() for a in jax.tree.leaves(
            optax.apply_updates(p, u)))

    return wrap


def test_exchange_record_under_shard_map():
    [r] = _traced_exchange(_optimizer_wrap())
    assert r == hvd.profiler.ExchangeRecord(
        prefix="DistributedOptimizer", axis="data",
        axis_size=EXCHANGE_DEVICES, leaves=2, bytes_asked=80, bytes_wire=80,
        wire_dtypes={"float32": 80}, average=True, at_ns=r.at_ns,
        parent=None)
    # The optimizer's ``name=`` is the record's prefix, as it is the
    # per-leaf scopes'; a sum is told from a mean.
    [named] = _traced_exchange(_optimizer_wrap(name="Outer", average=False))
    assert (named.prefix, named.average) == ("Outer", False)


def test_exchange_record_counts_the_wire_under_compression():
    [r] = _traced_exchange(_optimizer_wrap(
        compression=hvd.Compression.bf16))
    assert (r.leaves, r.bytes_asked, r.bytes_wire) == (2, 80, 40)
    assert r.wire_dtypes == {"bfloat16": 40}
    [r] = _traced_exchange(_optimizer_wrap(
        compression=hvd.Compression.fp16))
    assert r.wire_dtypes == {"float16": 40}


def test_exchange_record_says_when_the_axis_is_unbound():
    """Plain ``jit``: every collective falls back to the identity. The
    record is the one place that says so; nothing is cast for a wire that
    is not there."""
    [r] = _traced_exchange(
        _optimizer_wrap(compression=hvd.Compression.bf16), bound=False)
    assert r.axis == "data" and r.axis_size is None
    assert (r.leaves, r.bytes_asked, r.bytes_wire) == (2, 80, 80)
    assert r.wire_dtypes == {"float32": 80}


def test_exchange_record_of_distributed_value_and_grad():
    def wrap(loss, p, x):
        value, g = hvd.distributed_value_and_grad(
            loss, axis_name="data")(p, x)
        return value + sum(a.sum() for a in jax.tree.leaves(g))

    [r] = _traced_exchange(wrap)
    assert (r.prefix, r.axis_size, r.leaves, r.bytes_wire) == (
        "DistributedGrad", EXCHANGE_DEVICES, 2, 80)


def test_exchange_record_names_the_open_span_and_none_is_left_eagerly():
    with hvd.profiler.span("build_step"):
        [r] = _traced_exchange(_optimizer_wrap())
    assert r.parent == "build_step"
    # The eager branch (concrete leaves) keeps its timeline: no record.
    hvd.init()
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {k: jnp.ones(shape) for k, shape in sorted(EXCHANGE_PARAMS.items())}
    mark = len(hvd.profiler.exchanges())
    tx.update(params, tx.init(params), params)
    assert hvd.profiler.exchanges()[mark:] == []


def test_exchange_log_is_bounded(monkeypatch):
    import collections

    from horovod_tpu.common import profiler

    monkeypatch.setattr(profiler, "_exchanges", collections.deque(maxlen=2))
    for _ in range(3):
        _traced_exchange(_optimizer_wrap())
    assert len(profiler.exchanges()) == 2


def _flash_text(which, **blocks):
    """``blocks`` empty: S=128 is one tile at the default blocks; explicit
    small blocks stream. Both paths call their kernels by the same names."""
    from horovod_tpu.ops import attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def attn(q):
        return attention.flash_attention(q, q, q, interpret=True, **blocks)

    if which == "fwd":
        return _lowered_text(attn, q)
    return _lowered_text(jax.grad(lambda q: attn(q).sum()), q)


def _decode_text(paged):
    from horovod_tpu.ops import decode_attention as da

    b, h, hkv, d, cap = 2, 4, 2, 128, 256
    q = jnp.ones((b, 1, h, d), jnp.float32)
    if not paged:
        cache = jnp.ones((b, cap, hkv * d), jnp.float32)
        return _lowered_text(
            lambda q, k, v: da.decode_attention(
                q, k, v, jnp.int32(5), num_kv_heads=hkv, interpret=True),
            q, cache, cache)
    pool = jnp.ones((8, 128, hkv * d), jnp.float32)
    tables = jnp.zeros((b, 2), jnp.int32)
    lens = jnp.full((b,), 5, jnp.int32)
    return _lowered_text(
        lambda q, k, v: da.paged_decode_attention(
            q, k, v, tables, lens, num_kv_heads=hkv, interpret=True),
        q, pool, pool)


@pytest.mark.parametrize("constant,name,text_of", [
    ("KERNEL_FLASH_FWD", "hvd_flash_fwd", lambda: _flash_text("fwd")),
    ("KERNEL_FLASH_FWD", "hvd_flash_fwd",
     lambda: _flash_text("fwd", block_q=64, block_k=64)),
    ("KERNEL_FLASH_BWD_DQ", "hvd_flash_bwd_dq", lambda: _flash_text("bwd")),
    ("KERNEL_FLASH_BWD_DQ", "hvd_flash_bwd_dq",
     lambda: _flash_text("bwd", block_q=64, block_k=64)),
    ("KERNEL_FLASH_BWD_DKV", "hvd_flash_bwd_dkv",
     lambda: _flash_text("bwd")),
    ("KERNEL_FLASH_BWD_DKV", "hvd_flash_bwd_dkv",
     lambda: _flash_text("bwd", block_q=64, block_k=64)),
    ("KERNEL_DECODE", "hvd_decode", lambda: _decode_text(False)),
    ("KERNEL_PAGED_DECODE", "hvd_paged_decode", lambda: _decode_text(True)),
])
def test_kernel_names_in_hlo(constant, name, text_of):
    assert getattr(hvd.profiler, constant) == name
    # As a whole word, as the benchmark's readers match it.
    word = re.compile(r"(?<![\w.])" + re.escape(name) + r"(?![\w.])")
    assert any(word.search(path) for path in _scope_paths(text_of(), name))


def test_decode_scope_vocabulary():
    from horovod_tpu.utils import comm_accounting

    assert hvd.profiler.decode_scope("kernel_tp") == "hvd.decode.kernel_tp"
    assert set(comm_accounting.DECODE_PATH_MARKERS) == {
        "hvd.decode." + p for p in hvd.profiler.DECODE_PATHS}
    with pytest.raises(ValueError, match="unknown decode path"):
        hvd.profiler.decode_scope("elsewhere")


# ------------------------------------------------- the cache keeps names

@pytest.fixture
def scratch_compile_cache(tmp_path, monkeypatch):
    """jax's persistent cache in ``tmp_path`` with every program kept,
    as ``compile_cache.enable()`` finds it when the directory was placed
    from outside; everything put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu.utils import compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_enable_compilation_cache")
    before = [(n, getattr(jax.config, n)) for n in names]
    placed = str(tmp_path / "cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield placed
    for n, v in before:
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    jax.clear_caches()


def _compile_under(scope):
    def f(x):
        with jax.named_scope(scope):
            return jnp.sin(x) * 2

    jax.clear_caches()      # only the persistent cache may answer
    return jax.jit(jax.grad(lambda x: f(x).sum())).lower(
        jnp.ones(16)).compile().as_text()


def test_compile_cache_cannot_hand_back_another_commits_names(
        scratch_compile_cache):
    from horovod_tpu.utils import compile_cache

    # The trap, as jax comes: the key leaves location metadata out, so a
    # program that differs only in a scope gets the cached names back.
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    assert "trap_scope_a" in _compile_under("trap_scope_a")
    stale = _compile_under("trap_scope_b")
    assert "trap_scope_a" in stale and "trap_scope_b" not in stale

    # enable() closes it, in the branch that leaves the directory alone.
    assert compile_cache.enable() == scratch_compile_cache
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    assert "true_scope_a" in _compile_under("true_scope_a")
    # The key now holds the locations, call stack included, so "the same
    # program" means traced from the same lines: one call site, twice.
    texts, counts = [], [compile_cache.entry_count(scratch_compile_cache)]
    for _ in range(2):
        texts.append(_compile_under("true_scope_b"))
        counts.append(compile_cache.entry_count(scratch_compile_cache))
    for fresh in texts:
        assert "true_scope_b" in fresh and "true_scope_a" not in fresh
    assert counts[1] > counts[0]    # its own entry, not the other's
    assert counts[2] == counts[1]   # and warm from then on
    # It plants no span: nothing read the one it had (PR 34).
    assert not any(s.name == "compile_cache.enable"
                   for s in hvd.profiler.spans())
