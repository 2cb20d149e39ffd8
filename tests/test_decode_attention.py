"""Pallas decode-step attention (interpret mode on CPU) vs the masked
reference softmax — the kernel that frees the KV cache from the XLA
layout/update trade-off (artifacts/decode_ceiling_r5.json)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.decode_attention import \
    decode_attention as _decode_attention

# One compiled program a shape, for the kernel and for the reference: the
# cache index is traced, as it is in generate()'s decode scan, so the
# cases of one shape share theirs.
decode_attention = jax.jit(_decode_attention, static_argnums=4,
                           static_argnames="block_l")

@functools.partial(jax.jit, static_argnums=4)
def _reference(q, k_cache, v_cache, cache_index, hkv):
    b, s, h, d = q.shape
    L = k_cache.shape[1]
    k_cache = k_cache.reshape(b, L, hkv, d)
    v_cache = v_cache.reshape(b, L, hkv, d)
    group = h // hkv
    qg = q.reshape(b, s, hkv, group, d)
    logits = jnp.einsum("bshgd,blhd->bshgl", qg, k_cache).astype(
        jnp.float32) / np.sqrt(d)
    mask = jnp.arange(k_cache.shape[1]) <= cache_index
    logits = jnp.where(mask[None, None, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bshgl,blhd->bshgd", probs, v_cache).reshape(
        b, s, h, d)


@pytest.mark.parametrize("hkv,h", [
    (2, 2),    # MHA (group == 1)
    (2, 4),
    (4, 16),
    (1, 8),    # MQA (one K/V head)
])
@pytest.mark.parametrize("cache_index", [0, 3, 30])
def test_matches_reference(hkv, h, cache_index):
    rng = np.random.RandomState(0)
    b, L, d = 3, 32, 16
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32)) * 0.4
    k = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    v = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    out = decode_attention(q, k, v, cache_index, hkv)
    ref = _reference(q, k, v, cache_index, hkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_traced_cache_index_under_scan():
    # cache_index is traced in generate()'s decode scan.
    rng = np.random.RandomState(1)
    b, L, hkv, h, d = 2, 16, 2, 4, 8
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32)) * 0.4
    k = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    v = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4

    @jax.jit
    def scan_all(q, k, v):
        def body(c, i):
            return c, decode_attention(q, k, v, i, hkv)
        _, outs = jax.lax.scan(body, 0, jnp.arange(4))
        return outs

    outs = scan_all(q, k, v)
    for i in range(4):
        ref = _reference(q, k, v, i, hkv)
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("cache_index", [0, 255, 256, 700, 1023])
def test_multi_tile_accumulation(cache_index):
    # L > DECODE_BLOCK_L: the online-softmax state must accumulate
    # correctly across L-tiles, including indices on tile boundaries and
    # tiles fully above the causal bound (their compute is skipped).
    rng = np.random.RandomState(2)
    b, L, hkv, h, d = 2, 1024, 2, 4, 16
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32)) * 0.4
    k = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    v = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    out = decode_attention(q, k, v, cache_index, hkv, block_l=256)
    ref = _reference(q, k, v, cache_index, hkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_wide_heads_d128():
    # Llama-8B head width: d=128, f=1024 — the shape class the L-tiling
    # exists for (verified compiling at L=8192 on-chip; here parity).
    rng = np.random.RandomState(3)
    b, L, hkv, h, d = 1, 64, 2, 8, 128
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.3
    out = decode_attention(q, k, v, 50, hkv)
    ref = _reference(q, k, v, 50, hkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_bf16_inputs():
    rng = np.random.RandomState(4)
    b, L, hkv, h, d = 2, 32, 2, 4, 16
    q = jnp.asarray(rng.randn(b, 1, h, d) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, L, hkv * d) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, L, hkv * d) * 0.3, jnp.bfloat16)
    out = decode_attention(q, k, v, 20, hkv)
    assert out.dtype == jnp.bfloat16
    ref = _reference(q, k, v, 20, hkv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)


def test_block_l_selection():
    from horovod_tpu.ops.decode_attention import _pick_block_l

    # Fits the single-tile budget -> whole window (Llama-300M bench
    # config: L=384, f=512, bf16 = 786 KiB).
    assert _pick_block_l(384, 512, 2, 256) == 384
    # Past the budget -> largest divisor <= requested, NOT a power-of-2
    # halving (2176 = 128*17: halving would collapse 256->8; the divisor
    # picks 136... check) — init_kv_cache's 128-multiple rounding
    # guarantees >= 128-ish divisors.
    assert _pick_block_l(4096, 1024, 2, 256) == 256
    b = _pick_block_l(2176, 1024, 2, 256)
    assert 2176 % b == 0 and b >= 128          # 136 or better
    # Prime-ish L with no usable divisor but fits 8 MiB -> single tile.
    assert _pick_block_l(2131, 512, 2, 256) == 2131
    # Prime-ish L beyond 8 MiB -> degenerate divisor is all that's left
    # (correct, slow; generate() never builds such a window).
    assert _pick_block_l(8209, 1024, 2, 256) == 1


def test_validation():
    q = jnp.zeros((2, 2, 4, 8))
    k = v = jnp.zeros((2, 16, 2 * 8))
    with pytest.raises(ValueError, match="single-token"):
        decode_attention(q, k, v, 0, 2)
    with pytest.raises(ValueError, match="multiple"):
        decode_attention(jnp.zeros((2, 1, 3, 8)), k, v, 0, 2)


# ---------------------------------------------------------------------------
# shard_mapped kernel (TP-sharded serving path) + the sharding classifier.


def _tp_mesh(data_par, model_par):
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:data_par * model_par])
    return Mesh(devs.reshape(data_par, model_par), ("data", "model"))


@pytest.mark.parametrize("data_par,model_par,batch_axis", [
    (1, 2, None),       # pure TP, batch replicated
    (2, 2, "data"),     # dp x tp serving shape
    (1, 4, None),       # tp == hkv: one K/V head per shard (MQA per shard)
])
def test_sharded_decode_step_matches_reference(data_par, model_par,
                                               batch_axis):
    # The shard_mapped per-shard kernel + per-shard cache-row write must
    # reproduce the single-device masked softmax exactly: attention is
    # per-head independent, so head sharding must be invisible.
    from horovod_tpu.ops.decode_attention import sharded_decode_step

    rng = np.random.RandomState(7)
    b, L, hkv, h, d = 4, 32, 4, 8, 16
    idx = 9
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32)) * 0.4
    kn = jnp.asarray(rng.randn(b, 1, hkv, d).astype(np.float32)) * 0.4
    vn = jnp.asarray(rng.randn(b, 1, hkv, d).astype(np.float32)) * 0.4
    kc = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    vc = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    mesh = _tp_mesh(data_par, model_par)
    out, k2, v2 = jax.jit(functools.partial(
        sharded_decode_step, num_kv_heads=hkv, mesh=mesh, head_axis="model",
        batch_axis=batch_axis))(q, kn, vn, kc, vc, idx)
    k_ref = kc.at[:, idx].set(kn.reshape(b, hkv * d))
    v_ref = vc.at[:, idx].set(vn.reshape(b, hkv * d))
    np.testing.assert_allclose(np.asarray(k2), np.asarray(k_ref),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v_ref),
                               atol=1e-6)
    ref = _reference(q, k_ref, v_ref, idx, hkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_sharded_decode_step_traced_index():
    # cache_index is traced inside generate()'s decode scan.
    from horovod_tpu.ops.decode_attention import sharded_decode_step

    rng = np.random.RandomState(8)
    b, L, hkv, h, d = 2, 16, 2, 4, 8
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32)) * 0.4
    kn = jnp.asarray(rng.randn(b, 1, hkv, d).astype(np.float32)) * 0.4
    vn = jnp.asarray(rng.randn(b, 1, hkv, d).astype(np.float32)) * 0.4
    kc = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    vc = jnp.asarray(rng.randn(b, L, hkv * d).astype(np.float32)) * 0.4
    mesh = _tp_mesh(1, 2)

    @jax.jit
    def step(i):
        return sharded_decode_step(q, kn, vn, kc, vc, i, hkv, mesh=mesh,
                                   head_axis="model")

    for idx in (0, 7, 15):
        out, k2, v2 = step(idx)
        k_ref = kc.at[:, idx].set(kn.reshape(b, hkv * d))
        v_ref = vc.at[:, idx].set(vn.reshape(b, hkv * d))
        ref = _reference(q, k_ref, v_ref, idx, hkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


def test_sharded_decode_step_validation():
    from horovod_tpu.ops.decode_attention import sharded_decode_step

    mesh = _tp_mesh(1, 4)
    q = jnp.zeros((2, 1, 4, 8))
    kn = vn = jnp.zeros((2, 1, 2, 8))
    kc = vc = jnp.zeros((2, 16, 2 * 8))
    with pytest.raises(ValueError, match="not shardable"):
        # Hkv=2 does not divide over tp=4.
        sharded_decode_step(q, kn, vn, kc, vc, 0, 2, mesh=mesh,
                            head_axis="model")
    with pytest.raises(ValueError, match="single-token"):
        sharded_decode_step(jnp.zeros((2, 2, 4, 8)), kn, vn, kc, vc, 0, 2,
                            mesh=_tp_mesh(1, 2), head_axis="model")


# --- classifier: replicated / heads-sharded / exotic dispatch -------------


@pytest.fixture(scope="module")
def tiny_tp():
    """``(cfg, model, variables, prompt)`` of the tiny float32 Llama the
    classifier and TP tests share, initialised once (one compiled init).
    It outlives ``_fresh_state``: a config, a flax module and arrays on
    device 0, nothing of ``hvd`` or the mesh registry; no test writes to
    the tree it is handed."""
    import dataclasses

    from horovod_tpu.models.llama import LLAMA_TINY, LlamaLM

    cfg = dataclasses.replace(LLAMA_TINY, dtype=jnp.float32)
    model = LlamaLM(cfg)
    prompt = jnp.asarray(
        np.random.RandomState(3).randint(0, cfg.vocab_size, (4, 5)),
        jnp.int32)
    return cfg, model, jax.jit(model.init)(jax.random.PRNGKey(0),
                                           prompt), prompt


def _tp_sharded(variables, mesh, axis="model"):
    from jax.sharding import NamedSharding

    from horovod_tpu.models import llama_tp_param_specs

    specs = llama_tp_param_specs(variables["params"], axis=axis)
    return {"params": jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        variables["params"], specs)}


def test_classifier_replicated(tiny_tp):
    from horovod_tpu.models import classify_decode_sharding

    cfg, _, variables, prompt = tiny_tp
    info = classify_decode_sharding(variables, prompt, cfg.num_kv_heads)
    assert info.path == "kernel"


def test_classifier_heads_sharded_tp(tiny_tp):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import classify_decode_sharding

    mesh = _tp_mesh(2, 2)
    cfg, _, variables, prompt = tiny_tp
    sharded = _tp_sharded(variables, mesh)
    info = classify_decode_sharding(sharded, prompt, cfg.num_kv_heads)
    assert info.path == "kernel_tp"
    assert info.head_axis == "model" and info.batch_axis is None

    # dp x tp: prompt sharded over the data axis rides along.
    prompt_sh = jax.device_put(prompt, NamedSharding(mesh, P("data")))
    info = classify_decode_sharding(sharded, prompt_sh, cfg.num_kv_heads)
    assert info.path == "kernel_tp" and info.batch_axis == "data"


def test_classifier_exotic_falls_back_to_einsum(tiny_tp):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import classify_decode_sharding

    mesh = _tp_mesh(2, 2)
    cfg, _, variables, prompt = tiny_tp
    sharded = _tp_sharded(variables, mesh)

    # Uneven head split: tp=4 mesh axis on the H=4 wq heads while Hkv=2
    # can't split 4 ways (wk/wv stay replicated on the same mesh).
    mesh4 = _tp_mesh(1, 4)
    repl4 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh4, P())), variables)
    wq4 = repl4["params"]["layer_0"]["attention"]["wq"]["kernel"]
    repl4["params"]["layer_0"]["attention"]["wq"]["kernel"] = \
        jax.device_put(
            jax.device_get(wq4),
            NamedSharding(mesh4, P(None, "model", None)))
    info = classify_decode_sharding(repl4, prompt, cfg.num_kv_heads)
    assert info.path == "einsum" and "uneven" in info.reason

    # Sequence-sharded prompt (the cache would shard on seq): exotic.
    prompt_seq = jax.device_put(prompt[:, :4],
                                NamedSharding(mesh, P(None, "data")))
    info = classify_decode_sharding(sharded, prompt_seq, cfg.num_kv_heads)
    assert info.path == "einsum"

    # Attention params sharded OFF the heads dim (dim 0 of wq).
    bad = jax.tree_util.tree_map(lambda x: x, sharded)
    wq = bad["params"]["layer_0"]["attention"]["wq"]["kernel"]
    bad["params"]["layer_0"]["attention"]["wq"]["kernel"] = jax.device_put(
        wq, NamedSharding(mesh, P("model", None, None)))
    info = classify_decode_sharding(bad, prompt, cfg.num_kv_heads)
    assert info.path == "einsum"


def test_generate_tp_rides_shard_mapped_kernel(tiny_tp):
    # The CPU-mesh parity pin for the tentpole: generate() under Megatron
    # TP specs must (a) emit the SAME greedy tokens as the replicated
    # single-device run and (b) actually trace the shard_mapped Pallas
    # kernel, not the einsum fallback — proven both by the classifier
    # record and by the hvd.decode.* scope markers in the lowered step.
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu.models.llama as llama_mod
    from horovod_tpu.models import generate, init_kv_cache
    from horovod_tpu.models.llama import decode_kernel_sharded
    from horovod_tpu.utils.comm_accounting import decode_path_markers

    mesh = _tp_mesh(2, 2)
    cfg, model, variables, prompt = tiny_tp
    base = generate(model, variables, prompt, max_new_tokens=5)
    assert llama_mod.LAST_DECODE_PATH.path == "kernel"

    sharded = _tp_sharded(variables, mesh)
    prompt_sh = jax.device_put(prompt, NamedSharding(mesh, P("data")))
    with mesh:
        tp = generate(model, sharded, prompt_sh, max_new_tokens=5)
    assert llama_mod.LAST_DECODE_PATH.path == "kernel_tp"
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tp))

    # HLO-metadata attribution: a decode step traced under the TP context
    # carries ONLY the kernel_tp marker.
    cache = init_kv_cache(cfg, 4, 16)

    def step(v, tok, cache):
        return model.apply(v, tok, cache=cache, cache_index=5)

    with decode_kernel_sharded(mesh, "model", "data"):
        compiled = jax.jit(step).lower(
            variables, prompt[:, :1], cache).compile()
    markers = decode_path_markers(compiled)
    assert markers["hvd.decode.kernel_tp"] > 0
    assert markers["hvd.decode.einsum"] == 0
    assert markers["hvd.decode.kernel"] == 0
