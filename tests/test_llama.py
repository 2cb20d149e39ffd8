"""Decoder-only LM tests: shapes, causality, gradient flow, flash seam."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (LLAMA_TINY, LlamaLM, causal_lm_loss,
                                chunked_causal_lm_loss)
from model_helpers import jit_apply, jit_init


@pytest.fixture(scope="module")
def variables():
    """LLAMA_TINY's variables, initialised once for the file: the
    parameters are float32 and depend on neither the shape of the ids they
    are traced with nor the config's compute dtype (``PRNGKey(0)``, as
    each test used to draw them for itself). It outlives
    ``_fresh_state``: arrays on device 0, nothing of ``hvd`` or the mesh
    registry; no test writes to the tree."""
    return jit_init(LlamaLM(LLAMA_TINY), _ids((2, 16)))


def _ids(shape, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, LLAMA_TINY.vocab_size, shape),
        jnp.int32)


def test_forward_and_loss(variables):
    model = LlamaLM(LLAMA_TINY)
    ids = _ids((2, 16))
    logits = jit_apply(model)(variables, ids)
    assert logits.shape == (2, 16, LLAMA_TINY.vocab_size)
    loss = causal_lm_loss(logits, ids)
    assert 0.5 * np.log(LLAMA_TINY.vocab_size) < float(loss) < \
        2 * np.log(LLAMA_TINY.vocab_size)


def test_head_dtype_knob(variables):
    # Default: logits in the model compute dtype (bf16). head_dtype=f32
    # opts raw-logit consumers back into full precision (advisor round-2).
    import dataclasses

    ids = _ids((1, 8))
    model = LlamaLM(LLAMA_TINY)
    assert jit_apply(model)(variables, ids).dtype == LLAMA_TINY.dtype
    f32_model = LlamaLM(
        dataclasses.replace(LLAMA_TINY, head_dtype=jnp.float32))
    assert jit_apply(f32_model)(variables, ids).dtype == jnp.float32


def test_causality(variables):
    model = LlamaLM(LLAMA_TINY)
    ids = _ids((1, 12))
    forward = jit_apply(model)
    out1 = forward(variables, ids)
    ids2 = ids.at[0, 8].set((int(ids[0, 8]) + 1) % LLAMA_TINY.vocab_size)
    out2 = forward(variables, ids2)
    # Positions before 8 must be unchanged; position 8 must change.
    np.testing.assert_allclose(np.asarray(out1[0, :8]),
                               np.asarray(out2[0, :8]), atol=1e-4)
    assert not np.allclose(np.asarray(out1[0, 8]), np.asarray(out2[0, 8]))


def test_gradients_flow(variables):
    model = LlamaLM(LLAMA_TINY)
    ids = _ids((2, 8))

    def loss_fn(params):
        return causal_lm_loss(model.apply({"params": params}, ids), ids)

    grads = jax.jit(jax.grad(loss_fn))(variables["params"])
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    assert any(float(jnp.abs(l).max()) > 0 for l in leaves)


def test_flash_attention_seam(variables):
    from horovod_tpu.ops.attention import make_attention_fn

    cfg = LLAMA_TINY
    ids = _ids((1, 32))
    ref_model = LlamaLM(cfg)
    out_ref = jit_apply(ref_model)(variables, ids)
    flash_model = LlamaLM(cfg, attention_fn=make_attention_fn(
        causal=True, use_flash=True, block_q=16, block_k=16))
    out_flash = jit_apply(flash_model)(variables, ids)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_ref),
                               atol=5e-2, rtol=5e-2)


def test_sequence_parallel_ring_attention(variables):
    """Long-context integration: LlamaLM runs inside a sequence-sharded
    shard_map with ring attention plugged into the attention_fn seam and
    GLOBAL RoPE positions per shard — output must match the single-device
    model with the same params."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel.sequence import ring_attention

    n = 8
    cfg = LLAMA_TINY
    s = 64
    ids = _ids((2, s), seed=3)
    ref_model = LlamaLM(cfg)
    ref = jit_apply(ref_model)(variables, ids)

    sp_model = LlamaLM(cfg, attention_fn=lambda q, k, v, m: ring_attention(
        q, k, v, axis_name="seq", causal=True))
    mesh = make_mesh({"seq": n})
    s_local = s // n

    def body(params, ids_shard):
        idx = jax.lax.axis_index("seq")
        positions = idx * s_local + jnp.arange(s_local)
        return sp_model.apply(params, ids_shard, positions=positions)

    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    out = f(variables, ids)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_sequence_parallel_rope_positions_matter(variables):
    """Without global positions the sharded model must NOT match —
    guarding against silently-local RoPE (every shard rotating as if it
    held the sequence start)."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel.sequence import ring_attention

    n = 8
    cfg = LLAMA_TINY
    s = 64
    ids = _ids((2, s), seed=4)
    ref_model = LlamaLM(cfg)
    ref = np.asarray(jit_apply(ref_model)(variables, ids), np.float32)

    sp_model = LlamaLM(cfg, attention_fn=lambda q, k, v, m: ring_attention(
        q, k, v, axis_name="seq", causal=True))
    mesh = make_mesh({"seq": n})

    f = jax.jit(jax.shard_map(
        lambda p, i: sp_model.apply(p, i),  # positions default to LOCAL
        mesh=mesh, in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    out = np.asarray(f(variables, ids), np.float32)
    assert not np.allclose(out, ref, atol=5e-2, rtol=5e-2)


def test_sp_causal_lm_loss_matches_single_device():
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import sp_causal_lm_loss
    from horovod_tpu.parallel import make_mesh

    rng = np.random.RandomState(7)
    b, s, vocab = 2, 64, 50
    logits = jnp.asarray(rng.randn(b, s, vocab), jnp.float32)
    ids = jnp.asarray(rng.randint(0, vocab, (b, s)), jnp.int32)
    full = causal_lm_loss(logits, ids)

    mesh = make_mesh({"seq": 8})
    sp = jax.jit(jax.shard_map(
        lambda lg, i: sp_causal_lm_loss(lg, i, "seq"),
        mesh=mesh, in_specs=(P(None, "seq"), P(None, "seq")),
        out_specs=P(), check_vma=False))(logits, ids)
    np.testing.assert_allclose(float(sp), float(full), rtol=1e-6)


def test_sequence_parallel_ulysses(variables):
    """Ulysses all-to-all SP through the same seam: heads split over the
    axis, full-sequence attention per shard, global RoPE positions."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel.sequence import ulysses_attention

    n = 4  # must divide LLAMA_TINY's 4 heads
    cfg = LLAMA_TINY
    s = 64
    ids = _ids((2, s), seed=5)
    ref_model = LlamaLM(cfg)
    ref = jit_apply(ref_model)(variables, ids)

    sp_model = LlamaLM(cfg, attention_fn=lambda q, k, v, m:
                       ulysses_attention(q, k, v, axis_name="seq",
                                         causal=True))
    mesh = make_mesh({"seq": n}, devices=jax.devices()[:n])
    s_local = s // n

    def body(params, ids_shard):
        idx = jax.lax.axis_index("seq")
        positions = idx * s_local + jnp.arange(s_local)
        return sp_model.apply(params, ids_shard, positions=positions)

    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    out = f(variables, ids)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_sequence_parallel_ring_zigzag(variables):
    """Zigzag-layout SP: ids and RoPE positions both follow the zigzag
    shard order (zigzag_positions), output unshards to match the
    single-device model."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel.sequence import (
        ring_attention,
        zigzag_positions,
        zigzag_shard,
        zigzag_unshard,
    )

    n = 8
    cfg = LLAMA_TINY
    s = 64
    ids = _ids((2, s), seed=6)
    ref_model = LlamaLM(cfg)
    ref = jit_apply(ref_model)(variables, ids)

    sp_model = LlamaLM(cfg, attention_fn=lambda q, k, v, m: ring_attention(
        q, k, v, axis_name="seq", causal=True, layout="zigzag"))
    mesh = make_mesh({"seq": n})
    s_local = s // n

    def body(params, ids_shard):
        idx = jax.lax.axis_index("seq")
        positions = zigzag_positions(idx, s_local, n)
        return sp_model.apply(params, ids_shard, positions=positions)

    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    out = zigzag_unshard(f(variables, zigzag_shard(ids, n)), n)
    # Slightly looser than the contiguous test: the zigzag merge reorders
    # bf16 reductions (observed worst case ~0.07 on a handful of logits).
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-1, rtol=5e-2)


def test_remat_matches_no_remat(variables):
    import dataclasses

    ids = _ids((2, 16))
    # float32: under jit the two programs fuse differently, and in
    # bfloat16 that alone moves the gradients by their rounding.
    cfg = dataclasses.replace(LLAMA_TINY, dtype=jnp.float32)
    base = LlamaLM(cfg)
    remat = LlamaLM(dataclasses.replace(cfg, remat=True))

    def loss_fn(model):
        def f(params):
            return causal_lm_loss(model.apply({"params": params}, ids), ids)
        return f

    # Same params apply in both: remat only changes WHEN activations are
    # (re)computed, never the math.
    l0, g0 = jax.jit(jax.value_and_grad(loss_fn(base)))(variables["params"])
    l1, g1 = jax.jit(jax.value_and_grad(loss_fn(remat)))(variables["params"])
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1)


def test_chunked_loss_matches_full(variables):
    model = LlamaLM(LLAMA_TINY)
    ids = _ids((2, 16))

    def full(params):
        return causal_lm_loss(model.apply({"params": params}, ids), ids)

    def chunked(params):
        hidden = model.apply({"params": params}, ids, return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, params["lm_head"]["kernel"], ids, num_chunks=4)

    l0, g0 = jax.jit(jax.value_and_grad(full))(variables["params"])
    l1, g1 = jax.jit(jax.value_and_grad(chunked))(variables["params"])
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)

    # Gradients agree up to the bf16 rounding of the logits' cotangent
    # (the chunked head rounds softmax - onehot once, autodiff of the full
    # logits rounds its two terms apart — see the loss docstring), so
    # compare leaf-wise grad-norm ratios, not elements.
    def close_in_norm(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        denom = max(np.linalg.norm(a), 1e-12)
        assert np.linalg.norm(a - b) / denom < 2e-2, (
            np.linalg.norm(a - b), denom)

    jax.tree.map(close_in_norm, g0, g1)


def test_chunked_loss_rejects_indivisible():
    hidden = jnp.zeros((1, 10, LLAMA_TINY.dim), jnp.bfloat16)
    kernel = jnp.zeros((LLAMA_TINY.dim, LLAMA_TINY.vocab_size))
    with pytest.raises(ValueError, match="divisible"):
        chunked_causal_lm_loss(hidden, kernel, jnp.zeros((1, 10), jnp.int32),
                               num_chunks=3)


def _head_problem(batch, dtype, kernel_dtype=jnp.float32, seq=16):
    """Hidden states, a head kernel and ids at LLAMA_TINY's widths."""
    k_h, k_w = jax.random.split(jax.random.PRNGKey(3))
    hidden = jax.random.normal(
        k_h, (batch, seq, LLAMA_TINY.dim), jnp.float32).astype(dtype)
    kernel = (0.2 * jax.random.normal(
        k_w, (LLAMA_TINY.dim, LLAMA_TINY.vocab_size),
        jnp.float32)).astype(kernel_dtype)
    return hidden, kernel, _ids((batch, seq), seed=batch)


def _full_head_loss(ids):
    return lambda h, w: causal_lm_loss(h @ w.astype(h.dtype), ids)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-12)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("num_chunks", [1, 2, 8])
def test_chunked_loss_and_both_gradients_match_autodiff_of_full_logits(
        num_chunks, batch, dtype, tol):
    hidden, kernel, ids = _head_problem(batch, dtype)
    def chunked(h, w):
        return chunked_causal_lm_loss(h, w, ids, num_chunks=num_chunks)

    l0, g0 = jax.jit(jax.value_and_grad(
        _full_head_loss(ids), argnums=(0, 1)))(hidden, kernel)
    l1, g1 = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))(
        hidden, kernel)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    # Differentiated or not, the same loss to the bit.
    assert float(l1) == float(jax.jit(chunked)(hidden, kernel))
    for a, b in zip(g0, g1):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert _rel(a, b) < tol, (_rel(a, b), tol)


def test_chunked_loss_bf16_gradients_do_not_hang_on_the_chunk_count():
    # dW is summed over the chunks in float32 and rounded once: what is
    # left against float32 autodiff is the rounding of the logits and of
    # their cotangent, the same at every chunk count (the sum of bf16
    # partials this replaces grew with it).
    hidden, kernel, ids = _head_problem(2, jnp.bfloat16)
    _, exact = jax.jit(jax.value_and_grad(
        _full_head_loss(ids), argnums=(0, 1)))(
        hidden.astype(jnp.float32), kernel)
    gaps = []
    for num_chunks in (1, 8):
        got = jax.jit(jax.grad(
            lambda h, w: chunked_causal_lm_loss(
                h, w, ids, num_chunks=num_chunks), argnums=(0, 1)))(
                    hidden, kernel)
        gaps.append([_rel(a, b) for a, b in zip(exact, got)])
    assert max(gaps[0] + gaps[1]) < 1e-2, gaps
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0.05)


@pytest.mark.parametrize("wrap", [
    lambda loss, h, w: 3.0 * loss(h, w),
    lambda loss, h, w: loss(h, w) + 0.5 * (h.astype(jnp.float32) ** 2).sum()
    + (w ** 2).sum(),
    lambda loss, h, w: loss(h, w) * loss(h, 2.0 * w),
], ids=["scaled", "in_a_sum", "twice"])
def test_chunked_loss_takes_a_cotangent_that_is_not_one(wrap):
    hidden, kernel, ids = _head_problem(2, jnp.float32)

    def chunked(h, w):
        return chunked_causal_lm_loss(h, w, ids, num_chunks=4)

    g0 = jax.jit(jax.grad(lambda h, w: wrap(_full_head_loss(ids), h, w),
                          argnums=(0, 1)))(hidden, kernel)
    g1 = jax.jit(jax.grad(lambda h, w: wrap(chunked, h, w),
                          argnums=(0, 1)))(hidden, kernel)
    for a, b in zip(g0, g1):
        assert _rel(a, b) < 1e-6, _rel(a, b)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_loss_last_position_gets_no_gradient(dtype):
    hidden, kernel, ids = _head_problem(2, dtype)
    dh = jax.jit(jax.grad(lambda h: chunked_causal_lm_loss(
        h, kernel, ids, num_chunks=4)))(hidden)
    assert dh.dtype == dtype
    assert not np.asarray(dh[:, -1], np.float32).any()
    assert np.asarray(dh[:, :-1], np.float32).any(axis=-1).all()


@pytest.mark.parametrize("kernel_dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_loss_kernel_gradient_comes_back_in_the_kernels_dtype(
        kernel_dtype):
    hidden, kernel, ids = _head_problem(2, jnp.bfloat16, kernel_dtype)
    dw = jax.jit(jax.grad(lambda w: chunked_causal_lm_loss(
        hidden, w, ids, num_chunks=4)))(kernel)
    assert dw.dtype == kernel_dtype and dw.shape == kernel.shape
    want = jax.jit(jax.grad(lambda w: _full_head_loss(ids)(hidden, w)))(
        kernel)
    assert _rel(want, dw) < 2e-2


def _vocab_wide_products(jaxpr):
    """dot_generals with a vocabulary-wide operand or result, counted
    through every nested jaxpr, with the loops they stand in."""
    vocab = LLAMA_TINY.vocab_size
    loops = products = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scan", "while"):
            loops += 1
        if eqn.primitive.name == "dot_general" and any(
                vocab in v.aval.shape for v in eqn.invars + eqn.outvars):
            products += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inner = _vocab_wide_products(sub)
            loops, products = loops + inner[0], products + inner[1]
    return loops, products


def test_chunked_loss_one_loop_one_product_a_chunk_three_when_differentiated():
    hidden, kernel, ids = _head_problem(2, jnp.bfloat16)

    def chunked(h, w):
        return chunked_causal_lm_loss(h, w, ids, num_chunks=4)

    # The loop's body is traced once, so a count is products a chunk.
    assert _vocab_wide_products(
        jax.make_jaxpr(chunked)(hidden, kernel).jaxpr) == (1, 1)
    assert _vocab_wide_products(jax.make_jaxpr(jax.value_and_grad(
        chunked, argnums=(0, 1)))(hidden, kernel).jaxpr) == (1, 3)


def test_tensor_parallel_specs_match_data_parallel(variables):
    """Megatron-style TP via GSPMD: device_put params with
    llama_tp_param_specs over a (data, model) mesh, jit the train step,
    and the loss trajectory must match the fully-replicated run (XLA
    inserts the activation psums the layout implies)."""
    import optax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import llama_tp_param_specs

    cfg = LLAMA_TINY  # heads 4, kv 2, ffn 128, vocab 512: all divide tp=2
    model = LlamaLM(cfg)
    ids = _ids((8, 16))  # batch divides both dp=8 and dp=4
    params0 = variables["params"]
    tx = optax.adam(1e-2)

    def loss_fn(p, ids):
        return causal_lm_loss(model.apply({"params": p}, ids), ids)

    @jax.jit
    def step(p, s, ids):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    def run(mesh, param_specs):
        p = jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params0, param_specs)
        s = tx.init(p)
        x = jax.device_put(ids, NamedSharding(mesh, P("data")))
        losses = []
        with mesh:
            for _ in range(3):
                p, s, loss = step(p, s, x)
                losses.append(float(loss))
        return losses

    devs = np.array(jax.devices()[:8])
    repl = jax.tree.map(lambda x: P(), params0)
    dp_losses = run(Mesh(devs.reshape(8, 1), ("data", "model")), repl)
    tp_specs = llama_tp_param_specs(params0)
    # Guard the guard: if name matching ever broke, every leaf would fall
    # through to replicated P() and this test would compare dp against dp.
    sharded = [s for s in jax.tree.leaves(
        tp_specs, is_leaf=lambda x: isinstance(x, P)) if s != P()]
    assert len(sharded) >= 4 * cfg.num_layers + 2, tp_specs
    tp_mesh = Mesh(devs.reshape(4, 2), ("data", "model"))
    head_kernel = jax.device_put(
        params0["lm_head"]["kernel"],
        jax.sharding.NamedSharding(tp_mesh, tp_specs["lm_head"]["kernel"]))
    assert (head_kernel.addressable_shards[0].data.shape[1]
            == cfg.vocab_size // 2)
    tp_losses = run(tp_mesh, tp_specs)
    # Sharded matmuls reduce partials in a different order than the
    # replicated run, and the model computes in bf16 — the first step
    # agrees to reduction-order precision and later steps drift
    # chaotically from that seed difference, so tolerance widens with
    # step. Both runs must also actually train.
    np.testing.assert_allclose(dp_losses[0], tp_losses[0], rtol=1e-3)
    np.testing.assert_allclose(dp_losses, tp_losses, rtol=5e-2)
    assert tp_losses[-1] < tp_losses[0]


def test_kv_cache_decode_matches_full_forward(variables):
    # Greedy decoding through the static-shape KV cache must reproduce the
    # no-cache path exactly: token-by-token full forwards over the growing
    # sequence pick the same argmax at every step. f32 so numerics can't
    # flip a tie between the two einsum orders.
    import dataclasses

    from horovod_tpu.models import generate

    cfg = dataclasses.replace(LLAMA_TINY, dtype=jnp.float32)
    model = LlamaLM(cfg)
    prompt = _ids((2, 5), seed=3)

    n_new = 6
    out = generate(model, variables, prompt, max_new_tokens=n_new)
    assert out.shape == (2, 5 + n_new)
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))

    seq = prompt
    forward = jit_apply(model)      # one program a length
    for _ in range(n_new):
        logits = forward(variables, seq)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_kv_cache_logits_match_full_forward(variables):
    # Prefill + one decode step: the cached-path logits equal the full
    # forward's logits at the same positions (masked window softmax ==
    # prefix softmax; exp(-inf) is exactly 0).
    import dataclasses

    from horovod_tpu.models import init_kv_cache

    cfg = dataclasses.replace(LLAMA_TINY, dtype=jnp.float32)
    model = LlamaLM(cfg)
    ids = _ids((2, 8), seed=4)

    full = jit_apply(model)(variables, ids)
    cache = init_kv_cache(cfg, 2, 16)
    cached = jax.jit(lambda v, ids, cache, i: model.apply(
        v, ids, cache=cache, cache_index=i))
    pre, cache = cached(variables, ids[:, :7], cache, 0)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :7]),
                               rtol=1e-5, atol=1e-5)
    step, cache = cached(variables, ids[:, 7:8], cache, 7)
    np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(full[:, 7]),
                               rtol=1e-5, atol=1e-5)


def test_generate_sampling_and_validation(variables):
    from horovod_tpu.models import generate

    model = LlamaLM(LLAMA_TINY)
    prompt = _ids((1, 4), seed=5)

    # Temperature sampling: deterministic under a fixed key, right shape,
    # in-vocab tokens.
    a = generate(model, variables, prompt, max_new_tokens=3, temperature=0.8,
                 rng=jax.random.PRNGKey(7))
    b = generate(model, variables, prompt, max_new_tokens=3, temperature=0.8,
                 rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (1, 7)
    assert int(jnp.max(a)) < LLAMA_TINY.vocab_size

    import pytest

    with pytest.raises(ValueError, match="rng"):
        generate(model, variables, prompt, max_new_tokens=2, temperature=1.0)
    with pytest.raises(ValueError, match="exceeds"):
        generate(model, variables, prompt, max_new_tokens=4, max_len=6)
    # Single-token path (no scan).
    one = generate(model, variables, prompt, max_new_tokens=1)
    assert one.shape == (1, 5)


def test_generate_zero_tokens_and_temperature_shares_compile(variables):
    from horovod_tpu.models import generate
    from horovod_tpu.models.llama import _decode

    model = LlamaLM(LLAMA_TINY)
    prompt = _ids((1, 4), seed=6)

    # max_new_tokens=0 is a no-op, not an extra token.
    out = generate(model, variables, prompt, max_new_tokens=0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(prompt))

    # Temperature is a TRACED operand: sweeping values must not recompile
    # the decode program (greedy/sampling is the only static split).
    before = _decode._cache_size()
    generate(model, variables, prompt, max_new_tokens=2, temperature=0.7,
             rng=jax.random.PRNGKey(0))
    one = _decode._cache_size()
    generate(model, variables, prompt, max_new_tokens=2, temperature=1.3,
             rng=jax.random.PRNGKey(0))
    assert _decode._cache_size() == one > before


def test_generate_tensor_parallel_matches_single_device(variables):
    # Multi-chip INFERENCE: generate() with params device_put under the
    # Megatron TP specs (llama_tp_param_specs) — GSPMD propagates the
    # shardings through prefill + scan and inserts the per-block psums —
    # must emit the same greedy tokens as replicated params. f32 so
    # reduction order can't flip an argmax tie.
    import dataclasses

    from jax.sharding import Mesh, NamedSharding

    from horovod_tpu.models import generate, llama_tp_param_specs

    cfg = dataclasses.replace(LLAMA_TINY, dtype=jnp.float32)
    model = LlamaLM(cfg)
    prompt = _ids((2, 4), seed=11)
    base = generate(model, variables, prompt, max_new_tokens=5)

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    specs = llama_tp_param_specs(variables["params"], axis="model")
    sharded = {"params": jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        variables["params"], specs)}
    with mesh:
        tp = generate(model, sharded, prompt, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tp))
