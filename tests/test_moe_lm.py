"""MoE transformer LM (models/moe_lm.py): dense and expert-parallel modes
must agree, aux losses must flow, and the model must train."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import MOE_TINY, MoeLM, causal_lm_loss
from horovod_tpu.parallel import make_mesh
from model_helpers import jit_apply, jit_init

B, S = 2, 16


def _ids(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, MOE_TINY.vocab_size, (B, S)),
        jnp.int32)


def test_moe_lm_forward_and_aux():
    model = MoeLM(MOE_TINY)
    ids = _ids()
    variables = jit_init(model, ids)
    logits, col = jit_apply(model, mutable=["aux_loss"])(
        {"params": variables["params"]}, ids)
    assert logits.shape == (B, S, MOE_TINY.vocab_size)
    aux = jax.tree.leaves(col["aux_loss"])
    # One MoE layer in the tiny config (layer 1 of 2).
    assert len(aux) == 1
    assert float(aux[0]) > 0.5  # balancing loss is ~1 at uniform routing


def test_moe_lm_expert_parallel_matches_dense():
    # f32 so the comparison is exact routing equivalence, not bf16
    # accumulation noise.
    import dataclasses
    cfg = dataclasses.replace(MOE_TINY, dtype=jnp.float32)
    ep = 4
    assert cfg.num_experts == ep
    ids = _ids(1)
    dense_model = MoeLM(cfg)
    variables = jit_init(dense_model, ids)
    dense_logits = jit_apply(dense_model)(
        {"params": variables["params"]}, ids)

    mesh = make_mesh({"expert": ep}, devices=jax.devices()[:ep])
    ep_model = MoeLM(cfg, expert_axis="expert", local_experts=1)

    def expert_spec(path, leaf):
        # Expert weights (wi/wo) carry a leading expert axis; everything
        # else is replicated.
        names = [getattr(p, "key", "") for p in path]
        if names[-1] in ("wi", "wo"):
            return P("expert")
        return P()

    params = variables["params"]
    specs = jax.tree_util.tree_map_with_path(expert_spec, params)
    f = jax.jit(jax.shard_map(
        lambda p, i: ep_model.apply({"params": p}, i),
        mesh=mesh, in_specs=(specs, P()), out_specs=P(),
        check_vma=False))
    ep_logits = f(params, ids)
    np.testing.assert_allclose(np.asarray(ep_logits),
                               np.asarray(dense_logits),
                               rtol=1e-4, atol=1e-5)


def test_moe_lm_trains():
    import optax

    model = MoeLM(MOE_TINY)
    ids = _ids(2)
    variables = jit_init(model, ids)
    params = variables["params"]
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(p, o):
        def loss_fn(p_):
            logits, col = model.apply({"params": p_}, ids,
                                      mutable=["aux_loss"])
            aux = sum(jax.tree.leaves(col["aux_loss"]))
            return causal_lm_loss(logits, ids) + 0.01 * aux

        loss, g = jax.value_and_grad(loss_fn)(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses[::10]


def test_moe_lm_flash_attention_fn():
    """The attention_fn seam (flash kernel) matches the reference path,
    same as LlamaLM's."""
    from horovod_tpu.ops.attention import make_attention_fn

    ids = _ids(3)
    ref_model = MoeLM(MOE_TINY)
    variables = jit_init(ref_model, ids)
    ref = jit_apply(ref_model)({"params": variables["params"]}, ids)
    flash_model = MoeLM(MOE_TINY, attention_fn=make_attention_fn(
        causal=True, use_flash=True, block_q=16, block_k=16))
    out = jit_apply(flash_model)({"params": variables["params"]}, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-2, rtol=5e-2)


def test_moe_remat_matches_no_remat():
    import dataclasses

    # float32: under jit the two programs fuse differently, and in
    # bfloat16 that alone moves the gradients by their rounding.
    cfg = dataclasses.replace(MOE_TINY, dtype=jnp.float32)
    ids = _ids()
    base = MoeLM(cfg)
    remat = MoeLM(dataclasses.replace(cfg, remat=True))
    variables = jit_init(base, ids)

    def loss_fn(model):
        def f(params):
            logits, col = model.apply({"params": params}, ids,
                                      mutable=["aux_loss"])
            return (causal_lm_loss(logits, ids)
                    + sum(jax.tree.leaves(col["aux_loss"])))
        return f

    # remat must preserve the math INCLUDING the sow'd aux-loss collection
    # (nn.remat lifts mutable collections through the checkpoint).
    l0, g0 = jax.jit(jax.value_and_grad(loss_fn(base)))(variables["params"])
    l1, g1 = jax.jit(jax.value_and_grad(loss_fn(remat)))(variables["params"])
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1)


def test_moe_chunked_loss_matches_full():
    from horovod_tpu.models import chunked_causal_lm_loss

    model = MoeLM(MOE_TINY)
    ids = _ids()
    variables = jit_init(model, ids)
    p = variables["params"]
    logits, _ = jit_apply(model, mutable=["aux_loss"])({"params": p}, ids)
    hidden, _ = jit_apply(model, return_hidden=True, mutable=["aux_loss"])(
        {"params": p}, ids)
    l_full = causal_lm_loss(logits, ids)
    l_chunk = jax.jit(chunked_causal_lm_loss, static_argnames="num_chunks")(
        hidden, p["lm_head"]["kernel"], ids, num_chunks=4)
    np.testing.assert_allclose(float(l_full), float(l_chunk), rtol=1e-6)


def test_moe_kv_cache_decode_matches_full_forward():
    # models.llama.generate works on MoeLM: greedy decoding through the KV
    # cache reproduces the no-cache argmax loop exactly (f32 so the two
    # einsum orders can't flip a tie; router is f32 either way). Decode
    # runs at no-drop capacity, so exact parity requires the full
    # forward's capacity not to bind either — true here (MOE_TINY at b=2:
    # capacity 2 >= the max 2 assignments/expert); under binding
    # training-config capacity the two legitimately diverge (documented
    # in MoeLM.__call__).
    import dataclasses

    from horovod_tpu.models import MOE_TINY, MoeLM, generate

    cfg = dataclasses.replace(MOE_TINY, dtype=jnp.float32)
    model = MoeLM(cfg)
    prompt = jnp.asarray(
        np.random.RandomState(9).randint(0, cfg.vocab_size, (2, 5)),
        jnp.int32)
    variables = jit_init(model, prompt)
    params = {"params": variables["params"]}

    n_new = 5
    out = generate(model, params, prompt, max_new_tokens=n_new)
    assert out.shape == (2, 5 + n_new)

    seq = prompt
    forward = jit_apply(model, mutable=["aux_loss"])    # a program a length
    for _ in range(n_new):
        logits, _ = forward(params, seq)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))
