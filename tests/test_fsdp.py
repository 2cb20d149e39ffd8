"""ZeRO-3 / FSDP parameter+gradient sharding (jax/fsdp.py): spec
selection, structural state-spec matching, per-device memory, and
end-to-end training parity against the unsharded twin (the BASELINE
Llama-8B FSDP workload pattern at toy scale)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.jax.fsdp import (
    fsdp_param_specs,
    fsdp_shardings,
    fsdp_state_specs,
    sharded_size_bytes,
)
from horovod_tpu.models.llama import (
    LLAMA_TINY,
    LlamaLM,
    llama_tp_param_specs,
)
from horovod_tpu.models.losses import causal_lm_loss
from horovod_tpu.parallel import make_mesh

N_DEV = 8


def test_param_specs_pick_largest_free_divisible_dim():
    params = {
        "w": jnp.zeros((16, 64, 24)),     # 64 largest divisible by 8
        "embed": jnp.zeros((512, 48)),    # 512 largest
        "odd": jnp.zeros((30, 42)),       # nothing divisible by 8
        "scale": jnp.zeros((64,)),        # below min_leaf_elems
    }
    specs = fsdp_param_specs(params, num_shards=N_DEV, min_leaf_elems=1)
    assert specs["w"] == P(None, "data", None)
    assert specs["embed"] == P("data", None)
    assert specs["odd"] == P()
    # 64 elems < min_leaf_elems=1? no — with threshold 1 it shards.
    assert specs["scale"] == P("data")
    specs = fsdp_param_specs(params, num_shards=N_DEV, min_leaf_elems=128)
    assert specs["scale"] == P()


def test_param_specs_compose_with_tp_base():
    model = LlamaLM(LLAMA_TINY)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32)))["params"]
    tp = llama_tp_param_specs(params, axis="model")
    specs = fsdp_param_specs(params, num_shards=2, axis="data",
                             base_specs=tp, min_leaf_elems=1)
    wq = specs["layer_0"]["attention"]["wq"]["kernel"]
    # TP claimed the heads axis; FSDP takes the (largest) free dim.
    assert wq == P("data", "model", None)
    lm = specs["lm_head"]["kernel"]
    assert lm == P("data", "model")

    with pytest.raises(ValueError, match="already uses axis"):
        fsdp_param_specs(params, num_shards=2, axis="model", base_specs=tp)


def test_param_specs_accept_none_as_replicated_base():
    """``None`` is the common "replicated" idiom in user spec trees (jit
    accepts it); tree.map treats None as an empty subtree, so both
    fsdp_param_specs and fsdp_shardings must normalize rather than raise
    a structure mismatch."""
    params = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((16,))}
    base = {"w": P(None, "model"), "b": None}
    specs = fsdp_param_specs(params, num_shards=N_DEV, base_specs=base,
                             min_leaf_elems=1)
    assert specs["w"] == P("data", "model")
    assert specs["b"] == P("data")  # None base composed, dim 16 % 8 == 0

    mesh = make_mesh({"data": 4, "model": 2})
    sh = fsdp_shardings(mesh, {"w": P("data", None), "b": None})
    assert sh["b"].spec == P()
    assert sh["w"].spec == P("data", None)


def test_state_specs_structural_match():
    params = {
        "w": jnp.zeros((64, 16)),
        "nested": {"w": jnp.zeros((32, 8))},  # same leaf NAME, other path
    }
    specs = fsdp_param_specs(params, num_shards=N_DEV, min_leaf_elems=1)
    tx = optax.adamw(1e-3)
    sspecs = fsdp_state_specs(tx, params, specs)
    leaves = jax.tree_util.tree_leaves_with_path(
        sspecs, is_leaf=lambda s: isinstance(s, P))
    # Adam mu/nu leaves mirror their param's spec; count is replicated.
    by_str = {jax.tree_util.keystr(p): s for p, s in leaves}
    mu_w = [s for k, s in by_str.items() if "mu" in k and "nested" not in k]
    assert mu_w == [P("data", None)]
    mu_nested = [s for k, s in by_str.items()
                 if "mu" in k and "nested" in k]
    assert mu_nested == [P("data", None)]
    counts = [s for k, s in by_str.items() if "count" in k]
    assert counts and all(s == P() for s in counts)


def test_state_specs_adafactor_factored_moments_replicate():
    params = {"w": jnp.zeros((256, 512))}
    specs = fsdp_param_specs(params, num_shards=N_DEV, min_leaf_elems=1)
    sspecs = fsdp_state_specs(
        optax.adafactor(1e-3), params, specs)
    # Factored row/col moments match no param shape -> replicated (small).
    flat = jax.tree_util.tree_leaves(
        sspecs, is_leaf=lambda s: isinstance(s, P))
    assert P() in flat


def test_state_specs_refuses_large_unmatched_leaf():
    params = {"w": jnp.zeros((256, 512))}
    specs = fsdp_param_specs(params, num_shards=N_DEV, min_leaf_elems=1)

    big = jnp.zeros((4096, 4096))  # 16M elems, matches no param

    def init(p):
        return {"table": big, "inner": optax.adam(1e-3).init(p)}

    tx = optax.GradientTransformation(init, lambda u, s, p=None: (u, s))
    with pytest.raises(ValueError, match="matches no parameter"):
        fsdp_state_specs(tx, params, specs)


def _llama_setup():
    cfg = LLAMA_TINY
    model = LlamaLM(cfg)
    rng = np.random.RandomState(0)
    batch, seq = N_DEV, 32
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids[:1])["params"]
    return model, params, ids


def test_fsdp_training_parity_and_memory():
    """The heart of the feature: an FSDP-sharded Llama training step on 8
    devices matches the single-device step (loss + updated params), while
    each device holds ~1/8 of params and Adam moments."""
    model, params, ids = _llama_setup()
    mesh = make_mesh({"data": N_DEV})
    # SGD+momentum: elementwise param parity is well-conditioned (Adam's
    # first-step update is lr*sign(g), which flips on reduce-order noise
    # where g ~ 0); the momentum trace still exercises state sharding.
    tx = optax.sgd(1e-2, momentum=0.9)

    specs = fsdp_param_specs(params, num_shards=N_DEV, min_leaf_elems=1024)
    sspecs = fsdp_state_specs(tx, params, specs)
    psh = fsdp_shardings(mesh, specs)
    ssh = fsdp_shardings(mesh, sspecs)

    def loss_fn(p, ids):
        return causal_lm_loss(
            model.apply({"params": p}, ids), ids)

    def step(p, s, ids):
        loss, g = jax.value_and_grad(loss_fn)(p, ids)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    # Sharded: params/state FSDP-placed, batch over data, shardings pinned.
    p_sh = jax.device_put(params, psh)
    s_sh = jax.jit(tx.init, out_shardings=ssh)(p_sh)
    from jax.sharding import NamedSharding
    data_sh = NamedSharding(mesh, P("data"))
    step_sh = jax.jit(step, out_shardings=(psh, ssh, None))

    # Memory: a sharded leaf's per-device shard is 1/N of the full leaf.
    wq = p_sh["layer_0"]["attention"]["wq"]["kernel"]
    assert wq.addressable_shards[0].data.size * N_DEV == wq.size
    trace_wq = s_sh[0].trace["layer_0"]["attention"]["wq"]["kernel"]
    assert trace_wq.addressable_shards[0].data.size * N_DEV == trace_wq.size
    # And the budget arithmetic agrees with the real placement.
    assert sharded_size_bytes(params, specs, dict(mesh.shape)) == sum(
        x.addressable_shards[0].data.nbytes
        for x in jax.tree.leaves(p_sh))

    # Single-device twin.
    s_ref = tx.init(params)
    step_ref = jax.jit(step)

    p2_sh, s2_sh, loss_sh = step_sh(p_sh, s_sh,
                                    jax.device_put(ids, data_sh))
    p2, s2, loss = step_ref(params, s_ref, ids)
    np.testing.assert_allclose(float(loss_sh), float(loss),
                               rtol=2e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(p2_sh), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_fsdp_dp_tp_hybrid_trains():
    """dp×tp: TP specs on the model axis + FSDP over the data axis."""
    model, params, ids = _llama_setup()
    mesh = make_mesh({"data": 4, "model": 2})
    tx = optax.adam(1e-2)
    tp = llama_tp_param_specs(params, axis="model")
    specs = fsdp_param_specs(params, num_shards=4, axis="data",
                             base_specs=tp, min_leaf_elems=1024)
    sspecs = fsdp_state_specs(tx, params, specs)
    psh = fsdp_shardings(mesh, specs)
    ssh = fsdp_shardings(mesh, sspecs)

    def loss_fn(p, ids):
        return causal_lm_loss(model.apply({"params": p}, ids), ids)

    def step(p, s, ids):
        loss, g = jax.value_and_grad(loss_fn)(p, ids)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    from jax.sharding import NamedSharding
    p_sh = jax.device_put(params, psh)
    s_sh = jax.jit(tx.init, out_shardings=ssh)(p_sh)
    step_j = jax.jit(step, out_shardings=(psh, ssh, None))
    _, _, loss_sh = step_j(p_sh, s_sh,
                           jax.device_put(ids, NamedSharding(mesh,
                                                             P("data"))))
    _, _, loss = jax.jit(step)(params, tx.init(params), ids)
    # TP splits the bf16 contractions across the model axis (psum partials
    # reduce in a different order than the single-device matmul), so the
    # bar is bf16 noise — unlike pure FSDP, which recomputes identical
    # local matmuls after the all-gather and matches at f32 tolerance.
    np.testing.assert_allclose(float(loss_sh), float(loss),
                               rtol=1e-3)
