"""DistributedOptimizer / broadcast_parameters semantics.

Reference analogue: gradient-correctness tests in ``test/test_torch.py``
(grad vs manual) and the mnist example smoke runs (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import make_mesh, mesh, replicate, shard_batch

N = 8


def _loss_fn(params, x, y):
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


# The main path as a table: ``hvd.DistributedOptimizer`` inside
# ``jax.jit(jax.shard_map(...))`` on 4 virtual CPU devices against one
# device on the global batch. ``tolerance`` is the largest gap allowed
# between the two, as a part of the largest change a parameter made in
# the two steps: float32 sums in another order without compression, a
# gradient rounded to 11 (fp16) or 8 (bf16) significand bits before it is
# summed over 4 devices with it, with margin for Adam's division.
N_DEV = 4
TOLERANCE = {"none": 1e-5, "fp16": 4e-3, "bf16": 2e-2}
OPTIMIZERS = {
    "sgd_momentum": lambda: optax.sgd(0.1, momentum=0.9),
    "adamw": lambda: optax.adamw(1e-2),
}


def _mlp_loss(params, x, y):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return jnp.mean((h @ params["w2"] + params["b2"] - y) ** 2)


def _device_copies(leaf):
    return [np.asarray(s.data).tobytes() for s in leaf.addressable_shards]


@pytest.mark.parametrize("passes", [1, 2])
@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
@pytest.mark.parametrize("compression", sorted(TOLERANCE))
@pytest.mark.parametrize("average", [True, False])
def test_distributed_optimizer_matches_single_device(average, compression,
                                                     optimizer, passes):
    hvd.init()
    m = make_mesh({"data": N_DEV}, devices=jax.devices()[:N_DEV])
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {"w1": jax.random.normal(keys[0], (8, 16)) * 0.5,
              "b1": jnp.zeros((16,)),
              "w2": jax.random.normal(keys[1], (16, 2)) * 0.5,
              "b2": jnp.zeros((2,))}
    # One global batch a backward pass, two applied steps.
    xs = jax.random.normal(keys[2], (2 * passes, N_DEV * 4, 8))
    ys = jax.random.normal(keys[3], (2 * passes, N_DEV * 4, 2))

    tx = hvd.DistributedOptimizer(
        OPTIMIZERS[optimizer](), axis_name="data", average=average,
        compression=getattr(hvd.Compression, compression),
        backward_passes_per_step=passes)

    def train_step(p, opt_state, x, y):
        grads = jax.grad(_mlp_loss)(p, x, y)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    sharded_step = jax.jit(jax.shard_map(
        train_step, mesh=m,
        in_specs=(P(), P(), P("data"), P("data")), out_specs=(P(), P()),
        check_vma=False))

    # One device: the mean of the shards' gradients is the gradient on the
    # global batch, their sum N_DEV times it.
    base_tx = OPTIMIZERS[optimizer]()
    if passes > 1:
        base_tx = optax.MultiSteps(base_tx, every_k_schedule=passes)
    scale = 1.0 if average else float(N_DEV)

    @jax.jit
    def base_step(p, opt_state, x, y):
        grads = jax.tree.map(lambda g: g * scale,
                             jax.grad(_mlp_loss)(p, x, y))
        updates, opt_state = base_tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    got = replicate((params, tx.init(params)), m)
    want = (params, base_tx.init(params))
    for x, y in zip(xs, ys):
        got = sharded_step(*got, shard_batch(x, m), shard_batch(y, m))
        want = base_step(*want, x, y)

    for name in params:
        moved = np.abs(np.asarray(want[0][name] - params[name])).max()
        gap = np.abs(np.asarray(got[0][name]) - np.asarray(want[0][name]))
        assert gap.max() <= TOLERANCE[compression] * moved, name
    # What ``resnet50-dp4`` holds on the chip with limit 0: every device's
    # copy of the parameters and of the optimizer's state, bit for bit.
    got_params, got_state = got
    if passes > 1:
        # Between applied steps ``optax.MultiSteps`` accumulates each
        # device's own gradients; after one it empties the accumulator by
        # a product, which leaves zeros of either sign.
        for leaf in jax.tree.leaves(got_state.acc_grads):
            assert all(not np.asarray(s.data).any()
                       for s in leaf.addressable_shards)
        got_state = got_state._replace(acc_grads=None)
    for leaf in jax.tree.leaves((got_params, got_state)):
        copies = _device_copies(leaf)
        assert len(copies) == N_DEV and len(set(copies)) == 1


def test_distributed_value_and_grad():
    hvd.init()
    x = jnp.arange(N * 2 * 3, dtype=jnp.float32).reshape(N * 2, 3)
    y = jnp.ones((N * 2, 1))
    params = {"w": jnp.ones((3, 1)), "b": jnp.zeros((1,))}

    dvag = hvd.distributed_value_and_grad(_loss_fn, axis_name="data")
    m = mesh()
    f = jax.jit(
        jax.shard_map(
            dvag, mesh=m,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    _, grads = f(params, x, y)
    full_grads = jax.grad(_loss_fn)(params, x, y)
    np.testing.assert_allclose(
        np.asarray(grads["w"]), np.asarray(full_grads["w"]), rtol=1e-5
    )


def test_backward_passes_per_step():
    hvd.init()
    tx = hvd.DistributedOptimizer(
        optax.sgd(1.0), backward_passes_per_step=2, axis_name="data"
    )
    params = {"w": jnp.ones(2)}
    state = tx.init(params)
    g = {"w": jnp.ones(2)}
    # First micro-step accumulates; update is zero.
    u1, state = tx.update(g, state, params)
    assert np.allclose(np.asarray(u1["w"]), 0.0)
    # Second micro-step applies the averaged accumulated gradient.
    u2, state = tx.update(g, state, params)
    assert not np.allclose(np.asarray(u2["w"]), 0.0)


def test_broadcast_parameters_single():
    hvd.init()
    params = {"w": jnp.ones(3), "nested": {"b": jnp.zeros(2)}}
    out = hvd.broadcast_parameters(params, root_rank=0)
    assert out is params  # size-1 no-op
    opt_out = hvd.broadcast_optimizer_state(params, root_rank=0)
    assert opt_out is params


def test_distributed_optimizer_compression_in_jit():
    """Under jit, Compression.bf16 casts the gradient before the psum (the
    collective moves bf16) and restores f32 afterwards."""
    hvd.init()
    mesh = make_mesh({"data": 8})
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="data",
                                  compression=hvd.Compression.bf16)
    params = {"w": jnp.ones((4,), jnp.float32)}
    opt_state = tx.init(params)

    def step(p, o, g):
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o

    f = jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P()),
        check_vma=False)
    grads = {"w": jnp.full((4,), 2.0, jnp.float32)}
    jaxpr = str(jax.make_jaxpr(f)(params, opt_state, grads))
    # The collective's operand must be bf16 (cast fused into the psum).
    assert "bf16[4]" in jaxpr, jaxpr[:2000]

    p2, _ = jax.jit(f)(params, opt_state, grads)
    # Result back in f32, numerically the plain SGD step.
    assert p2["w"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(p2["w"]), 1.0 - 0.1 * 2.0,
                               rtol=1e-2)
    hvd.shutdown()


def test_compression_skipped_on_unbound_axis():
    """Plain jit (pjit-style identity fallback): the bf16 round-trip would
    truncate gradients for zero wire savings, so it must not happen."""
    hvd.init()
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="data",
                                  compression=hvd.Compression.bf16)
    p = {"w": jnp.ones((4,), jnp.float32)}
    o = tx.init(p)
    g = {"w": jnp.full((4,), 1.0000001, jnp.float32)}
    u, _ = jax.jit(lambda g, o, p: tx.update(g, o, p))(g, o, p)
    got = float(np.asarray(u["w"])[0])
    full = float(np.float32(-0.1) * np.float32(1.0000001))
    # bf16 would collapse 1.0000001 -> 1.0 and yield exactly -0.1.
    assert abs(got - full) < 1e-9, got
    hvd.shutdown()


def test_grouped_allreduce_traced_and_size1():
    hvd.init()
    # Size-1 eager: identity values, fresh arrays, order preserved.
    outs = hvd.grouped_allreduce([np.ones(3, np.float32),
                                  np.arange(4, dtype=np.float32)],
                                 average=True)
    np.testing.assert_array_equal(np.asarray(outs[0]), np.ones(3))
    np.testing.assert_array_equal(np.asarray(outs[1]), np.arange(4))

    # Traced tier: tree of psums over the mesh axis.
    from horovod_tpu.parallel import make_mesh

    m = make_mesh({"data": jax.device_count()})

    def body(xs):
        return hvd.grouped_allreduce(list(xs), average=False,
                                     axis_name="data")

    f = jax.jit(jax.shard_map(
        body, mesh=m, in_specs=(P("data"),), out_specs=P(),
        check_vma=False))
    n = jax.device_count()
    xs = (jnp.ones((n, 2)), jnp.arange(float(n))[:, None])
    got = f(xs)  # per-device (1, k) shards psum'd over the axis
    np.testing.assert_allclose(np.asarray(got[0]).ravel(), np.full(2, n))
    np.testing.assert_allclose(np.asarray(got[1]).ravel(),
                               [sum(range(n))])

    import pytest

    with pytest.raises(TypeError, match="list/tuple"):
        hvd.grouped_allreduce(np.ones(3))
