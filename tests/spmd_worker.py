"""Worker for the SPMD multi-host test: launched by ``horovodrun --spmd``,
joins the JAX distributed runtime through ``hvd.init()``, and trains one
data-parallel step over the *global* mesh (2 processes x 2 virtual CPU
devices = 4-way data parallelism)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main():
    hvd.init()
    assert hvd.size() == 2, hvd.size()
    assert jax.process_count() == 2, jax.process_count()
    # The mesh is global: both processes' devices.
    assert jax.device_count() == 4, jax.device_count()
    mesh = hvd.parallel.mesh()
    assert mesh.devices.size == 4, mesh.devices

    rng = np.random.RandomState(0)
    X = jnp.asarray(rng.rand(64, 4), jnp.float32)
    Y = X @ jnp.asarray([[1.0], [-2.0], [3.0], [0.5]])
    params = {"w": jnp.zeros((4, 1))}
    tx = hvd.DistributedOptimizer(optax.adam(0.05), axis_name="data")
    s = tx.init(params)

    def loss_fn(p, x, y):
        return ((x @ p["w"] - y) ** 2).mean()

    def step(p, s, x, y):
        l, g = jax.value_and_grad(loss_fn)(p, x, y)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, hvd.allreduce(l)

    f = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P()), check_vma=False))

    xs = hvd.parallel.shard_batch(X, mesh)
    ys = hvd.parallel.shard_batch(Y, mesh)
    params = hvd.parallel.replicate(params, mesh)
    s = hvd.parallel.replicate(s, mesh)
    for _ in range(60):
        params, s, loss = f(params, s, xs, ys)
        jax.block_until_ready(loss)
    # loss is replicated (out_specs=P()); read this process's copy.
    loss_val = float(np.asarray(loss.addressable_shards[0].data).ravel()[0])
    assert np.isfinite(loss_val), loss_val
    print(f"rank {hvd.rank()}: spmd multihost loss={loss_val:.6f} "
          f"devices={jax.device_count()} OK")


if __name__ == "__main__":
    main()
