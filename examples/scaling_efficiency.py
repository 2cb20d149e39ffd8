"""Scaling-efficiency harness: throughput at mesh sizes 1..N on one host.

The reference's headline numbers are scaling efficiencies (90% for
Inception V3 / ResNet-101, 68% for VGG-16 at 512 GPUs — reference
``docs/benchmarks.md:5-6``); BASELINE.md tracks the same metric for the
rebuild. This harness measures it the same way the reference's benchmark
does: train the model data-parallel at world sizes 1, 2, 4, ..., N with a
fixed per-chip batch, and report rate(N) / (N * rate(1)).

Hermetic by default (virtual CPU devices, small MLP); on a pod slice run it
with the real mesh and --model resnet50:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/scaling_efficiency.py --model mlp --steps 20
"""

import argparse
import json
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", choices=["mlp", "resnet50"], default="mlp")
    parser.add_argument("--batch-per-chip", type=int, default=64)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    devices = jax.devices()
    if devices[0].platform == "cpu" and len(devices) > 1:
        print("note: virtual CPU devices share host cores — efficiency "
              "numbers are only meaningful on real chips")

    if args.model == "mlp":
        from horovod_tpu.models import MnistMLP

        model = MnistMLP(features=(1024, 1024))
        sample = jnp.ones((1, 28, 28))
        make_batch = lambda b, rng: (  # noqa: E731
            jnp.asarray(rng.rand(b, 28, 28), jnp.float32),
            jnp.asarray(rng.randint(0, 10, b), jnp.int32))
    else:
        from horovod_tpu.models import ResNet50

        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        sample = jnp.ones((1, 224, 224, 3))
        make_batch = lambda b, rng: (  # noqa: E731
            jnp.asarray(rng.rand(b, 224, 224, 3), jnp.float32),
            jnp.asarray(rng.randint(0, 1000, b), jnp.int32))

    def measure(n):
        mesh = hvd.parallel.make_mesh(devices=devices[:n])
        init = jax.jit(model.init, static_argnames="train")
        variables = init(jax.random.PRNGKey(0), sample, train=True) \
            if args.model == "resnet50" \
            else init(jax.random.PRNGKey(0), sample)
        tx = hvd.DistributedOptimizer(
            optax.sgd(0.01, momentum=0.9), axis_name="data")

        if args.model == "resnet50":
            params, stats = variables["params"], variables["batch_stats"]

            def loss_fn(p, st, xb, yb):
                logits, new = model.apply(
                    {"params": p, "batch_stats": st}, xb, train=True,
                    mutable=["batch_stats"])
                return optax.softmax_cross_entropy(
                    logits, jax.nn.one_hot(yb, 1000)).mean(), \
                    new["batch_stats"]

            def train_step(p, st, s, xb, yb):
                (l, st), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    p, st, xb, yb)
                u, s = tx.update(g, s, p)
                return optax.apply_updates(p, u), st, s, l

            state = (params, stats, tx.init(params))
            in_specs = (P(), P(), P(), P("data"), P("data"))
            out_specs = (P(), P(), P(), P())
        else:
            params = variables

            def loss_fn(p, xb, yb):
                return optax.softmax_cross_entropy_with_integer_labels(
                    model.apply(p, xb), yb).mean()

            def train_step(p, s, xb, yb):
                l, g = jax.value_and_grad(loss_fn)(p, xb, yb)
                u, s = tx.update(g, s, p)
                return optax.apply_updates(p, u), s, l

            state = (params, tx.init(params))
            in_specs = (P(),) * 2 + (P("data"), P("data"))
            out_specs = (P(),) * 3

        step = jax.jit(jax.shard_map(
            train_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

        b = args.batch_per_chip * n
        xb, yb = make_batch(b, np.random.RandomState(0))
        xb = hvd.parallel.shard_batch(xb, mesh)
        yb = hvd.parallel.shard_batch(yb, mesh)
        state = hvd.parallel.replicate(state, mesh)

        for _ in range(args.warmup):
            out = step(*state, xb, yb)
            state, _ = out[:-1], out[-1]
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = step(*state, xb, yb)
            state, loss = out[:-1], out[-1]
            # Host fetch of the loss as the per-step sync barrier.
            jax.device_get(loss)
        dt = time.perf_counter() - t0
        return b * args.steps / dt

    sizes = []
    n = 1
    while n <= len(devices):
        sizes.append(n)
        n *= 2
    if sizes[-1] != len(devices):
        sizes.append(len(devices))

    rates = {}
    for n in sizes:
        rates[n] = measure(n)
        print(f"n={n}: {rates[n]:.1f} img/sec "
              f"({rates[n] / n:.1f}/chip)")

    base = rates[sizes[0]]
    efficiency = {n: rates[n] / (n * base) for n in sizes}
    for n in sizes:
        print(f"scaling efficiency @{n}: {100 * efficiency[n]:.1f}%")
    print(json.dumps({
        "metric": "scaling_efficiency",
        "model": args.model,
        "sizes": sizes,
        "img_sec": {str(k): round(v, 1) for k, v in rates.items()},
        "efficiency": {str(k): round(v, 4) for k, v in efficiency.items()},
    }))


if __name__ == "__main__":
    main()
