"""Synthetic CNN benchmark — counterpart of the reference's
``examples/tensorflow_synthetic_benchmark.py`` (random data, reports
img/sec). Covers the reference's own benchmark-table model families
(``docs/benchmarks.md``: ResNet, Inception V3, VGG-16)."""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import VGG16, InceptionV3, ResNet50, ResNet101

# name -> (constructor, native input size)
MODELS = {
    "resnet50": (ResNet50, 224),
    "resnet101": (ResNet101, 224),
    "inception3": (InceptionV3, 299),
    "vgg16": (VGG16, 224),
}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", choices=list(MODELS), default="resnet50")
    parser.add_argument("--batch-size", type=int, default=128,
                        help="per-chip batch size")
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--num-batches", type=int, default=5)
    parser.add_argument("--image-size", type=int, default=0,
                        help="override the model's native input size")
    parser.add_argument("--fp32", action="store_true",
                        help="disable bf16 activations")
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.parallel.mesh()
    n = hvd.local_num_devices()
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    model_cls, size = MODELS[args.model]
    size = args.image_size or size
    model = model_cls(num_classes=1000, dtype=dtype)

    batch = args.batch_size * n
    x = hvd.parallel.shard_batch(
        jnp.asarray(np.random.RandomState(0).rand(batch, size, size, 3),
                    dtype=jnp.float32), mesh)
    y = hvd.parallel.shard_batch(
        jnp.asarray(np.random.RandomState(1).randint(0, 1000, batch)), mesh)

    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        jnp.ones((1, size, size, 3)), train=True)
    # VGG has no BatchNorm (stats stays an empty pytree); VGG and Inception
    # have train-time dropout (a fixed rng is fine for synthetic thruput).
    params = variables["params"]
    stats = variables.get("batch_stats", {})
    has_stats = "batch_stats" in variables
    rngs = {"dropout": jax.random.PRNGKey(2)}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                  axis_name="data")
    opt_state = tx.init(params)

    def loss_fn(p, st, xb, yb):
        if has_stats:
            logits, new_state = model.apply(
                {"params": p, "batch_stats": st}, xb, train=True,
                mutable=["batch_stats"], rngs=rngs)
            new_st = new_state["batch_stats"]
        else:
            logits = model.apply({"params": p}, xb, train=True, rngs=rngs)
            new_st = st
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, yb).mean()
        return loss, new_st

    def train_step(p, st, s, xb, yb):
        (loss, st), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, st, xb, yb)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), st, s, loss

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()), check_vma=False,
    ), donate_argnums=(0, 1, 2))

    params = hvd.parallel.replicate(params, mesh)
    stats = hvd.parallel.replicate(stats, mesh)
    opt_state = hvd.parallel.replicate(opt_state, mesh)

    if hvd.rank() == 0:
        print(f"Model: {args.model}, batch/chip: {args.batch_size}, "
              f"chips: {n}, dtype: {dtype.__name__}")

    # warmup
    params, stats, opt_state, loss = step(params, stats, opt_state, x, y)
    float(loss)

    img_secs = []
    for i in range(args.num_batches):
        t0 = time.perf_counter()
        for _ in range(args.num_iters):
            params, stats, opt_state, loss = step(
                params, stats, opt_state, x, y)
        float(loss)
        img_sec = batch * args.num_iters / (time.perf_counter() - t0)
        img_secs.append(img_sec)
        if hvd.rank() == 0:
            print(f"Iter #{i}: {img_sec:.1f} img/sec total")

    if hvd.rank() == 0:
        mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
        print(f"Img/sec per chip: {mean / n:.1f} +- {conf / n:.1f}")
        print(f"Total img/sec on {n} chip(s): {mean:.1f} +- {conf:.1f}")


if __name__ == "__main__":
    main()
