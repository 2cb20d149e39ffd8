"""ImageNet ResNet-50 training — the full data-parallel recipe.

Counterpart of the reference's ``examples/pytorch_imagenet_resnet50.py`` /
``keras_imagenet_resnet50.py``: linear learning-rate scaling with warmup,
SGD + momentum, periodic checkpoints on rank 0, resume-from-latest with
parameters broadcast (here: restored identically on every host — the SPMD
equivalent of the reference's ``broadcast_parameters`` consistency step).

Trains on synthetic ImageNet-shaped data (no network egress in this
environment), which is also how the reference's benchmark mode works; swap
``synthetic_batches`` for a real input pipeline to train on ImageNet.

    python examples/jax_imagenet_resnet50.py --steps 20
"""

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import ResNet50
from horovod_tpu.utils.checkpoint import (
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)

IMAGE_SIZE = 224
NUM_CLASSES = 1000


def synthetic_batches(batch, image_size, seed=0):
    rng = np.random.RandomState(seed)
    while True:
        x = rng.rand(batch, image_size, image_size, 3).astype(np.float32)
        y = rng.randint(0, NUM_CLASSES, size=(batch,))
        yield x, y


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-per-chip", type=int, default=64)
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--base-lr", type=float, default=0.0125,
                        help="lr per 32-image batch; scaled linearly")
    parser.add_argument("--warmup-steps", type=int, default=20)
    parser.add_argument("--checkpoint-dir", default=None)
    parser.add_argument("--checkpoint-every", type=int, default=50)
    parser.add_argument("--image-size", type=int, default=IMAGE_SIZE)
    args = parser.parse_args()
    image_size = args.image_size

    hvd.init()
    mesh = hvd.parallel.mesh()
    n = hvd.local_num_devices()
    batch = args.batch_per_chip * n

    # Reference recipe: lr scales linearly with total batch, warmed up from
    # a small value over the first epochs (pytorch_imagenet_resnet50.py).
    peak_lr = args.base_lr * batch / 32
    schedule = optax.join_schedules(
        [optax.linear_schedule(peak_lr / 10, peak_lr, args.warmup_steps),
         optax.cosine_decay_schedule(peak_lr, max(1, args.steps))],
        [args.warmup_steps])

    model = ResNet50(num_classes=NUM_CLASSES, dtype=jnp.bfloat16)
    variables = jax.jit(model.init, static_argnames="train")(
        jax.random.PRNGKey(0), jnp.ones((1, image_size, image_size, 3)),
        train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = hvd.DistributedOptimizer(
        optax.sgd(schedule, momentum=0.9), axis_name="data")
    opt_state = tx.init(params)
    start_step = 0

    if args.checkpoint_dir:
        path = latest_checkpoint(args.checkpoint_dir)
        if path:
            state = restore_checkpoint(path, like={
                "params": params, "batch_stats": batch_stats,
                "opt_state": opt_state, "step": 0})
            params, batch_stats = state["params"], state["batch_stats"]
            opt_state, start_step = state["opt_state"], int(state["step"])
            if hvd.rank() == 0:
                print(f"resumed from {path} at step {start_step}")

    def loss_fn(p, stats, xb, yb):
        logits, new_state = model.apply(
            {"params": p, "batch_stats": stats}, xb, train=True,
            mutable=["batch_stats"])
        one_hot = jax.nn.one_hot(yb, NUM_CLASSES)
        loss = optax.softmax_cross_entropy(logits, one_hot).mean()
        return loss, new_state["batch_stats"]

    def train_step(p, stats, s, xb, yb):
        (loss, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, stats, xb, yb)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), stats, s, hvd.allreduce(loss)

    step_fn = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))

    params = hvd.parallel.replicate(params, mesh)
    batch_stats = hvd.parallel.replicate(batch_stats, mesh)
    opt_state = hvd.parallel.replicate(opt_state, mesh)

    data = synthetic_batches(batch, image_size)
    t0 = time.perf_counter()
    window_start = start_step
    for step in range(start_step, args.steps):
        x, y = next(data)
        xb = hvd.parallel.shard_batch(jnp.asarray(x), mesh)
        yb = hvd.parallel.shard_batch(jnp.asarray(y), mesh)
        params, batch_stats, opt_state, loss = step_fn(
            params, batch_stats, opt_state, xb, yb)
        if (step + 1) % 10 == 0 and hvd.rank() == 0:
            dt = time.perf_counter() - t0
            n_steps = step + 1 - window_start
            print(f"step {step + 1}: loss={float(loss):.4f} "
                  f"{n_steps * batch / dt:.0f} img/sec")
            t0 = time.perf_counter()
            window_start = step + 1
        if (args.checkpoint_dir and hvd.rank() == 0
                and (step + 1) % args.checkpoint_every == 0):
            save_checkpoint(
                os.path.join(args.checkpoint_dir, f"ckpt_{step + 1}"),
                {"params": params, "batch_stats": batch_stats,
                 "opt_state": opt_state, "step": step + 1})


if __name__ == "__main__":
    main()
