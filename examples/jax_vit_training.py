"""Vision Transformer training — data-parallel, synthetic ImageNet shapes.

Extends the reference's CNN benchmark family (`docs/benchmarks.md`) with the
transformer vision architecture; same DP recipe as
``jax_imagenet_resnet50.py`` (linear lr scaling + warmup, AdamW as is
conventional for ViT), same measurement style as the language examples
(donated-chain timing, device fetch as the barrier).

    python examples/jax_vit_training.py --model s16 --batch-per-chip 64
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import (
    VIT_B16,
    VIT_S16,
    VIT_TINY,
    VisionTransformer,
    classification_loss,
)

CONFIGS = {"b16": VIT_B16, "s16": VIT_S16, "tiny": VIT_TINY}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", choices=sorted(CONFIGS), default="s16")
    parser.add_argument("--batch-per-chip", type=int, default=64)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--warmup-steps", type=int, default=5,
                        help="steps excluded from throughput timing")
    parser.add_argument("--base-lr", type=float, default=1e-3)
    parser.add_argument("--remat", action="store_true")
    parser.add_argument("--attention", choices=["xla", "flash"],
                        default="xla",
                        help="attention core: plain XLA softmax (default; "
                        "wins at ViT's s=197 per the round-5 phase probe) "
                        "or the streaming flash kernel (auto-pads 197→256)")
    def _positive(v):
        v = int(v)
        if v < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return v

    parser.add_argument("--steps-per-call", type=_positive, default=1,
                        help="train steps fused into one dispatched "
                        "program via lax.scan — amortizes the host's "
                        "per-dispatch latency on small-step models")
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.parallel.mesh()
    n = hvd.local_num_devices()
    batch = args.batch_per_chip * n

    import dataclasses

    cfg = CONFIGS[args.model]
    if args.remat:
        cfg = dataclasses.replace(cfg, remat=True)
    attention_fn = None
    if args.attention == "flash":
        from horovod_tpu.ops.attention import make_attention_fn

        attention_fn = make_attention_fn(causal=False, use_flash=True)
    model = VisionTransformer(cfg, attention_fn=attention_fn)

    rng = np.random.RandomState(hvd.rank())
    lead = ((args.steps_per_call, batch) if args.steps_per_call > 1
            else (batch,))
    x = jnp.asarray(rng.rand(
        *lead, cfg.image_size, cfg.image_size, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, cfg.num_classes, size=lead))

    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.ones((1, cfg.image_size, cfg.image_size, 3)),
        deterministic=True)
    # Warmup counts OPTIMIZER steps: with a device-side step loop each
    # dispatched call advances steps_per_call of them.
    lr = optax.linear_schedule(args.base_lr / 10, args.base_lr * n,
                               args.warmup_steps * args.steps_per_call)
    tx = hvd.DistributedOptimizer(optax.adamw(lr), axis_name="data")
    opt_state = tx.init(variables)

    def train_step(v, s, xb, yb):
        def loss_fn(vv):
            return classification_loss(
                model.apply(vv, xb, deterministic=True), yb)

        loss, grads = jax.value_and_grad(loss_fn)(v)
        updates, s = tx.update(grads, s, v)
        return optax.apply_updates(v, updates), s, hvd.allreduce(loss)

    if args.steps_per_call > 1:
        inner = train_step

        def train_step(v, s, xb, yb):  # noqa: F811 — deliberate rebind
            # Device-side data loop: ONE dispatched program consumes K
            # stacked batches (xb/yb carry a leading K axis), the way a
            # prefetching input pipeline feeds a device loop: the
            # per-dispatch host latency is paid once per K steps (its
            # share of a ViT-S step is not measured on the current code).
            def body(carry, batch):
                v, s, loss = inner(*carry, *batch)
                return (v, s), loss

            (v, s), losses = jax.lax.scan(body, (v, s), (xb, yb))
            return v, s, losses[-1]

    batch_spec = (P(None, "data") if args.steps_per_call > 1
                  else P("data"))
    step_fn = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), batch_spec, batch_spec),
        out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))

    variables = hvd.parallel.replicate(variables, mesh)
    opt_state = hvd.parallel.replicate(opt_state, mesh)
    if args.steps_per_call > 1:
        # Stacked batches: leading axis is the device-side step loop,
        # axis 1 is the data-parallel batch.
        from jax.sharding import NamedSharding

        sh = NamedSharding(mesh, P(None, "data"))
        xb, yb = jax.device_put(x, sh), jax.device_put(y, sh)
    else:
        xb = hvd.parallel.shard_batch(x, mesh)
        yb = hvd.parallel.shard_batch(y, mesh)

    loss = None
    for _ in range(args.warmup_steps):
        variables, opt_state, loss = step_fn(variables, opt_state, xb, yb)
    # Device->host value fetch as the barrier (block_until_ready is one too
    # on this runtime — chip_smoke.py times both; the scalar is wanted on
    # the host anyway). (--warmup-steps 0 leaves loss None: nothing to
    # fence, compile time then lands inside the timed region by the user's
    # choice.)
    if loss is not None:
        float(loss)

    t0 = time.perf_counter()
    timed = max(1, args.steps - args.warmup_steps)
    for _ in range(timed):
        variables, opt_state, loss = step_fn(variables, opt_state, xb, yb)
    float(loss)
    dt = time.perf_counter() - t0

    if hvd.rank() == 0:
        img_sec = timed * args.steps_per_call * batch / dt
        print(f"vit-{args.model} {cfg.image_size}px: {img_sec:.0f} img/sec "
              f"({img_sec / n:.0f}/chip), loss={float(loss):.3f}")


if __name__ == "__main__":
    main()
