"""Builder for ResNet image classification trained by SGD with momentum
through ``hvd.DistributedOptimizer``: the program's main path as
``bench.py``'s ``build_resnet50_step`` constructs it, with the sizes taken
from the configuration file and the mesh from the caller.

Everything here goes through the program's public API. The batch is
fixed, made from the seed and resident on the device, as in upstream's
synthetic benchmark: there is no input pipeline in these cells.
"""

from builders import training


def conv_flops_per_image(model):
    """Forward multiply-adds x 2 of every convolution and of the head,
    from the shapes alone (ResNet v1.5: stride on each stage's first 3x3).
    Batch norm, ReLU and pooling are not counted."""
    size, width = model["image_size"], model["num_filters"]
    size = (size + 1) // 2                      # 7x7 stride 2
    macs = size * size * 49 * model["channels"] * width
    size = (size + 1) // 2                      # 3x3 max-pool stride 2
    cin = width
    for stage, count in enumerate(model["stage_sizes"]):
        mid = width * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            out = (size + stride - 1) // stride
            macs += size * size * cin * mid             # 1x1
            macs += out * out * 9 * mid * mid           # 3x3, strided
            macs += out * out * mid * 4 * mid           # 1x1
            if cin != 4 * mid or stride != 1:
                macs += out * out * cin * 4 * mid       # projection
            size, cin = out, 4 * mid
    macs += cin * model["num_classes"]
    return 2.0 * macs


def train_flops_per_image(model):
    """Forward plus backward (twice the forward: one pass for the inputs'
    gradient, one for the weights')."""
    return 3.0 * conv_flops_per_image(model)


def build(config, traffic, mesh):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.resnet import ResNet

    m, opt = config["model"], config["optimizer"]
    model = ResNet(stage_sizes=m["stage_sizes"], num_classes=m["num_classes"],
                   num_filters=m["num_filters"],
                   dtype=jnp.dtype(config["compute_dtype"]))
    size = m["image_size"]
    batch = traffic["per_chip_batch"] * mesh.size
    tx = hvd.DistributedOptimizer(
        optax.sgd(opt["learning_rate"], momentum=opt["momentum"]),
        axis_name="data")

    def loss_fn(p, stats, x, y):
        logits, new_state = model.apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, new_state["batch_stats"]

    def train_step(state, data):
        p, stats, opt_state = state
        x, y = data
        (loss, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, stats, x, y)
        updates, opt_state = tx.update(grads, opt_state, p)
        return (optax.apply_updates(p, updates), stats, opt_state), loss

    # The loss stays each replica's own and batch statistics leave under
    # P() although replicas differ: bench.py's step, left as it is.
    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P("data")), out_specs=(P(), P()),
        check_vma=False), donate_argnums=(0,))

    weight_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, size, size, m["channels"])),
                           train=True))

    def make_batch(rng):
        # Cast on the host, so that shard_batch moves each shard straight
        # to its own device. Every row differs.
        images = rng.integers(0, 256, (batch, size, size, m["channels"]),
                              dtype=np.uint8)
        images = (images.astype(np.float32) / 255.0).astype(jnp.bfloat16)
        labels = rng.integers(0, m["num_classes"], (batch,), dtype=np.int32)
        return images, labels

    return training.Workbench(
        step=step,
        weight_shapes=weight_shapes,
        init_state=lambda w: (w["params"], w["batch_stats"],
                              tx.init(w["params"])),
        weight_params=lambda w: w["params"],
        params_of=lambda state: state[0],
        # optax.sgd's trace after one step from zero is the gradient itself.
        first_gradient=lambda state: state[2][0].trace,
        identical_of=lambda state: (state[0], state[2]),
        batch_shapes=(
            jax.ShapeDtypeStruct((batch, size, size, m["channels"]),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct((batch,), jnp.int32)),
        make_batch=make_batch,
        samples_per_step=batch,
        flops_per_step=train_flops_per_image(m) * batch,
        state_shardings=NamedSharding(mesh, P()),
        batch_shardings=NamedSharding(mesh, P("data")),
    )


def run(ctx):
    return training.run(ctx, build)
