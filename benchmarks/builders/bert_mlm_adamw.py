"""Builder for BERT masked-LM pre-training by AdamW through
``hvd.DistributedOptimizer``: the step of
``examples/jax_bert_pretraining.py`` with the sizes taken from the
configuration file, the sequence length and batch from the traffic file
and the mesh from the caller. Attention goes through the program's own
rule (``make_attention_fn()``: Pallas flash at sequence 512 and above,
XLA softmax below).

The batch is fixed, made from the seed and resident on the device: token
ids uniform over the vocabulary, 15% of positions masked (their input
replaced by ``[MASK]``) and scored. There is no input pipeline.
"""

from builders import training

MASK_TOKEN = 103    # [MASK] in the bert-base-uncased vocabulary


def train_flops_per_step(model, batch, seq):
    """Forward plus backward FLOPs of one step, from shapes: 6 x tokens x
    matmul parameters (encoder and head; embeddings are gathers) plus the
    attention scores and context, 6 x 2 x layers x B x S^2 x hidden. A
    copy of ``examples/bert_phase_profile.train_flops_per_step``."""
    h, layers = model["hidden_size"], model["num_hidden_layers"]
    per_layer = 4 * h * h + 2 * h * model["intermediate_size"]
    matmul_params = layers * per_layer + h * model["vocab_size"]
    dense = 6.0 * batch * seq * matmul_params
    attention = 6.0 * 2.0 * layers * batch * seq * seq * h
    return dense + attention


def build(config, traffic, mesh):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.bert import BertConfig, BertEncoder, mlm_loss
    from horovod_tpu.ops.attention import make_attention_fn

    m, opt = config["model"], config["optimizer"]
    cfg = BertConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        intermediate_size=m["intermediate_size"],
        max_position_embeddings=m["max_position_embeddings"],
        type_vocab_size=m["type_vocab_size"],
        dtype=jnp.dtype(config["compute_dtype"]), remat=False)
    model = BertEncoder(cfg, attention_fn=make_attention_fn())
    seq = traffic["sequence_length"]
    batch = traffic["per_chip_batch"] * mesh.size
    tx = hvd.DistributedOptimizer(
        optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                    eps=opt["eps"], weight_decay=opt["weight_decay"]),
        axis_name="data")

    def loss_fn(p, ids, labels, mask):
        logits = model.apply({"params": p}, ids, deterministic=True)
        return mlm_loss(logits, labels, mask)

    def train_step(state, data):
        p, opt_state = state
        loss, grads = jax.value_and_grad(loss_fn)(p, *data)
        updates, opt_state = tx.update(grads, opt_state, p)
        return (optax.apply_updates(p, updates), opt_state), \
            hvd.allreduce(loss)

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P("data")), out_specs=(P(), P()),
        check_vma=False), donate_argnums=(0,))

    weight_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, seq), jnp.int32),
                           deterministic=True)["params"])

    def make_batch(rng):
        labels = rng.integers(0, m["vocab_size"], (batch, seq),
                              dtype=np.int32)
        mask = rng.random((batch, seq)) < config["mlm_probability"]
        ids = np.where(mask, np.int32(MASK_TOKEN), labels)
        return ids, labels, mask

    return training.Workbench(
        step=step,
        weight_shapes=weight_shapes,
        init_state=lambda w: (w, tx.init(w)),
        weight_params=lambda w: w,
        params_of=lambda state: state[0],
        # Adam's first moment after one step from zero is (1 - b1) x the
        # gradient the optimizer got.
        first_gradient=lambda state: jax.tree.map(
            lambda mu: mu / (1.0 - opt["b1"]), state[1][0].mu),
        identical_of=lambda state: state,
        batch_shapes=(jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                      jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                      jax.ShapeDtypeStruct((batch, seq), jnp.bool_)),
        make_batch=make_batch,
        samples_per_step=batch,
        flops_per_step=train_flops_per_step(m, batch, seq),
        state_shardings=NamedSharding(mesh, P()),
        batch_shardings=NamedSharding(mesh, P("data")),
    )


def run(ctx):
    return training.run(ctx, build)
