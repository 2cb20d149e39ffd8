"""Builder for Ouro next-token training by AdamW through
``hvd.DistributedOptimizer``: ``horovod_tpu.models.OuroLM`` with the sizes
of the configuration file (one stage of an eight-stage circular pipeline:
the layers it holds, run ``total_ut_steps`` times, with the embedding,
the final norm, the gate and the whole head), the sequence length and
batch of the traffic file and the mesh of the caller. Attention goes
through the program's own rule (``make_attention_fn(causal=True)``: the
flash kernels at sequence 512 and above, streamed past one block, here
16 heads of width 128 with no grouping), the loss through
``ouro_lm_loss``: every pass's exit through the one head as one sweep of
``weighted_chunked_causal_lm_loss`` under the exit distribution, less the
entropy term; each application of a block recomputed in the backward
pass.

The batch is fixed, made from the seed and resident on the device: token
ids uniform over the vocabulary, unbroken sequences. There is no input
pipeline. The step's state carries, beside parameters and AdamW's
moments, the mean exit distribution and its mean entropy
(``total_ut_steps + 1`` numbers) of the step it came out of and of the
first step: :func:`run` reads the last checked step's for
``loop_exit_entropy`` and prints both, so that a gate that has shut three
exits is seen and not mistaken for a cell that measures four.

The functions that count work (:func:`loop_flash_work`,
:func:`loop_head_work`, :func:`train_flops_per_step`) are the
benchmark's, from shapes; the readers of this configuration's per-layer
metrics call them.
"""

import numpy as np

from builders import training


def causal_pairs(seq):
    """Query-key pairs of one causal sequence: every j <= i."""
    return seq * (seq + 1) // 2


def block_applications(config):
    """Applications of a block in one forward pass of the model: the
    layers held, every pass."""
    return config["num_layers"] * config["total_ut_steps"]


def matrix_parameters(config):
    """``(attention, MLP)``: the parameters of one layer's matrices."""
    hidden = config["hidden_size"]
    heads = config["num_attention_heads"] + config["num_key_value_heads"]
    return (2 * hidden * heads * config["head_dim"],
            3 * hidden * config["intermediate_size"])


def loop_flash_work(config, batch, seq, forward_calls):
    """``(flops, bytes)`` of the flash kernels' calls in one step: an
    application's ``forward_calls`` forward calls, one dq and one dkdv
    over every pair ``j <= i``, at 16 query heads over 16 of width 128,
    for each of the :func:`block_applications`, counted as
    ``layer_metrics/attn_flash_roofline.flash_band_work`` counts a
    layer's."""
    from layer_metrics import attn_flash_roofline

    flops, nbytes = attn_flash_roofline.flash_band_work(
        batch, config["num_attention_heads"], config["num_key_value_heads"],
        seq, config["head_dim"], causal_pairs(seq), forward_calls)
    times = block_applications(config)
    return times * flops, times * nbytes


def loop_head_work(config, batch, seq):
    """``(flops, bytes)`` of the head's one sweep in one step: three
    vocabulary-wide products a chunk (logits, the states' gradient, the
    kernel's gradient), ``6 x rows x hidden x vocabulary`` FLOPs over the
    ``total_ut_steps x batch x seq`` rows of the stacked exits. Bytes at
    the least: the kernel read once a chunk in bf16 and its float32
    gradient written once; the rows' states read and their gradient
    written in bf16. The products bound it."""
    hidden, vocab = config["hidden_size"], config["vocab_size"]
    rows = config["total_ut_steps"] * batch * seq
    return (6 * rows * hidden * vocab,
            config["loss_chunks"] * hidden * vocab * 2 + hidden * vocab * 4
            + 2 * rows * hidden * 2)


def train_flops_per_step(config, batch, seq):
    """Forward plus backward FLOPs of one step, from shapes, recomputation
    not counted: 6 x tokens x the matrices a token meets, which are every
    layer's attention and MLP ONCE A PASS, the head once an exit and the
    gate once a pass (the lookup is no product); and for attention 12 x
    head width x heads x the causal pairs (scores and context, forward
    and twice backward) for every application of a block. Rotary, norms
    and the exit distribution are not counted."""
    attention, mlp = matrix_parameters(config)
    hidden, passes = config["hidden_size"], config["total_ut_steps"]
    met = block_applications(config) * (attention + mlp) \
        + passes * hidden * (config["vocab_size"] + 1)
    pairs = 12.0 * config["head_dim"] * config["num_attention_heads"] \
        * batch * causal_pairs(seq) * block_applications(config)
    return 6.0 * batch * seq * met + pairs


def model_config(config):
    import jax.numpy as jnp

    from horovod_tpu.models.ouro import OuroConfig

    if config["num_key_value_heads"] != config["num_attention_heads"] \
            or config["use_sliding_window"] or config["rope_scaling"] \
            or config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("the program's block has plain multi-head "
                         "attention over every earlier key, the plain "
                         "rotary embedding, a SiLU gate and an untied head")
    if len(config["deployment"]["layers_held"]) != config["num_layers"]:
        raise ValueError("num_layers counts the layers held")
    return OuroConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_layers"],
        total_ut_steps=config["total_ut_steps"],
        num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        mlp_hidden=config["intermediate_size"],
        norm_eps=config["rms_norm_eps"],
        exit_entropy_beta=config["assumed"]["exit_entropy_beta"],
        dtype=jnp.dtype(config["compute_dtype"]), remat=config["remat"])


def build(config, traffic, mesh):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import OuroLM, ouro_lm_loss
    from horovod_tpu.ops.attention import make_attention_fn

    opt = config["optimizer"]
    cfg = model_config(config)
    model = OuroLM(cfg, attention_fn=make_attention_fn(causal=True))
    seq = traffic["sequence_length"]
    batch = traffic["per_chip_batch"] * mesh.size
    tx = hvd.DistributedOptimizer(
        optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                    eps=opt["eps"], weight_decay=opt["weight_decay"]),
        axis_name="data")

    def loss_fn(p, ids):
        states, gate_logits = model.apply({"params": p}, ids,
                                          return_hidden=True)
        return ouro_lm_loss(
            states, gate_logits, p["lm_head"]["kernel"], ids,
            num_chunks=config["loss_chunks"], beta=cfg.exit_entropy_beta)

    def train_step(state, data):
        p, opt_state, (_, first) = state
        (loss, exits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, *data)
        updates, opt_state = tx.update(grads, opt_state, p)
        # A distribution sums to 1: all zeros is the state no step has
        # written yet.
        first = jnp.where(jnp.any(first != 0), first, exits)
        return (optax.apply_updates(p, updates), opt_state,
                jnp.stack([exits, first])), hvd.allreduce(loss)

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P("data")), out_specs=(P(), P()),
        check_vma=False), donate_argnums=(0,))

    weight_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, seq), jnp.int32))["params"])
    no_exits = jnp.zeros((2, cfg.total_ut_steps + 1), jnp.float32)

    return training.Workbench(
        step=step,
        weight_shapes=weight_shapes,
        init_state=lambda w: (w, tx.init(w), no_exits),
        weight_params=lambda w: w,
        params_of=lambda state: state[0],
        # Adam's first moment after one step from zero is (1 - b1) x the
        # gradient the optimizer got.
        first_gradient=lambda state: jax.tree.map(
            lambda mu: mu / (1.0 - opt["b1"]), state[1][0].mu),
        identical_of=lambda state: state[:2],
        batch_shapes=(jax.ShapeDtypeStruct((batch, seq), jnp.int32),),
        make_batch=lambda rng: (rng.integers(
            0, config["vocab_size"], (batch, seq), dtype=np.int32),),
        samples_per_step=batch,
        flops_per_step=train_flops_per_step(config, batch, seq),
        state_shardings=NamedSharding(mesh, P()),
        batch_shardings=NamedSharding(mesh, P("data")),
    )


def run(ctx):
    """``training.run`` with the exits' numbers kept for the readers:
    ``layer_inputs["loop_exits"]`` is ``[last, first]``, the last and the
    first checked step's mean exit distribution and then its mean
    entropy. The harness asks for the parameters of the state once, after
    the last checked step; the numbers ride in the same state."""
    seen = {}

    def build_keeping_exits(config, traffic, mesh):
        bench = build(config, traffic, mesh)
        params_of = bench.params_of

        def params_and_exits(state):
            seen["loop_exits"] = np.asarray(state[2]).tolist()
            return params_of(state)

        bench.params_of = params_and_exits
        return bench

    out = training.run(ctx, build_keeping_exits)
    last, first = seen["loop_exits"]
    print("[loop] mean exit distribution and entropy, first checked step: "
          + ", ".join(f"{v:.6g}" for v in first) + "; last: "
          + ", ".join(f"{v:.6g}" for v in last)
          + f" (uniform over {len(first) - 1} exits reads "
          f"{np.log(len(first) - 1):.6g})", flush=True)
    out["layer_inputs"].update(seen)
    return out
