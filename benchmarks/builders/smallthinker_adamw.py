"""Builder for SmallThinker next-token training by AdamW through
``hvd.DistributedOptimizer``: ``horovod_tpu.models.SmallThinkerLM`` with
the sizes of the configuration file (the chip's share of a four-chip
expert-parallel deployment: the experts held, the depth and the
vocabulary slice it names), the sequence length and batch of the traffic
file and the mesh of the caller. Attention goes through the program's own
rule (``make_attention_fn(causal=True[, window=...])``: the flash kernels
at sequence 512 and above, streamed past one block), the expert layer
through ``parallel.moe.moe_apply_held``, the loss through
``chunked_causal_lm_loss``, each block recomputed in the backward pass.

The batch is fixed, made from the seed and resident on the device: token
ids uniform over the vocabulary slice, unbroken sequences. There is no
input pipeline. The step's state carries, beside parameters and AdamW's
moments, the expert loads of the step it came out of (assignments each
held expert received, layer by layer): :func:`run` reads the last checked
step's for ``moe_load_max_over_mean`` and the expert layer's roofline.
"""

import functools

import numpy as np

from builders import training


def band_pairs(seq, window=None):
    """Query-key pairs inside the causal band of one sequence: key j for
    query i iff ``i - window < j <= i`` (no window: every j <= i)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_windows(config):
    """One entry a layer that is run: its window, or None where it sees
    every earlier key."""
    return [config["sliding_window_size"] if windowed else None
            for windowed in
            config["sliding_window_layout"][:config["num_layers"]]]


def expected_rows_held(config, tokens):
    """Assignments that land on the held experts of one layer when the
    router spreads them evenly: tokens x chosen x held / router width."""
    deployment = config["deployment"]
    return (tokens * config["moe_num_active_primary_experts"]
            * len(deployment["experts_held"]) / deployment["router_width"])


def train_flops_per_step(config, batch, seq):
    """Forward plus backward FLOPs of one step, from shapes, recomputation
    not counted: 6 x tokens x the matrices every token meets (q, k, v, o
    and the router of each layer, the head over the vocabulary slice);
    6 x the rows the held experts are expected to receive x an expert's
    three matrices; and for attention 12 x head width x query heads x the
    pairs inside each layer's band (scores and context, forward and twice
    backward)."""
    hidden, head = config["hidden_size"], config["head_dim"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    tokens = batch * seq
    layers = layer_windows(config)
    per_layer = (2 * hidden * heads * head + 2 * hidden * kv_heads * head
                 + hidden * config["deployment"]["router_width"])
    dense = 6.0 * tokens * (len(layers) * per_layer
                            + hidden * config["vocab_size"])
    experts = 6.0 * len(layers) * expected_rows_held(config, tokens) \
        * 3 * hidden * config["moe_ffn_hidden_size"]
    attention = 12.0 * head * heads * batch * sum(
        band_pairs(seq, w) for w in layers)
    return dense + experts + attention


def starting_weights(config, weights):
    """The seeded weights as training starts from them: the projections
    that write into the residual stream (attention's ``wo``, the experts'
    ``w_down``) scaled by 1/sqrt(2 x published layers), as GPT-2 and
    Megatron-LM initialise them. The harness draws every matrix at one
    standard deviation; the configuration's ``assumed.init`` says why
    these two are smaller."""
    import jax

    scale = (2.0 * config["published"]["num_hidden_layers"]) ** -0.5

    def leaf(path, x):
        names = {str(getattr(k, "key", k)) for k in path}
        return x * scale if names & {"wo", "w_down"} else x

    return jax.tree_util.tree_map_with_path(leaf, weights)


def model_config(config):
    import jax.numpy as jnp

    from horovod_tpu.models.smallthinker import SmallThinkerConfig

    deployment = config["deployment"]
    return SmallThinkerConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        num_experts=deployment["router_width"],
        num_selected=config["moe_num_active_primary_experts"],
        expert_hidden=config["moe_ffn_hidden_size"],
        experts_held=tuple(deployment["experts_held"]),
        sliding_window=config["sliding_window_size"],
        window_layout=tuple(config["sliding_window_layout"]),
        rope_layout=tuple(config["rope_layout"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]), remat=config["remat"])


def build(config, traffic, mesh):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import SmallThinkerLM, chunked_causal_lm_loss
    from horovod_tpu.ops.attention import make_attention_fn

    if len(config["deployment"]["experts_held"]) != \
            config["moe_num_primary_experts"]:
        raise ValueError("moe_num_primary_experts counts the experts held")
    opt = config["optimizer"]
    cfg = model_config(config)
    model = SmallThinkerLM(
        cfg, attention_fn=make_attention_fn(causal=True),
        window_attention_fn=make_attention_fn(
            causal=True, window=cfg.sliding_window))
    seq = traffic["sequence_length"]
    batch = traffic["per_chip_batch"] * mesh.size
    tx = hvd.DistributedOptimizer(
        optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                    eps=opt["eps"], weight_decay=opt["weight_decay"]),
        axis_name="data")

    def loss_fn(p, ids):
        hidden, load = model.apply({"params": p}, ids, return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, p["lm_head"]["kernel"], ids,
            num_chunks=config["loss_chunks"]), load

    def train_step(state, data):
        p, opt_state, _ = state
        (loss, load), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, *data)
        updates, opt_state = tx.update(grads, opt_state, p)
        return (optax.apply_updates(p, updates), opt_state, load), \
            hvd.allreduce(loss)

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P("data")), out_specs=(P(), P()),
        check_vma=False), donate_argnums=(0,))

    weight_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, seq), jnp.int32))["params"])
    no_load = jnp.zeros((cfg.num_layers, len(cfg.held())), jnp.int32)
    start = functools.partial(starting_weights, config)

    return training.Workbench(
        step=step,
        weight_shapes=weight_shapes,
        init_state=lambda w: (start(w), tx.init(w), no_load),
        weight_params=start,
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
    """``training.run`` with the expert loads of the last checked step
    kept for the readers: ``layer_inputs["moe_load"][layer][held
    expert]``. The harness asks for the parameters of the state once,
    after the last checked step; the loads ride in the same state."""
    loads = {}

    def build_keeping_loads(config, traffic, mesh):
        bench = build(config, traffic, mesh)
        params_of = bench.params_of

        def params_and_loads(state):
            loads["moe_load"] = np.asarray(state[2]).tolist()
            return params_of(state)

        bench.params_of = params_and_loads
        return bench

    out = training.run(ctx, build_keeping_loads)
    cell, by_layer = ctx["cell"], loads["moe_load"]
    tokens = (out["layer_inputs"]["bench"].samples_per_step
              * cell.traffic["sequence_length"])
    print(f"[moe] assignments landed on the held experts, by layer: "
          f"{[sum(layer) for layer in by_layer]} (expected "
          f"{expected_rows_held(cell.config, tokens):.0f} a layer); "
          f"largest expert {max(map(max, by_layer))}, "
          f"all {sum(map(sum, by_layer))}", flush=True)
    out["layer_inputs"].update(loads)
    return out
