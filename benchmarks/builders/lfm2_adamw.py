"""Builder for LFM2 next-token training by AdamW through
``hvd.DistributedOptimizer``: ``horovod_tpu.models.Lfm2LM`` with the sizes
of the configuration file (the chip's share of an eight-chip
expert-parallel deployment: the routed experts held, the published layers
run and the vocabulary slice it names), the sequence length and batch of
the traffic file and the mesh of the caller. Attention goes through the
program's own rule (``make_attention_fn(causal=True)``: the flash kernels
at sequence 512 and above, streamed past one block, here at head width
64), the convolution mixers through ``ops.linear_attention.causal_conv``,
the routed experts through ``parallel.moe.moe_apply_held`` under the
sigmoid rule with its bias, the loss through ``chunked_causal_lm_loss``
with the embedding as the head, each block recomputed in the backward
pass.

The batch is fixed, made from the seed and resident on the device: token
ids uniform over the vocabulary slice, unbroken sequences. There is no
input pipeline. The step's state carries, beside parameters and AdamW's
moments, the routed experts' loads of the step it came out of
(assignments each held expert received, sparse layer by sparse layer):
:func:`run` reads the last checked step's for
``moe_sigmoid_load_max_over_mean`` and the held experts' roofline.

The functions that count work (:func:`shortconv_pointwise_work`,
:func:`head64_flash_work`, :func:`train_flops_per_step`) are the
benchmark's, from shapes; the readers of this configuration's per-layer
metrics call them.
"""

import functools

import numpy as np

from builders import training
# The band's pairs are counted as the other expert configurations'
# builders count them.
from builders.smallthinker_adamw import band_pairs

CONV, FULL = "conv", "full_attention"
# Projections that write into the residual stream: scaled by 1/sqrt(2 x
# published layers) as the configuration's ``assumed.init`` says.
RESIDUAL_OUTPUTS = ("wo", "out_proj", "w_down")


def layers(config):
    """One ``(mixer kind, whether its FFN routes)`` a layer that is run:
    the published layers ``deployment.layers_run`` in order, the first
    ``num_dense_layers`` of them dense."""
    kinds = [config["layer_types"][i]
             for i in config["deployment"]["layers_run"]]
    if len(kinds) != config["num_layers"]:
        raise ValueError("deployment.layers_run names num_layers layers")
    return [(kind, i >= config["num_dense_layers"])
            for i, kind in enumerate(kinds)]


def sparse_layers(config):
    return sum(sparse for _, sparse in layers(config))


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def expected_rows_held(config, tokens):
    """Assignments that land on the held experts of one sparse layer when
    the router spreads them evenly: tokens x chosen x held / router
    width."""
    deployment = config["deployment"]
    return (tokens * config["num_experts_per_tok"]
            * len(deployment["experts_held"]) / deployment["router_width"])


def shortconv_pointwise_work(config, batch, seq):
    """``(flops, bytes)`` of the gated convolutions' pointwise passes in
    one step, every convolution layer's together, at the least the passes
    need: the forward reads the two gates and ``u`` and writes the gated
    result (4 arrays of tokens x hidden in bf16); the backward reads
    those three and the result's gradient and writes three gradients (7
    arrays). The block's recomputed forward reads what the backward reads
    anyway and adds nothing to the least. The taps and their gradient are
    3 x hidden floats. FLOPs (two gates, three taps, forward and
    backward) are some 40 a token and channel: the passes are bound by
    bytes."""
    conv_layers = sum(kind == CONV for kind, _ in layers(config))
    elements = batch * seq * config["hidden_size"]
    return (conv_layers * 40 * elements,
            conv_layers * (4 + 7) * elements * 2)


def head64_flash_work(config, batch, seq, forward_calls):
    """``(flops, bytes)`` of the flash kernels' calls of the attention
    layers in one step: a layer's ``forward_calls`` forward calls, one dq
    and one dkdv over every pair ``j <= i``, at 32 query heads over 8 of
    width 64, counted as
    ``layer_metrics/attn_flash_roofline.flash_band_work`` counts a
    layer's."""
    from layer_metrics import attn_flash_roofline

    flops = nbytes = 0
    for kind, _ in layers(config):
        if kind != FULL:
            continue
        f, b = attn_flash_roofline.flash_band_work(
            batch, config["num_attention_heads"],
            config["num_key_value_heads"], seq, head_dim(config),
            band_pairs(seq), forward_calls)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def matrix_parameters(config):
    """``(convolution mixer, attention, dense MLP, router, one routed
    expert)``: the parameters of a layer's matrices, by part (taps, norm
    scales and the bias are not matrices)."""
    hidden = config["hidden_size"]
    kv = 2 * hidden * config["num_key_value_heads"] * head_dim(config)
    return (4 * hidden * hidden, 2 * hidden * hidden + kv,
            3 * hidden * config["intermediate_size"],
            hidden * config["deployment"]["router_width"],
            3 * hidden * config["moe_intermediate_size"])


def train_flops_per_step(config, batch, seq):
    """Forward plus backward FLOPs of one step, from shapes, recomputation
    not counted: 6 x tokens x the matrices every token meets (a
    convolution mixer's two projections or attention's q, k, v and o; the
    dense MLP or the router; the head over the vocabulary slice, once:
    the lookup is no product); 6 x the rows the held experts are expected
    to receive x a routed expert's three matrices; and for attention 12 x
    head width x query heads x the causal pairs (scores and context,
    forward and twice backward). Taps, gates, rotary and norms are not
    counted."""
    conv, attention, mlp, router, expert = matrix_parameters(config)
    tokens = batch * seq
    met = config["hidden_size"] * config["vocab_size"]
    pairs = 0.0
    for kind, sparse in layers(config):
        met += conv if kind == CONV else attention
        met += router if sparse else mlp
        if kind == FULL:
            pairs += 12.0 * head_dim(config) * config[
                "num_attention_heads"] * batch * band_pairs(seq)
    experts = 6.0 * sparse_layers(config) \
        * expected_rows_held(config, tokens) * expert
    return 6.0 * tokens * met + experts + pairs


def starting_weights(config, draws):
    """The seeded weights as training starts from them (the
    configuration's ``assumed.init``): the projections that write into
    the residual stream scaled by 1/sqrt(2 x published layers); a
    convolution's taps uniform within 1/sqrt(taps) of zero, the harness's
    normal draw taken through the normal's distribution function; the
    expert bias at its own standard deviation."""
    import jax
    from jax.scipy.special import ndtr

    init = config["init"]
    std = init["kernel"]
    residual = (2.0 * config["published"]["num_hidden_layers"]) ** -0.5

    def leaf(path, x):
        names = {str(getattr(k, "key", k)) for k in path}
        if names & set(RESIDUAL_OUTPUTS):
            return x * residual
        if "taps" in names:
            return (2.0 * ndtr(x / std) - 1.0) * x.shape[0] ** -0.5
        if "expert_bias" in names:
            return x * (init["expert_bias"] / std)
        return x

    return jax.tree_util.tree_map_with_path(leaf, draws)


def model_config(config):
    import jax.numpy as jnp

    from horovod_tpu.models.lfm2 import Lfm2Config

    deployment = config["deployment"]
    if len(deployment["experts_held"]) != config["num_experts"]:
        raise ValueError("num_experts counts the routed experts held")
    if config["conv_bias"] or \
            config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("the program's block has no convolution bias and "
                         "the plain rotary embedding")
    if not (config["use_expert_bias"] and config["norm_topk_prob"]
            and config["tie_embedding"]) \
            or config["routed_scaling_factor"] != 1:
        raise ValueError("the program's block routes with the bias, "
                         "normalises the chosen scores, scales the routed "
                         "part by 1 and ties the head")
    return Lfm2Config(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_layers"],
        layer_types=tuple(kind for kind, _ in layers(config)),
        num_dense_layers=config["num_dense_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        conv_taps=config["conv_L_cache"],
        mlp_hidden=config["intermediate_size"],
        num_experts=deployment["router_width"],
        num_selected=config["num_experts_per_tok"],
        expert_hidden=config["moe_intermediate_size"],
        experts_held=tuple(deployment["experts_held"]),
        norm_eps=config["norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]), remat=config["remat"])


def build(config, traffic, mesh):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import Lfm2LM, chunked_causal_lm_loss
    from horovod_tpu.models.lfm2 import decay_mask
    from horovod_tpu.ops.attention import make_attention_fn

    opt = config["optimizer"]
    cfg = model_config(config)
    model = Lfm2LM(cfg, attention_fn=make_attention_fn(causal=True))
    seq = traffic["sequence_length"]
    batch = traffic["per_chip_batch"] * mesh.size
    # The expert bias is outside the decay; its gradient is zero, so
    # AdamW leaves it as it was.
    tx = hvd.DistributedOptimizer(
        optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                    eps=opt["eps"], weight_decay=opt["weight_decay"],
                    mask=decay_mask),
        axis_name="data")

    def loss_fn(p, ids):
        hidden, load = model.apply({"params": p}, ids, return_hidden=True)
        # The head is the embedding: its gradient is the sum of the
        # lookup's and the head's.
        return chunked_causal_lm_loss(
            hidden, p["tok_embeddings"]["embedding"].T, ids,
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
    no_load = jnp.zeros((sparse_layers(config), len(cfg.held())), jnp.int32)
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
    """``training.run`` with the routed experts' loads of the last checked
    step kept for the readers: ``layer_inputs["moe_load"][sparse
    layer][held expert]``. The harness asks for the parameters of the
    state once, after the last checked step; the loads ride in the same
    state."""
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
    print(f"[moe] assignments landed on the held experts, by sparse layer: "
          f"{[sum(layer) for layer in by_layer]} (expected "
          f"{expected_rows_held(cell.config, tokens):.0f} a layer); "
          f"largest expert {max(map(max, by_layer))}, "
          f"all {sum(map(sum, by_layer))}", flush=True)
    out["layer_inputs"].update(loads)
    return out
