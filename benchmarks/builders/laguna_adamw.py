"""Builder for Laguna next-token training by AdamW through
``hvd.DistributedOptimizer``: ``horovod_tpu.models.LagunaLM`` with the
sizes of the configuration file (the chip's share of a sixteen-chip
expert-parallel deployment: the routed experts held, the depth and the
vocabulary slice it names), the sequence length and batch of the traffic
file and the mesh of the caller. Attention goes through the program's own
rule (``make_attention_fn(causal=True[, window=...])``: the flash kernels
at sequence 512 and above, streamed past one block), the routed experts
through ``parallel.moe.moe_apply_held``, the loss through
``chunked_causal_lm_loss``, each block recomputed in the backward pass.

The batch is fixed, made from the seed and resident on the device: token
ids uniform over the vocabulary slice, unbroken sequences. There is no
input pipeline. The step's state carries, beside parameters and AdamW's
moments, the routed experts' loads of the step it came out of
(assignments each held expert received, sparse layer by sparse layer):
:func:`run` reads the last checked step's for
``moe_held_load_max_over_mean`` and the held experts' roofline.

The functions that count work (``band_pairs``,
:func:`window_flash_work`, :func:`train_flops_per_step`) are the
benchmark's, from shapes; the readers of this configuration's per-layer
metrics call them.
"""

import functools

import numpy as np

from builders import training
# The band's pairs are counted, and the seeded weights scaled (``wo`` and
# every ``w_down`` by 1/sqrt(2 x published layers): the configuration's
# ``assumed.init``), as the other expert configuration's builder does.
from builders.smallthinker_adamw import band_pairs, starting_weights

FULL, SLIDING = "full_attention", "sliding_attention"
SPARSE = "sparse"


def layers(config):
    """One ``(attention kind, query heads, MLP kind)`` a layer that is
    run."""
    n = config["num_layers"]
    return list(zip(config["layer_types"][:n],
                    config["num_attention_heads_per_layer"][:n],
                    config["mlp_layer_types"][:n]))


def sparse_layers(config):
    return sum(mlp == SPARSE for _, _, mlp in layers(config))


def expected_rows_held(config, tokens):
    """Assignments that land on the held experts of one sparse layer when
    the router spreads them evenly: tokens x chosen x held / router
    width."""
    deployment = config["deployment"]
    return (tokens * config["num_experts_per_tok"]
            * len(deployment["experts_held"]) / deployment["router_width"])


def window_flash_work(config, batch, seq, forward_calls):
    """``(flops, bytes)`` of the flash kernels' calls of the sliding
    layers in one step: a layer's ``forward_calls`` forward calls, one dq
    and one dkdv over the pairs of the band ``i - window < j <= i``, at
    the sliding layers' query heads over the key/value heads, counted as
    ``layer_metrics/attn_flash_roofline.flash_band_work`` counts a
    layer's."""
    from layer_metrics import attn_flash_roofline

    flops = nbytes = 0
    for kind, heads, _ in layers(config):
        if kind != SLIDING:
            continue
        f, b = attn_flash_roofline.flash_band_work(
            batch, heads, config["num_key_value_heads"], seq,
            config["head_dim"], band_pairs(seq, config["sliding_window"]),
            forward_calls)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def matrix_parameters(config):
    """``(full attention, sliding attention, dense MLP, shared expert,
    router, one routed expert)``: the parameters of a layer's matrices,
    by part."""
    hidden, head = config["hidden_size"], config["head_dim"]
    kv = 2 * hidden * config["num_key_value_heads"] * head

    def attention(heads):
        # q and o, k and v, the gate a head.
        return 2 * hidden * heads * head + kv + hidden * heads

    by_kind = {kind: heads for kind, heads, _ in layers(config)}
    return (attention(by_kind[FULL]), attention(by_kind[SLIDING]),
            3 * hidden * config["intermediate_size"],
            3 * hidden * config["shared_expert_intermediate_size"],
            hidden * config["deployment"]["router_width"],
            3 * hidden * config["moe_intermediate_size"])


def train_flops_per_step(config, batch, seq):
    """Forward plus backward FLOPs of one step, from shapes, recomputation
    not counted: 6 x tokens x the matrices every token meets (each layer's
    q, k, v, o and gate at its own head count; the dense MLP or the
    router and the shared expert; the head over the vocabulary slice);
    6 x the rows the held experts are expected to receive x a routed
    expert's three matrices; and for attention 12 x head width x the
    layer's query heads x the pairs inside its band (scores and context,
    forward and twice backward). Rotary, gate and norms are not
    counted."""
    full, sliding, mlp, shared, router, expert = matrix_parameters(config)
    tokens = batch * seq
    met = config["hidden_size"] * config["vocab_size"]
    attention = 0.0
    for kind, heads, mlp_kind in layers(config):
        met += full if kind == FULL else sliding
        met += shared + router if mlp_kind == SPARSE else mlp
        attention += 12.0 * config["head_dim"] * heads * batch * band_pairs(
            seq, config["sliding_window"] if kind == SLIDING else None)
    experts = 6.0 * sparse_layers(config) \
        * expected_rows_held(config, tokens) * expert
    return 6.0 * tokens * met + experts + attention


def _rotary(parameters):
    from horovod_tpu.models.laguna import RotarySpec

    kind = parameters["rope_type"]
    if kind not in ("yarn", "default"):
        raise ValueError(f"no rotary embedding of type {kind!r}")
    yarn = {} if kind == "default" else dict(
        yarn_factor=float(parameters["factor"]),
        original_positions=parameters["original_max_position_embeddings"],
        beta_fast=float(parameters["beta_fast"]),
        beta_slow=float(parameters["beta_slow"]),
        attention_factor=parameters["attention_factor"])
    return RotarySpec(theta=float(parameters["rope_theta"]),
                      fraction=parameters["partial_rotary_factor"], **yarn)


def model_config(config):
    import jax.numpy as jnp

    from horovod_tpu.models.laguna import LagunaConfig

    deployment = config["deployment"]
    if len(deployment["experts_held"]) != config["num_experts"]:
        raise ValueError("num_experts counts the routed experts held")
    if not config["gating"] or config["moe_apply_router_weight_on_input"] \
            or config["attention_bias"]:
        raise ValueError("the program's block has the gate, weighs an "
                         "expert's output and has no attention bias")
    return LagunaConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_layers"],
        layer_types=tuple(config["layer_types"]),
        heads_per_layer=tuple(config["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        full_rotary=_rotary(config["rope_parameters"]["full_attention"]),
        sliding_rotary=_rotary(
            config["rope_parameters"]["sliding_attention"]),
        mlp_hidden=config["intermediate_size"],
        num_experts=deployment["router_width"],
        num_selected=config["num_experts_per_tok"],
        expert_hidden=config["moe_intermediate_size"],
        shared_hidden=config["shared_expert_intermediate_size"],
        routed_scale=config["moe_routed_scaling_factor"],
        experts_held=tuple(deployment["experts_held"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]), remat=config["remat"])


def build(config, traffic, mesh):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import LagunaLM, chunked_causal_lm_loss
    from horovod_tpu.ops.attention import make_attention_fn

    opt = config["optimizer"]
    cfg = model_config(config)
    model = LagunaLM(
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
