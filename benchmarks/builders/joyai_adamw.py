"""Builder for JoyAI-LLM-Flash training by AdamW through
``hvd.DistributedOptimizer``: ``horovod_tpu.models.JoyAILM`` with the
sizes of the configuration file (the chip's share of a thirty-two-chip
expert-parallel deployment: the routed experts held, the depth, the
multi-token-prediction module and the vocabulary slice it names), the
sequence length and batch of the traffic file and the mesh of the caller.
Attention goes through the program's own rule
(``make_attention_fn(causal=True)``: the flash kernels at sequence 512
and above, streamed past one block, here with q and k 192 wide over v
128), the routed experts through ``parallel.moe.moe_apply_held`` under
the sigmoid rule with its bias and the model's 1e-20, both losses
(the next token's, and the module's two ahead) through
``chunked_causal_lm_loss`` and the one head, each block recomputed in the
backward pass, the module's too.

The batch is fixed, made from the seed and resident on the device: token
ids uniform over the vocabulary slice, unbroken sequences. There is no
input pipeline. The step's state carries, beside parameters and AdamW's
moments, the routed experts' loads of the step it came out of
(assignments each held expert received, sparse layer by sparse layer, the
module's block last): :func:`run` reads the last checked step's for
``moe_sigmoid256_load_max_over_mean`` and the held experts' roofline.

The functions that count work (:func:`latent_flash_work`,
:func:`train_flops_per_step`) are the benchmark's, from shapes; the
readers of this configuration's per-layer metrics call them.
"""

import functools

import numpy as np

from builders import training
# The band's pairs and the even share's rows are counted, and the seeded
# weights scaled (``wo`` and every ``w_down`` by 1/sqrt(2 x published
# layers), the expert bias to its own standard deviation: the
# configuration's ``assumed.init``), as the other expert configurations'
# builders do.
from builders.lfm2_adamw import expected_rows_held, starting_weights
from builders.smallthinker_adamw import band_pairs


def attention_blocks(config):
    """Blocks that attend: the layers run and the multi-token-prediction
    module's."""
    return config["num_layers"] + config["num_nextn_predict_layers"]


def sparse_blocks(config):
    """Blocks whose FFN routes: the layers after the leading dense ones
    and the module's; one row of ``moe_load`` each, in that order."""
    return attention_blocks(config) - config["first_k_dense_replace"]


def latent_flash_work(config, batch, seq, forward_calls):
    """``(flops, bytes)`` of the flash kernels' calls of every attending
    block in one step: a block's ``forward_calls`` forward calls, one dq
    and one dkdv over every pair ``j <= i`` of every head. A pair costs
    the forward ``2 x 192`` (score) ``+ 2 x 128`` (context) FLOPs; dq the
    score again, ``dp`` over 128 and ``ds k`` over 192: 1024; dk/dv the
    score, ``p^T do`` and ``dp`` over 128 and ``ds^T q`` over 192: 1280.
    Bytes: each call's operands and results once in bf16, q, k, dq and dk
    192 wide, v, o, do and dv 128, with the f32 row statistics; k at the
    head count the kernels read, all 32: the one rotary key a token is
    copied into every head's k before the call."""
    heads = config["num_attention_heads"]
    qk, vo = config["qk_head_dim"], config["v_head_dim"]
    pairs = batch * heads * band_pairs(seq)
    wide = batch * heads * seq * qk * 2         # one bf16 q / k / dq / dk
    narrow = batch * heads * seq * vo * 2       # one bf16 v / o / do / dv
    stat = batch * heads * seq * 4              # one f32 row statistic
    forward = 2 * qk + 2 * vo
    flops = (forward_calls * forward + (4 * qk + 2 * vo)
             + (4 * qk + 4 * vo)) * pairs
    nbytes = (forward_calls * (2 * wide + 2 * narrow + stat)
              + (3 * wide + 2 * narrow + 2 * stat)      # q k v do .. -> dq
              + (3 * wide + 3 * narrow + 2 * stat))     # ... -> dk, dv
    blocks = attention_blocks(config)
    return blocks * flops, blocks * nbytes


def matrix_parameters(config):
    """``(latent attention, dense MLP, shared expert, router, one routed
    expert, the module's projection)``: the parameters of a block's
    matrices, by part (norm scales and the bias are not matrices)."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, vo = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    attention = (hidden * q_rank + q_rank * heads * (nope + rope)
                 + hidden * (kv_rank + rope) + kv_rank * heads * (nope + vo)
                 + heads * vo * hidden)
    expert = 3 * hidden * config["moe_intermediate_size"]
    return (attention, 3 * hidden * config["intermediate_size"],
            config["n_shared_experts"] * expert,
            hidden * config["deployment"]["router_width"], expert,
            2 * hidden * hidden)


def train_flops_per_step(config, batch, seq):
    """Forward plus backward FLOPs of one step, from shapes, recomputation
    not counted: 6 x tokens x the matrices every token meets (each
    attending block's five attention matrices; the dense MLP or the
    router and the shared expert; the module's projection; the head over
    the vocabulary slice once for each of the two passes); 6 x the rows
    the held experts are expected to receive x a routed expert's three
    matrices, the module's block included; and for attention 3 x (2 x 192
    + 2 x 128) x heads x the causal pairs a block (scores over 192 and
    context over 128, forward and twice backward: what the mathematics
    needs, less than the kernels compute, which rebuild the scores in
    each backward kernel). Rotation, norms and the lookup are not
    counted."""
    attention, mlp, shared, router, expert, eh_proj = matrix_parameters(
        config)
    tokens = batch * seq
    module = config["num_nextn_predict_layers"]
    dense = config["first_k_dense_replace"]
    met = (attention_blocks(config) * attention + dense * mlp
           + sparse_blocks(config) * (shared + router) + module * eh_proj
           + (1 + module) * config["hidden_size"] * config["vocab_size"])
    experts = 6.0 * sparse_blocks(config) \
        * expected_rows_held(config, tokens) * expert
    pairs = 3.0 * (2 * config["qk_head_dim"] + 2 * config["v_head_dim"]) \
        * config["num_attention_heads"] * batch * band_pairs(seq) \
        * attention_blocks(config)
    return 6.0 * tokens * met + experts + pairs


def model_config(config):
    import jax.numpy as jnp

    from horovod_tpu.models.joyai import JoyAIConfig

    deployment = config["deployment"]
    if len(deployment["experts_held"]) != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the routed experts held")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]) \
            or config["head_dim"] != config["qk_rope_head_dim"] \
            or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("qk_head_dim is the two parts together, head_dim "
                         "the rotary part, and every head has its own key")
    if config["attention_bias"] or config["rope_scaling"] is not None \
            or not config["rope_interleave"] or config["hidden_act"] != "silu":
        raise ValueError("the program's block has no attention bias, plain "
                         "interleaved rotary pairs and SiLU-gated MLPs")
    if (config["scoring_func"], config["topk_method"], config["n_group"],
            config["topk_group"], config["moe_layer_freq"]) != (
            "sigmoid", "noaux_tc", 1, 1, 1) or not config["norm_topk_prob"] \
            or config["tie_word_embeddings"]:
        raise ValueError("the program's block routes every layer after the "
                         "dense ones by sigmoid scores with the bias in one "
                         "group, normalises the chosen scores and has an "
                         "untied head")
    return JoyAIConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_layers"],
        num_dense_layers=config["first_k_dense_replace"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        mlp_hidden=config["intermediate_size"],
        num_experts=deployment["router_width"],
        num_selected=config["num_experts_per_tok"],
        expert_hidden=config["moe_intermediate_size"],
        shared_hidden=(config["n_shared_experts"]
                       * config["moe_intermediate_size"]),
        routed_scale=config["routed_scaling_factor"],
        weight_sum_eps=config["routing_weight_sum_eps"],
        mtp_layers=config["num_nextn_predict_layers"],
        experts_held=tuple(deployment["experts_held"]),
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]), remat=config["remat"])


def build(config, traffic, mesh):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import JoyAILM, joyai_lm_loss
    from horovod_tpu.models.lfm2 import decay_mask
    from horovod_tpu.ops.attention import make_attention_fn

    opt = config["optimizer"]
    cfg = model_config(config)
    model = JoyAILM(cfg, attention_fn=make_attention_fn(causal=True))
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
        hidden, mtp_hidden, load = model.apply({"params": p}, ids,
                                               return_hidden=True)
        # One head for both passes: its gradient, and the embedding's,
        # has two sources.
        return joyai_lm_loss(
            hidden, mtp_hidden, p["lm_head"]["kernel"], ids,
            num_chunks=config["loss_chunks"],
            mtp_weight=config["mtp_loss_weight"]), load

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
    no_load = jnp.zeros((sparse_blocks(config), len(cfg.held())), jnp.int32)
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
    block][held expert]``. The harness asks for the parameters of the
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
    print(f"[moe] assignments landed on the held experts, by sparse block "
          f"(the module's last): {[sum(layer) for layer in by_layer]} "
          f"(expected {expected_rows_held(cell.config, tokens):.0f} a "
          f"block); largest expert {max(map(max, by_layer))}, "
          f"all {sum(map(sum, by_layer))}", flush=True)
    out["layer_inputs"].update(loads)
    return out
