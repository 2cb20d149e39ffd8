"""Builder for Olmo-Hybrid next-token training by AdamW through
``hvd.DistributedOptimizer``: ``horovod_tpu.models.OlmoHybridLM`` with the
sizes of the configuration file (the chip's share of a two-chip
head-parallel deployment: the heads held, the depth and the vocabulary
slice it names), the sequence length and batch of the traffic file and
the mesh of the caller. The full-attention layers go through the
program's own rule (``make_attention_fn(causal=True)``: the flash kernels
at sequence 512 and above, streamed past one block), the linear layers
through ``ops.linear_attention.gated_delta_rule``, the loss through
``chunked_causal_lm_loss``, each block recomputed in the backward pass.

The batch is fixed, made from the seed and resident on the device: token
ids uniform over the vocabulary slice, unbroken sequences. There is no
input pipeline. The step's state carries, beside parameters and AdamW's
moments, three numbers a linear layer of the step it came out of (the
smallest and the mean log decay over its tokens and heads, the largest
norm of a head's state after the last token): :func:`run` prints the last
checked step's, so that a degenerate decay (a state forgotten within a
chunk, or never) is seen and not mistaken for speed.

**What the checked steps hold on the device.** After the third checked
step the harness has, beside the state (12 bytes a parameter), the first
gradient, the seeded draws and the parameters' change: at 4 bytes each
that is 24 bytes a parameter, 18.4 GB for this cell's 766M, more than the
chip has. The harness compares the first gradient leaf by leaf by its
norm alone, so ``first_gradient`` hands a large matrix over as the norms
of its columns (the same norm, a thousandth of the bytes): 20 bytes a
parameter, 15.3 GB. Which matrices: ``REDUCED_FROM``. The norm of the two
trees' *difference* cannot be read from such a tree:
``tools/read_gaps.py`` still prints its two ``first_gradient_difference``
numbers, which are void in this cell (:func:`build` says so in every run
that reduces), and :func:`build` refuses a limit that names one. The repair
is the harness's (free the first gradient once its norms are read:
``ROADMAP.md`` D8).
"""

import functools
import math

import numpy as np

from builders import training
from harness import manifest

LINEAR, FULL = "linear_attention", "full_attention"
# Leaves the harness has no rule for, or a wrong one: it draws each as a
# ``kernel`` and :func:`starting_weights` maps the draw to what it is.
DRAWN_HERE = ("A_log", "dt_bias")
# A matrix of this many elements or more reaches the harness as the norms
# of its columns (the module's docstring). Every projection, the MLP, the
# embedding and the head of the cell lie above it (the smallest, a 3840 x
# 1440 projection, 5 times over); what lies below (``wa`` and ``wb`` at
# 3840 x 15, the taps, the scales) is 0.4M of the 766.2M together. At the
# rehearsal's sizes nothing reaches it and the gradient goes whole.
REDUCED_FROM = 2 ** 20


def _reduced(leaf):
    """Whether ``first_gradient`` hands this leaf (an array or its shape)
    over as its columns' norms."""
    return len(leaf.shape) == 2 and math.prod(leaf.shape) >= REDUCED_FROM


def layers(config):
    """The kinds of the layers that are run."""
    return config["layer_types"][:config["num_layers"]]


def causal_pairs(seq):
    """Query-key pairs of one causal sequence: every j <= i."""
    return seq * (seq + 1) // 2


def scan_pass_work(tokens, heads, d_k, d_v, chunk):
    """``(flops, bytes)`` of one forward pass of the gated delta rule over
    ``tokens`` tokens of ``heads`` heads, 2 FLOPs a multiply-add. A chunk
    of C tokens: ``q k^T`` and ``k k^T`` (C^2 d_k each), the triangular
    solve as C^2 (d_k + d_v), the two C x C by C x d_v products, and the
    three d_k x d_v products a token against the state. Bytes: q, k, v and
    o once in bf16, g and beta once in float32, and one float32 state a
    chunk."""
    per_token = chunk * (2 * d_k + (d_k + d_v) + 2 * d_v) + 3 * d_k * d_v
    flops = 2 * heads * tokens * per_token
    nbytes = heads * tokens * ((2 * d_k + 2 * d_v) * 2 + 2 * 4) \
        + heads * math.ceil(tokens / chunk) * d_k * d_v * 4
    return flops, nbytes


def scan_passes(config):
    """Forward passes' worth of the scan in one step of one layer:
    forward, the block's recomputation where it is on, and the backward
    pass at twice the forward."""
    return 4 if config["remat"] else 3


def _layer_pass_work(config, tokens):
    """:func:`scan_pass_work` at the configuration's heads and widths and
    the program's chunk."""
    from horovod_tpu.ops.linear_attention import CHUNK

    return scan_pass_work(
        tokens, config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["linear_value_head_dim"],
        CHUNK)


def scan_work_per_step(config, batch, seq):
    """``(flops, bytes)`` of everything the step computes under the
    scan's scope, recomputation counted: every linear layer, every pass."""
    flops, nbytes = _layer_pass_work(config, batch * seq)
    times = layers(config).count(LINEAR) * scan_passes(config)
    return times * flops, times * nbytes


def matrix_parameters(config):
    """``(linear mixer, full mixer, MLP)``: the parameters of one layer's
    matrices, by part, at the heads held."""
    hidden = config["hidden_size"]
    heads = config["linear_num_value_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    linear = hidden * (2 * heads * d_k + 2 * heads * d_v + 2 * heads) \
        + heads * d_v * hidden
    full = 4 * hidden * config["num_attention_heads"] * config["head_dim"]
    return linear, full, 3 * hidden * config["intermediate_size"]


def train_flops_per_step(config, batch, seq):
    """Forward plus backward FLOPs of one step, from shapes, recomputation
    not counted: 6 x tokens x the matrices every token meets (each layer's
    mixer at the heads held and its MLP, the head over the vocabulary
    slice); three forward passes' worth of the delta rule's scan a linear
    layer; and for a full layer 12 x head width x heads x the causal
    pairs (scores and context, forward and twice backward). The
    convolutions and the norms are not counted."""
    linear, full, mlp = matrix_parameters(config)
    kinds = layers(config)
    tokens = batch * seq
    dense = 6.0 * tokens * (
        kinds.count(LINEAR) * linear + kinds.count(FULL) * full
        + len(kinds) * mlp + config["hidden_size"] * config["vocab_size"])
    scan = 3.0 * kinds.count(LINEAR) * _layer_pass_work(config, tokens)[0]
    attention = 12.0 * config["head_dim"] * config["num_attention_heads"] \
        * batch * kinds.count(FULL) * causal_pairs(seq)
    return dense + scan + attention


def draw_shapes(shapes):
    """The tree the harness fills from the seed: the model's own, with
    each leaf of :data:`DRAWN_HERE` under the name of a leaf it has a rule
    for."""
    if not isinstance(shapes, dict):
        return shapes
    return {name: {"kernel": sub} if name in DRAWN_HERE
            else draw_shapes(sub) for name, sub in shapes.items()}


def starting_weights(config, draws):
    """The seeded weights as training starts from them (the
    configuration's ``assumed.init``). ``wo`` and ``w_down``, which write
    into the residual stream, are scaled by 1/sqrt(2 x published layers).
    ``A_log``, ``dt_bias`` and the convolutions' taps are no normal
    draws: the harness's normal draw goes through the normal's
    distribution function to a uniform ``u`` in (0, 1), and ``A_log =
    log(1 + 15 u)``, ``dt_bias`` is the inverse softplus of a step ``0.001
    x 100^u``, a tap is ``u - 1/2``."""
    import jax.numpy as jnp
    from jax.scipy.special import ndtr

    std = config["init"]["kernel"]
    residual = (2.0 * config["published"]["num_hidden_layers"]) ** -0.5

    def uniform(x):
        return ndtr(x / std)

    def walk(name, sub):
        if not isinstance(sub, dict):
            return sub
        if name == "A_log":
            return jnp.log1p(15.0 * uniform(sub["kernel"]))
        if name == "dt_bias":
            dt = 0.001 * 100.0 ** uniform(sub["kernel"])
            return dt + jnp.log(-jnp.expm1(-dt))
        if name.startswith("conv_"):
            return {"kernel": uniform(sub["kernel"]) - 0.5}
        if name in ("wo", "w_down"):
            return {"kernel": sub["kernel"] * residual}
        return {n: walk(n, s) for n, s in sub.items()}

    return walk("", draws)


def model_config(config):
    import jax.numpy as jnp

    from horovod_tpu.models.olmo_hybrid import OlmoHybridConfig

    held = tuple(config["deployment"]["heads_held"])
    counts = {config[key] for key in (
        "num_attention_heads", "num_key_value_heads",
        "linear_num_key_heads", "linear_num_value_heads")}
    if counts != {len(held)}:
        raise ValueError("the four head counts count the heads held")
    if not config["linear_allow_neg_eigval"]:
        raise ValueError("the program's beta is 2 sigmoid, in (0, 2), as "
                         "linear_allow_neg_eigval true has it")
    return OlmoHybridConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_layers"],
        layer_types=tuple(config["layer_types"]),
        mlp_hidden=config["intermediate_size"],
        num_heads=config["published"]["num_attention_heads"],
        head_dim=config["head_dim"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        heads_held=held,
        norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]), remat=config["remat"])


def build(config, traffic, mesh):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import OlmoHybridLM, chunked_causal_lm_loss
    from horovod_tpu.ops.attention import make_attention_fn

    opt = config["optimizer"]
    cfg = model_config(config)
    model = OlmoHybridLM(cfg, attention_fn=make_attention_fn(causal=True))
    seq = traffic["sequence_length"]
    batch = traffic["per_chip_batch"] * mesh.size
    tx = hvd.DistributedOptimizer(
        optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                    eps=opt["eps"], weight_decay=opt["weight_decay"]),
        axis_name="data")

    def loss_fn(p, ids):
        hidden, stats = model.apply({"params": p}, ids, return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, p["lm_head"]["kernel"], ids,
            num_chunks=config["loss_chunks"]), stats

    def train_step(state, data):
        p, opt_state, _ = state
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, *data)
        updates, opt_state = tx.update(grads, opt_state, p)
        return (optax.apply_updates(p, updates), opt_state, stats), \
            hvd.allreduce(loss)

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P("data")), out_specs=(P(), P()),
        check_vma=False), donate_argnums=(0,))

    weight_shapes = draw_shapes(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, seq), jnp.int32))["params"]))
    no_stats = jnp.zeros((layers(config).count(LINEAR), 3), jnp.float32)
    start = functools.partial(starting_weights, config)
    reduced = sum(_reduced(leaf) for leaf in jax.tree.leaves(weight_shapes))
    if reduced:
        # Only at the cell's sizes: the rehearsal's gradient goes whole.
        judged = [name for name in manifest.load_module(
            "reference", config["reference"]).LIMITS if "difference" in name]
        if judged:
            raise ValueError(
                f"{judged}: this cell's first gradient is reduced to column "
                "norms, from which no difference can be read")
        print(f"[check] first_gradient: {reduced} matrices of {REDUCED_FROM} "
              "elements or more are handed over as their columns' norms; "
              "read_gaps.py's first_gradient_difference numbers are void "
              "here", flush=True)

    def first_gradient(state):
        # Adam's first moment after one step from zero is (1 - b1) x the
        # gradient the optimizer got. A large matrix goes to the harness
        # as the norms of its columns, a vector whose norm is the
        # matrix's: the harness compares leaf norms and keeps what it is
        # handed beside the state (the module's docstring).
        def leaf(mu):
            g = mu / (1.0 - opt["b1"])
            if _reduced(g):
                return jnp.sqrt(jnp.sum(g * g, axis=0))
            return g

        return jax.tree.map(leaf, state[1][0].mu)

    def init_state(draws):
        weights = start(draws)
        return weights, tx.init(weights), no_stats

    return training.Workbench(
        step=step,
        weight_shapes=weight_shapes,
        init_state=init_state,
        weight_params=start,
        params_of=lambda state: state[0],
        first_gradient=first_gradient,
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
    """``training.run`` with the linear layers' three numbers of the last
    checked step printed. The harness asks for the parameters of the
    state once, after the last checked step; the numbers ride in the same
    state."""
    seen = {}

    def build_keeping_stats(config, traffic, mesh):
        bench = build(config, traffic, mesh)
        params_of = bench.params_of

        def params_and_stats(state):
            seen["stats"] = np.asarray(state[2]).tolist()
            return params_of(state)

        bench.params_of = params_and_stats
        return bench

    out = training.run(ctx, build_keeping_stats)
    lowest, mean, norm = zip(*seen["stats"])
    print("[linattn] last checked step, by linear layer: smallest decay "
          + ", ".join(f"e^{g:.4g}" for g in lowest)
          + "; mean decay a token "
          + ", ".join(f"{math.exp(g):.6g}" for g in mean)
          + "; largest state norm after the last token "
          + ", ".join(f"{n:.6g}" for n in norm), flush=True)
    return out
