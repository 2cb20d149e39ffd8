"""Plain reference for the ``ouro-2.6b`` configuration: the looped LM with
its exit gate, its loss and AdamW, written out in ``jax.numpy`` float32
at ``highest`` matmul precision. It imports nothing of the program: no
flax module, no kernel, no ``remat`` of the program's, no ``custom_vjp``,
no chunked loss, no ``shard_map``, no ``DistributedOptimizer``, no optax,
and none of ``horovod_tpu.models``.

It follows ``ByteDance/Ouro-2.6B``'s public ``config.json`` and modelling
code, and the first-stage objective of "Scaling Latent Reasoning via
Looped Language Models" (arXiv:2510.25741). For block ``l`` with input
``x`` (tokens x 2048), RMSNorm eps 1e-6 with a float32 scale, every
product without bias:

* ``a = W_o CausalSoftmaxAttention(RoPE(W_q N1(x)), RoPE(W_k N1(x)), W_v
  N1(x))``: 16 heads of 128 over as many K/V heads, rotate-half over the
  whole head at ``1e6^(-2i/128)``, positions 0..S-1, scores at
  ``1/sqrt(128)``; ``x' = x + N2(a)``;
* ``y = x' + N4(W_down(silu(W_gate N3(x')) * (W_up N3(x'))))``, 5632 wide;
* a pass: ``h_0 = Embed(ids)``, ``h_t = FinalNorm(Block_L(...
  Block_1(h_{t-1})))`` for ``t = 1..4``: **the four passes are unrolled
  here over the one set of parameters and autodiff sums a shared
  parameter's gradient**; the final norm ends every pass and its output
  goes on into the next; the same positions in every pass;
* exits: ``g_t = w_g . h_t + b_g`` (one gate for the passes), ``lam_t =
  sigmoid(g_t)``, ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for ``t < 4`` and
  ``p_4 = prod_{j<4} (1 - lam_j)``: the last exit takes what is left;
* the loss: the mean over the ``B (S - 1)`` positions that have a target
  of ``sum_t p_t nll_t - beta H(p)``, ``nll_t = lse(W_head h_t) - (W_head
  h_t)[target]``, ``H(p) = -sum_t p_t log p_t``, ``beta`` the
  configuration's ``assumed.exit_entropy_beta``.

Departures from the published description: none in the function. The
depth is the configuration's cut (``num_layers`` of the published 48);
``early_exit_threshold`` is an inference key and is not read; the paper's
second training stage (the gate alone, against the loss's improvement a
pass) is not the objective here. What the config leaves open (``beta``,
no bias outside the gate) is in the configuration file's ``assumed``.

Attention is an explicit masked softmax in blocks of queries so that a
sequence of 8192 fits; the head is applied in blocks of positions, every
exit's logits of a block at a time. ``sequence_loss_sum`` is the loss as
one function and takes one parameter tree a pass where a test wants the
passes unshared (``passes``): the sum of the unshared copies' gradients is
the shared leaf's. ``follow`` takes its gradient by ``loss_sum_and_grad``,
the same reverse sweep walked a pass at a time so that it fits the chip
beside what the harness holds.

Data parallelism is Horovod's: each replica's own mean, gradients
averaged, one update. AdamW as optax's default with decay on every
parameter; its two moments live on the host between steps and the first
gradient is returned on the host, as in the other decoder references.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference import precision as precision_of

QUERY_BLOCK = 512       # x 8192 keys x 16 heads x 4 bytes: 0.27 GB of scores
HEAD_BLOCK = 2048       # x 49152 logits x 4 bytes: 0.4 GB an exit
HIGHEST = lax.Precision.HIGHEST
mm = functools.partial(jnp.einsum, precision=HIGHEST)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rotate(x, theta):
    """Rotate-half over the whole head of (S, H, D) at positions
    0..S-1."""
    seq, _, width = x.shape
    half = width // 2
    inv_freq = float(theta) ** (
        -2.0 * np.arange(half, dtype=np.float64) / width)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _attention(rnd, q, k, v):
    """Causal softmax attention of one sequence, q, k and v (S, H, D): a
    block of queries at a time against every key."""
    seq, heads, width = q.shape
    block = math.gcd(seq, QUERY_BLOCK)

    @jax.checkpoint
    def queries(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = mm("qhd,khd->hqk", rnd(qb), rnd(k)) / math.sqrt(width)
        seen = jnp.arange(seq)[None, :] <= start + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", rnd(probs), rnd(v))

    out = lax.map(queries, jnp.arange(0, seq, block))
    return out.reshape(seq, heads, width)


def _layer(rnd, p, x, config):
    """One block on one sequence ``x`` (S, hidden): a norm before and
    after each sublayer."""
    eps = config["rms_norm_eps"]
    a = p["attention"]
    z = _rms_norm(x, p["input_layernorm"]["scale"], eps)
    q, k, v = (mm("sd,dhk->shk", rnd(z), rnd(a[n]["kernel"]))
               for n in ("wq", "wk", "wv"))
    q, k = _rotate(q, config["rope_theta"]), _rotate(k, config["rope_theta"])
    mixed = mm("shk,hkd->sd", rnd(_attention(rnd, q, k, v)),
               rnd(a["wo"]["kernel"]))
    x = x + _rms_norm(mixed, p["input_layernorm_2"]["scale"], eps)
    z = _rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    hidden = jax.nn.silu(mm("sd,df->sf", rnd(z), rnd(p["w_gate"]["kernel"]))) \
        * mm("sd,df->sf", rnd(z), rnd(p["w_up"]["kernel"]))
    out = mm("sf,fd->sd", rnd(hidden), rnd(p["w_down"]["kernel"]))
    return x + _rms_norm(out, p["post_attention_layernorm_2"]["scale"], eps)


def exit_distribution(gate_logits):
    """``p`` (T, S) from the gates' logits (T, S): ``p_t = lam_t x`` what
    the passes before left, and the last exit takes what is left."""
    lam = jax.nn.sigmoid(gate_logits)
    left = jnp.ones_like(lam[0])
    p = []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def _stack_of(params):
    """What a pass reads of ``params``: the layers and the final norm."""
    return {name: sub for name, sub in params.items()
            if name.startswith("layer_") or name == "final_norm"}


def sequence_pass(stack, x, rnd, config):
    """One pass of one sequence ``x`` (S, hidden) through the layers and
    the final norm of ``stack``: the states the next pass and this pass's
    exit read."""
    for i in range(config["num_layers"]):
        x = jax.checkpoint(functools.partial(_layer, rnd, config=config))(
            stack[f"layer_{i}"], x)
    return _rms_norm(x, stack["final_norm"]["scale"], config["rms_norm_eps"])


def exits_loss_sum(trees, states, ids, rnd, config):
    """Sum over one sequence's positions but the last of ``sum_t p_t
    nll_t - beta H(p)``, from every pass's normed states (T, S, hidden);
    exit ``t`` reads the gate and the head of ``trees[t]``."""
    beta = config["assumed"]["exit_entropy_beta"]
    p = exit_distribution(jnp.stack([
        mm("sd,do->so", rnd(states[t]),
           rnd(tree["early_exit_gate"]["kernel"]))[:, 0]
        + tree["early_exit_gate"]["bias"][0]
        for t, tree in enumerate(trees)]))
    seq = ids.shape[0]
    block = math.gcd(seq, HEAD_BLOCK)
    targets = jnp.roll(ids, -1)
    scored = jnp.arange(seq) < seq - 1

    @jax.checkpoint
    def positions(start):
        xb = lax.dynamic_slice_in_dim(states, start, block, axis=1)
        pb = lax.dynamic_slice_in_dim(p, start, block, axis=1)
        tb = lax.dynamic_slice_in_dim(targets, start, block, axis=0)
        total = -beta * jnp.sum(jax.scipy.special.entr(pb), axis=0)
        for t in range(len(trees)):
            logits = mm("sd,dv->sv", rnd(xb[t]),
                        rnd(trees[t]["lm_head"]["kernel"]))
            nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, tb[:, None], axis=-1)[:, 0]
            total = total + pb[t] * nll
        return jnp.sum(total * lax.dynamic_slice_in_dim(scored, start, block))

    return jnp.sum(lax.map(positions, jnp.arange(0, seq, block)))


def sequence_loss_sum(params, ids, rnd, config, passes=None):
    """The loss of one sequence of ids, summed over its positions but the
    last: the lookup, ``total_ut_steps`` passes unrolled over the one set
    of parameters, the exits. ``passes``, where given, is one parameter
    tree a pass in ``params``' place (the passes unshared; the lookup
    reads the first)."""
    trees = passes or [params] * config["total_ut_steps"]
    if len(trees) != config["total_ut_steps"]:
        raise ValueError("one parameter tree a pass")
    x = trees[0]["tok_embeddings"]["embedding"][ids]
    states = []
    for tree in trees:
        x = sequence_pass(_stack_of(tree), x, rnd, config)
        states.append(x)
    return exits_loss_sum(trees, jnp.stack(states), ids, rnd, config)


def loss_sum_and_grad(rnd, config):
    """``f(params, ids) -> (loss, gradient)`` of ``sequence_loss_sum`` for
    one sequence, the reverse sweep walked a pass at a time: the exits'
    program hands back the gradient of every pass's states, then each
    pass, last to first, is one ``jax.vjp`` whose part of the shared
    parameters' gradient is ADDED to the sum so far before the pass
    before it starts. The same function and the same gradient as
    ``jax.value_and_grad`` of ``sequence_loss_sum``
    (``tests/test_ouro.py`` holds the two together); as ONE program the
    compiler keeps the 24 applications' recomputed activations and weight
    gradients alive side by side: 11.3 GB of temporaries compiled for a
    v5e, where the chip lets a program reserve 9.3 (my chip run, PR 48).
    Four small programs, compiled once: the compiler's least effort, as
    the other decoder references (each runs a few times, and how fast is
    not measured)."""
    passes = config["total_ut_steps"]
    jit = functools.partial(jax.jit, compiler_options={
        "exec_time_optimization_effort": -1.0})
    embed = jit(lambda table, ids: table[ids])
    run_pass = jit(functools.partial(sequence_pass, rnd=rnd, config=config))

    @jit
    def exits(params, states, ids):
        return jax.value_and_grad(lambda params, states: exits_loss_sum(
            [params] * passes, states, ids, rnd, config), argnums=(0, 1))(
            params, states)

    @functools.partial(jit, donate_argnums=(2,))
    def back(stack, x, so_far, d_out):
        _, vjp = jax.vjp(functools.partial(sequence_pass, rnd=rnd,
                                           config=config), stack, x)
        d_stack, d_x = vjp(d_out)
        return jax.tree.map(jnp.add, so_far, d_stack), d_x

    lookup = jit(lambda d_table, ids, d_x: d_table.at[ids].add(d_x),
                 donate_argnums=(0,))

    def loss_and_grad(params, ids):
        stack = _stack_of(params)
        xs = [embed(params["tok_embeddings"]["embedding"], ids)]
        for _ in range(passes):
            xs.append(run_pass(stack, xs[-1]))
        loss, (grads, d_states) = exits(params, jnp.stack(xs[1:]), ids)
        so_far, d_x = _stack_of(grads), jnp.zeros_like(xs[0])
        for t in reversed(range(passes)):
            so_far, d_x = back(stack, xs[t], so_far, d_states[t] + d_x)
        grads = {**grads, **so_far}
        grads["tok_embeddings"] = {"embedding": lookup(
            grads["tok_embeddings"]["embedding"], ids, d_x)}
        return loss, grads

    return loss_and_grad


def follow(params, shards, steps, config, precision="f32"):
    """Train ``steps`` steps from ``params`` on the fixed batch.

    ``shards`` is a list of ``(ids,)``, one per replica. Returns
    ``(losses, first_gradient, params)``: per step the list of every
    replica's loss, the averaged gradient of step one as the optimizer
    gets it (on the host), and the parameters after the last step."""
    opt = config["optimizer"]
    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, decay = opt["eps"], opt["weight_decay"]
    one = loss_sum_and_grad(precision_of.rounder(precision), config)

    def shard_grad(params, ids):
        """The mean loss over a replica's positions and its gradient, a
        sequence at a time."""
        count = ids.shape[0] * (ids.shape[1] - 1)
        loss, grads = 0.0, None
        for row in ids:
            row_loss, g = one(params, row)
            loss = loss + row_loss / count
            grads = scale(g, 1.0 / count) if grads is None \
                else add(grads, g, 1.0 / count)
        return loss, grads

    scale = jax.jit(lambda a, w: jax.tree.map(lambda x: w * x, a),
                    donate_argnums=(0,))
    add = jax.jit(lambda a, b, w: jax.tree.map(
        lambda x, y: x + w * y, a, b), donate_argnums=(0,))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update_leaf(p, m, v, g, t):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (step + decay * p), m, v

    leaves, treedef = jax.tree.flatten(params)
    moments = [None] * len(leaves)      # per leaf (mu, nu), on the host
    losses, first = [], None
    for t in range(1, steps + 1):
        step_losses, grads = [], None
        for (ids,) in shards:
            loss, g = shard_grad(params, jnp.asarray(ids))
            step_losses.append(float(loss))
            grads = g if grads is None else add(grads, g, 1.0)
        if len(shards) > 1:
            grads = jax.tree.map(lambda x: x / len(shards), grads)
        losses.append(step_losses)
        if first is None:
            first = jax.device_get(grads)
        new = []
        for i, (p, g) in enumerate(zip(jax.tree.leaves(params),
                                       jax.tree.leaves(grads))):
            m, v = moments[i] or (jnp.zeros_like(p), jnp.zeros_like(p))
            # ``params`` may be the caller's own arrays: update a copy.
            p, m, v = update_leaf(jnp.array(p) if t == 1 else p,
                                  jnp.asarray(m), jnp.asarray(v), g,
                                  float(t))
            moments[i] = (np.asarray(m), np.asarray(v))
            new.append(p)
        del grads
        params = jax.tree.unflatten(treedef, new)
    return losses, first, params


# Limits of the numbers compared. PERF.md, section 2, has the readings they
# were set from, taken on the chip at the cell's own sizes (my chip runs, PR
# 48). What reads the first step alone hangs on no learning rate and was
# read on 28 sound seeds and 4 int8 seeds (the reference in the program's
# place in int8, this chip's faster matmul type); each limit lies between
# its two readings with five times of room or more either way:
# first_gradient_worst_matrix sound 0.0118 at most (a layer's wk or wq),
# int8 no less than 0.397; first_gradient_global 0.0056 against 0.220; the
# norm of the first gradient's DIFFERENCE from the reference's over all
# leaves 0.0269 against 0.670, by its worst matrix 0.0308 against 0.750
# (the two differences on the 18 seeds that kept the gradient).
# loss_step1 does not separate on every seed (sound 3.9e-5 at most; int8
# 1.8e-5 to 4.3e-4) and stands at three times the sound runs' largest
# against a forward pass that is another function. What reads all three
# steps (the later losses, the parameters' change) was read at the
# configuration's rate 1e-6 on 5 sound seeds and 2 int8 seeds and set by
# one rule, written down before the readings and applied inside the same
# chip call, ahead of 7 unseen seeds that then all read correct (largest
# of the 12 sound seeds in brackets): the geometric middle of the sound
# runs' largest and the control's smallest where they lie two and a
# quarter times apart or more, else three times the sound runs' largest.
# loss_step2 sound 2.3e-5 against int8's 1.2e-4 (2.3e-5 of 12); loss_step3
# 2.0e-5 against 6.1e-4 (2.7e-5); the parameters' change by its worst
# matrix 0.0031 against 0.121 (0.0045), by its median matrix 0.0024
# against 0.045 (0.0038); over all leaves it does not separate on every
# seed (0.0019; int8 0.0032 and 0.0227) and stands at three times the first
# five's largest (0.0026 of 12), against a step that returns its state
# unchanged (reads 1).
LIMITS = {
    "loss_step1": 0.0001,
    "loss_step2": 5.2e-05,
    "loss_step3": 0.00011,
    "first_gradient_worst_matrix": 0.06,
    "first_gradient_global": 0.03,
    "first_gradient_difference": 0.13,
    "first_gradient_difference_worst_matrix": 0.15,
    "param_change_worst_matrix": 0.019,
    "param_change_median_matrix": 0.01,
    "param_change_global": 0.0057,
}
# At the rehearsal's tiny sizes on the CPU (hidden 64, two layers run four
# times, one sequence of 256, head width 32; 6 seeds, the three controls on
# each, at the configuration's rate 1e-6; benchmarks/tools/read_gaps.py
# --rehearse-cpu) eight numbers separate, and each limit lies between its
# two readings. The norm of the first gradient's DIFFERENCE from the
# reference's separates furthest: over all leaves sound 0.0167 at most (the
# reference in bf16 0.0111), int8 no less than 0.148, fp8 0.164; by its
# worst matrix 0.0187 (bf16 0.0131) against 0.183 and 0.184: three times of
# room either way. The worst matrix's first gradient by its norm: sound
# 0.0048 (bf16 0.0037) against int8's 0.0368 and fp8's 0.0296. The
# parameters' change by its worst matrix 0.0038 (bf16 0.0021) against
# int8's 0.0188, by its median matrix 0.0020 against 0.0145, over all
# leaves 0.0018 against 0.0132: over twice either way. The second and third
# loss: 2.5e-5 and 3.0e-5 against int8's 8.9e-5 and 8.2e-5, the limits
# their geometric middles. Two do not separate and stand at two to three
# times the sound runs' largest against the fault each is there to catch:
# the gradient's norm over all leaves (sound 0.0032; int8 from 0.0036)
# against a gradient scaled or not averaged, the first loss (2.4e-5; int8
# from 7e-6) against a forward pass that is another function. Every broken
# step of tests/benchmark/test_control_ouro.py reads not correct by each of
# the first step's five.
REHEARSAL_LIMITS = {
    "loss_step1": 7e-5,
    "loss_step2": 4.7e-5,
    "loss_step3": 5e-5,
    "first_gradient_worst_matrix": 0.013,
    "first_gradient_global": 0.006,
    "first_gradient_difference": 0.05,
    "first_gradient_difference_worst_matrix": 0.06,
    "param_change_worst_matrix": 0.0084,
    "param_change_median_matrix": 0.0054,
    "param_change_global": 0.0049,
}
CONTROL = "int8"
