"""Plain reference for the ``olmo-hybrid-7b`` configuration: the Olmo-Hybrid
block with a next-token loss and AdamW, written out in ``jax.numpy``
float32 at ``highest`` matmul precision. It imports nothing of the
program: no flax module, no kernel, no chunked scan, no ``shard_map``, no
``DistributedOptimizer``, no optax.

It follows the public ``config.json`` of ``allenai/Olmo-Hybrid-7B``, whose
linear layers' keys are those of flash-linear-attention's ``GatedDeltaNet``
(Gated DeltaNet, arXiv:2412.06464). For layer ``l`` with input ``x``
(tokens x 3840)::

    h = x + RMSNorm(Mixer_l(x))
    y = h + RMSNorm(MLP(h)),   MLP(h) = W_down(silu(W_gate h) * (W_up h))

a final RMSNorm before the untied head, ``rms_norm_eps`` 1e-6, no bias
anywhere. ``layer_types[l]`` picks the mixer.

``"full_attention"``: ``q, k, v = W_q x, W_k x, W_v x``; ``q`` and ``k``
each pass an RMSNorm with one learned scale over the whole projection
(all heads' columns together); heads of width 128; causal softmax
attention at scale 128^-1/2, **no rotary embedding**; ``W_o``.

``"linear_attention"``, head h of H, d_k = 96, d_v = 192::

    q~, k~, v~ = W_q x, W_k x, W_v x          (H*96, H*96, H*192 columns)
    q, k, v    = silu(conv4(q~)), silu(conv4(k~)), silu(conv4(v~))
                 conv4: per channel, taps on tokens t-3..t, zero before
                 the sequence, no bias
    q^ = q/||q||_2 * d_k^-1/2,  k^ = k/||k||_2   per head and token
                 (||x||_2 = sqrt(sum x^2 + 1e-6))
    beta_t = 2 * sigmoid(W_b x)_h             (allow_neg_eigval: in (0, 2))
    g_t    = -exp(A_log_h) * softplus((W_a x)_h + dt_bias_h)
    alpha_t = exp(g_t) in (0, 1)
    S_t    = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k^_t) k^_t^T
             S in R^{d_v x d_k}, S_0 = 0
    o_t    = S_t q^_t
    y      = W_o [ RMSNorm_{d_v}(o_t) * silu(W_g x) ]

the norm per head with one learned ``d_v`` scale. The recurrence runs
**token by token** exactly as written, a ``lax.scan`` over tokens
recomputed in blocks of tokens in the backward pass (a state is H x 192 x
96 floats: 8,192 of them a layer would not fit); attention is an explicit
masked softmax over all keys, in blocks of queries.

Departures from the published description, each stated in the
configuration file (``assumed``, ``deployment``):

* the mixers hold the heads the parameters have columns for (15 of 30:
  one chip of the two that share each layer), and what the other chip's
  heads would add to ``W_o``'s sum is left out: the partial result goes
  into the norm and on to the next layer, and the full layers' q/k norm is
  over the held columns. The vocabulary is the held slice, the depth one
  period. Every size but the widths is read off the parameters' shapes, so
  the same function given the whole layer's parameters is the whole layer;
* the block's norm placement, the q/k norm and the absence of a rotary
  embedding are the family's convention (the config has no key for them);
* AdamW as optax's default: decay on every parameter, no schedule.

The loss is a mean over every position but each sequence's last, so a
replica's shard is taken sequence by sequence inside one gradient. Data
parallelism is Horovod's: each replica's own mean, gradients averaged, one
update. AdamW's two moments live on the host between steps and the first
gradient is returned on the host: at 16 bytes a parameter the program's
state fills the chip, and the reference beside the caller's copy of the
seeded weights would not fit with them on the device.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference import precision as precision_of

QUERY_BLOCK = 1024
HEAD_BLOCK = 2048
TOKEN_BLOCK = 128
HIGHEST = lax.Precision.HIGHEST
mm = functools.partial(jnp.einsum, precision=HIGHEST)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _attention(rnd, q, k, v):
    """Causal softmax attention of one sequence, q, k, v (S, H, D): a
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


def _full_attention(rnd, p, x, config):
    eps, width = config["rms_norm_eps"], config["head_dim"]
    q, k, v = (mm("sd,de->se", rnd(x), rnd(p[n]["kernel"]))
               for n in ("wq", "wk", "wv"))
    q = _rms_norm(q, p["q_norm"]["scale"], eps)
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    heads = lambda a: a.reshape(a.shape[0], -1, width)      # noqa: E731
    ctx = _attention(rnd, heads(q), heads(k), heads(v))
    return mm("se,ed->sd", rnd(ctx.reshape(q.shape)), rnd(p["wo"]["kernel"]))


def _conv_silu(x, taps):
    """Channel c of token t: silu(sum_i taps[i, c] x[t - 3 + i, c]), with
    zeros before the sequence."""
    n, seq = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((n - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[i:i + seq] * taps[i] for i in range(n)))


def _delta_rule(rnd, q, k, v, g, beta):
    """The recurrence token by token: q, k (S, H, d_k), v (S, H, d_v),
    g and beta (S, H). Returns o (S, H, d_v)."""
    seq, heads, d_k = q.shape
    block = math.gcd(seq, TOKEN_BLOCK)

    def token(state, xs):               # state (H, d_v, d_k)
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[:, None, None] * state
        write = beta_t[:, None] * (
            v_t - mm("hed,hd->he", rnd(state), rnd(k_t)))
        state = state + mm("he,hd->hed", rnd(write), rnd(k_t))
        return state, mm("hed,hd->he", rnd(state), rnd(q_t))

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    blocks = jax.tree.map(
        lambda a: a.reshape((seq // block, block) + a.shape[1:]),
        (q, k, v, g, beta))
    _, o = lax.scan(
        tokens, jnp.zeros((heads, v.shape[-1], d_k), jnp.float32), blocks)
    return o.reshape(seq, heads, -1)


def _linear_attention(rnd, p, x, config):
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    project = lambda n: mm("sd,de->se", rnd(x),      # noqa: E731
                           rnd(p[n]["kernel"]))
    heads = lambda a, width: a.reshape(a.shape[0], -1, width)  # noqa: E731
    l2 = lambda a: a * lax.rsqrt(      # noqa: E731
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q = l2(heads(_conv_silu(project("wq"), p["conv_q"]["kernel"]), d_k)) \
        * d_k ** -0.5
    k = l2(heads(_conv_silu(project("wk"), p["conv_k"]["kernel"]), d_k))
    v = heads(_conv_silu(project("wv"), p["conv_v"]["kernel"]), d_v)
    beta = 2.0 * jax.nn.sigmoid(project("wb"))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(project("wa") + p["dt_bias"])
    o = _delta_rule(rnd, q, k, v, g, beta)
    o = _rms_norm(o, p["o_norm"]["scale"], config["rms_norm_eps"]) \
        * jax.nn.silu(heads(project("wg"), d_v))
    return mm("se,ed->sd", rnd(o.reshape(o.shape[0], -1)),
              rnd(p["wo"]["kernel"]))


def _layer(rnd, p, x, config, kind):
    eps = config["rms_norm_eps"]
    mixer = {"linear_attention": _linear_attention,
             "full_attention": _full_attention}[kind]
    h = x + _rms_norm(mixer(rnd, p["mixer"], x, config),
                      p["mixer_norm"]["scale"], eps)
    hidden = jax.nn.silu(mm("sd,df->sf", rnd(h), rnd(p["w_gate"]["kernel"]))) \
        * mm("sd,df->sf", rnd(h), rnd(p["w_up"]["kernel"]))
    return h + _rms_norm(
        mm("sf,fd->sd", rnd(hidden), rnd(p["w_down"]["kernel"])),
        p["mlp_norm"]["scale"], eps)


def sequence_nll_sum(params, ids, rnd, config):
    """Sum over one sequence's positions but the last of the next token's
    negative log-likelihood."""
    x = params["tok_embeddings"]["embedding"][ids]
    for i in range(config["num_layers"]):
        x = jax.checkpoint(functools.partial(
            _layer, rnd, config=config, kind=config["layer_types"][i]))(
            params[f"layer_{i}"], x)
    x = _rms_norm(x, params["final_norm"]["scale"], config["rms_norm_eps"])
    head = params["lm_head"]["kernel"]
    block = math.gcd(ids.shape[0], HEAD_BLOCK)
    targets = jnp.roll(ids, -1)
    scored = jnp.arange(ids.shape[0]) < ids.shape[0] - 1

    @jax.checkpoint
    def positions(start):
        xb = lax.dynamic_slice_in_dim(x, start, block, axis=0)
        tb = lax.dynamic_slice_in_dim(targets, start, block, axis=0)
        logits = mm("sd,dv->sv", rnd(xb), rnd(head))
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * lax.dynamic_slice_in_dim(scored, start, block))

    return jnp.sum(lax.map(positions, jnp.arange(0, ids.shape[0], block)))


def follow(params, shards, steps, config, precision="f32"):
    """Train ``steps`` steps from ``params`` on the fixed batch.

    ``shards`` is a list of ``(ids,)``, one per replica. Returns
    ``(losses, first_gradient, params)``: per step the list of every
    replica's loss, the averaged gradient of step one as the optimizer
    gets it (on the host), and the parameters after the last step."""
    opt = config["optimizer"]
    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, decay = opt["eps"], opt["weight_decay"]
    one = functools.partial(sequence_nll_sum,
                            rnd=precision_of.rounder(precision),
                            config=config)

    @jax.jit
    def shard_grad(params, ids):
        def mean_nll(params):
            total = jnp.sum(lax.map(
                jax.checkpoint(lambda row: one(params, row)), ids))
            return total / (ids.shape[0] * (ids.shape[1] - 1))

        return jax.value_and_grad(mean_nll)(params)

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
# were set from, taken on the chip at the cell's own sizes (my chip runs,
# PR 30): the largest that sound runs of the program gave over 33 seeds,
# and the smallest that the control gave on 8 seeds a type (the reference in
# the program's place in int8, this chip's faster matmul type, and in fp8).
#
# This cell's three steps are violent: AdamW at 1e-4 with no warm-up moves
# every one of 766M parameters by 1e-4 a step in its gradient's direction,
# and the loss on the one fixed sequence falls 10.2 -> 1.3-1.8 -> 0.005. So
# the losses after the first are read wide, and each limit lies between its
# two readings with two to four times of room on both sides:
#
# * loss_step2: sound 0.0137 at most (0.0003-0.0137, median 0.003), fp8 no
#   less than 0.218, int8 0.45: the first update, made from the first
#   gradient, is where a lower precision shows, and the one number that
#   catches both controls on every seed.
# * loss_step3, a gap of a number near zero at the end of that path: sound
#   0.42 at most (0.002-0.42, median 0.07), int8 no less than 3.96 (3.96-46),
#   the decay left out 95.7; fp8 reads 0.45-1.1 and is caught by loss_step2.
#   What spreads is the ratio of the two losses, 1.42 at most sound and
#   4.96 at least in int8: the limit is their geometric middle, 2.6.
# * the parameters' change, held against int8 and against a step that
#   returns its state unchanged (which reads 1), with the more room above
#   the sound readings, since fresh seeds read higher (the 25th seed read
#   a third above the first 24): by its worst matrix sound 0.0036 at most
#   (0.0011-0.0036) against int8's 0.049; by its median matrix 0.00085
#   against 0.0038; over all leaves 0.00069 against 0.0036. fp8 reads
#   0.0051, 0.0011 and 0.0017 at least, about the limits themselves, and is
#   not what they are there for.
# * loss_step1: sound 9.1e-5 at most (4.5e-6 to 9.1e-5: bf16 on a loss of
#   10.2). A control moves it little (int8 2.0e-5 to 9.0e-4, fp8 7.9e-5 to
#   4.3e-4). It is the number that catches a forward pass that is another
#   function: each of the four broken steps of
#   tests/benchmark/test_control_olmo_hybrid.py reads 9.5e-4 or more (the
#   convolution shifted by a token 1.3e-3, which moves little else).
#
# The first gradient's norms spread more than fp8 moves them (worst matrix:
# sound 0.0011-0.031, the worst leaf a linear layer's wk or wq, where fp8
# reads 0.017 at least and int8 0.115; over all leaves sound 0.0045 at most,
# fp8 0.0008, int8 0.0075), so their limits stand at three times the sound
# runs' largest, against the fault each is there to catch: a gradient
# scaled or not averaged, a decay or a beta that is another (the decay left
# out reads 2.1 and 0.54, beta not doubled 0.18 and 0.066).
LIMITS = {
    "loss_step1": 2.5e-4,
    "loss_step2": 0.06,
    "loss_step3": 1.6,
    "first_gradient_worst_matrix": 0.1,
    "first_gradient_global": 0.015,
    "param_change_worst_matrix": 0.012,
    "param_change_median_matrix": 0.002,
    "param_change_global": 0.0018,
}
# At the rehearsal's tiny sizes on the CPU (hidden 64, 2 of 4 heads held,
# one sequence of 256 in four chunks of 64; 8 seeds, both controls on each)
# the numbers lie elsewhere. Four separate, and each limit lies between
# its two readings: sound runs reach 0.0089 by the worst matrix's first
# gradient where int8 gives no less than 0.0238 and fp8 0.0209; 0.0063 by
# the worst matrix's change against 0.0170 and 0.0313; 0.00066 by the
# median matrix's change against 0.0069 and 0.0205; 0.00048 by the change
# over all leaves against 0.0102 and 0.0181. The losses (2.6e-5, 2.2e-5,
# 5.0e-5 sound; a control's smallest lies under each) and the gradient's
# norm over all leaves (0.0061 sound, and less under either control)
# hardly move under a lower precision and stand at three times the sound
# runs' largest. That norm is a coherent 0.6% too large at this size and
# no rounding noise: 1/255, the mean's weight of one position of the 256,
# rounds up by 0.39% in bf16 (at the cell's 8,192 it rounds down by
# 0.012%); in float32 the program is the reference to 2e-7.
REHEARSAL_LIMITS = {
    "loss_step1": 8e-5,
    "loss_step2": 7e-5,
    "loss_step3": 1.5e-4,
    "first_gradient_worst_matrix": 0.014,
    "first_gradient_global": 0.018,
    "param_change_worst_matrix": 0.0105,
    "param_change_median_matrix": 0.0022,
    "param_change_global": 0.0022,
}
CONTROL = "int8"
