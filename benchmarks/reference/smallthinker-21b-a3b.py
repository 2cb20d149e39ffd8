"""Plain reference for the ``smallthinker-21b-a3b`` configuration: the
SmallThinker block with a next-token loss and AdamW, written out in
``jax.numpy`` float32 at ``highest`` matmul precision. It imports nothing
of the program: no flax module, no kernel, no ``ragged_dot``, no
``shard_map``, no ``DistributedOptimizer``, no optax.

It follows the SmallThinker report (arXiv:2507.20984) and the model's
public ``config.json``. For layer ``l`` with input ``x`` (tokens x 2560):

* ``r = x W_r``, from the layer's input before the attention's norm;
* ``a = x + Attn_l(RMSNorm(x))``: 28 query heads over 4 key/value heads of
  width 128, causal, scale 1/sqrt(128); where ``sliding_window_layout[l]``
  is 0 every earlier key and no rotary embedding, where it is 1 the keys
  ``i - window < j <= i`` and rotate-half RoPE over the whole head width;
* ``h = RMSNorm(a)``; the chosen set is the ``k`` largest of ``r_t`` and
  the weights a softmax over the chosen logits; expert ``e`` is
  ``W_down,e (relu(W_gate,e h) * (W_up,e h))``;
* ``out = a + sum over the chosen experts HELD HERE of w_e y_e(h)``.

Attention is an explicit masked softmax over all keys, in blocks of
queries so that it fits; every held expert is applied densely to every
token and weighted, with weight zero where it was not chosen. Departures
from the published description, each stated in the configuration file
(``assumed``, ``deployment``):

* only the experts the configuration holds (ids 0-15 of 64) add to a
  layer's result, and that partial result goes on to the next layer: the
  chip's share of a four-chip deployment, with nothing standing in for the
  other chips; the vocabulary is the held slice, the depth one period;
* no attention bias; the router reads the un-normed input (as the public
  llama.cpp graph of this model has it); no auxiliary balancing loss;
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
HIGHEST = lax.Precision.HIGHEST
mm = functools.partial(jnp.einsum, precision=HIGHEST)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """Rotate-half rotary embedding of (S, H, D) at positions 0..S-1."""
    seq, _, width = x.shape
    half = width // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(rnd, q, k, v, window):
    """Causal softmax attention of one sequence: q (S, H, D), k and v
    (S, Hkv, D), a block of queries at a time against every key."""
    seq, heads, width = q.shape
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = math.gcd(seq, QUERY_BLOCK)

    @jax.checkpoint
    def queries(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = mm("qhd,khd->hqk", rnd(qb), rnd(k)) / math.sqrt(width)
        qi = start + jnp.arange(block)[:, None]
        kj = jnp.arange(seq)[None, :]
        seen = kj <= qi
        if window is not None:
            seen = seen & (kj > qi - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", rnd(probs), rnd(v))

    out = lax.map(queries, jnp.arange(0, seq, block))
    return out.reshape(seq, heads, width)


def _layer(rnd, p, x, config, windowed, rotated):
    eps, chosen_k = config["rms_norm_eps"], \
        config["moe_num_active_primary_experts"]
    held = config["deployment"]["experts_held"]
    # The router, ahead of attention, on the un-normed input.
    r = mm("sd,de->se", rnd(x), rnd(p["router"]["kernel"]))
    a = p["attention"]
    h = _rms_norm(x, p["attention_norm"]["scale"], eps)
    q, k, v = (mm("sd,dhk->shk", rnd(h), rnd(a[n]["kernel"]))
               for n in ("wq", "wk", "wv"))
    if rotated:
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    ctx = _attention(rnd, q, k, v,
                     config["sliding_window_size"] if windowed else None)
    x = x + mm("shk,hkd->sd", rnd(ctx), rnd(a["wo"]["kernel"]))
    h = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    # The k largest logits of a token, weighted by a softmax over them.
    kth = lax.stop_gradient(jnp.sort(r, axis=-1)[:, -chosen_k])[:, None]
    weights = jax.nn.softmax(jnp.where(r >= kth, r, -jnp.inf), axis=-1)

    @jax.checkpoint
    def expert(h, gate, up, down):
        hidden = jax.nn.relu(mm("sd,df->sf", rnd(h), rnd(gate))) \
            * mm("sd,df->sf", rnd(h), rnd(up))
        return mm("sf,fd->sd", rnd(hidden), rnd(down))

    for slot, e in enumerate(held):
        x = x + weights[:, e:e + 1] * expert(
            h, p["w_gate"]["kernel"][slot], p["w_up"]["kernel"][slot],
            p["w_down"]["kernel"][slot])
    return x


def sequence_nll_sum(params, ids, rnd, config):
    """Sum over one sequence's positions but the last of the next token's
    negative log-likelihood."""
    x = params["tok_embeddings"]["embedding"][ids]
    for i in range(config["num_layers"]):
        x = jax.checkpoint(functools.partial(
            _layer, rnd, config=config,
            windowed=config["sliding_window_layout"][i],
            rotated=config["rope_layout"][i]))(params[f"layer_{i}"], x)
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
# PR 26): the largest that sound runs of the program gave over 9 seeds, and
# the smallest that the control gave on 2 seeds a type (the reference in the
# program's place in int8, this chip's faster matmul type, and in fp8).
#
# Four numbers separate, and each limit lies between its two readings with
# room on both sides. first_gradient_worst_matrix: sound 0.00071 at most,
# fp8 no less than 0.0058, int8 0.0076 (the worst leaf is a router: which
# experts a token gets is decided on small differences between logits, and
# a lower precision moves many of them). The parameters' change over all
# leaves: 0.00063 against fp8's 0.0094 and int8's 0.049; by its worst
# matrix 0.0019 against 0.017 and 0.35; by its median matrix 0.000057
# against 0.0020 and 0.0057 (Adam divides by the gradient's own size, so a
# rounding that flips the sign of small entries moves every leaf). The
# others move less under a lower precision and stand at about three times
# the sound runs' largest, against the fault each is there to catch: the
# losses against a part of the batch or of the band left out (sound 8.9e-6,
# 1.9e-5, 2.6e-5; with the window ignored step two reads 1.0e-4, with one
# expert's rows dropped 8.3e-4), the gradient's norm over all leaves
# against a gradient scaled or not averaged (0.00013).
LIMITS = {
    "loss_step1": 3e-5,
    "loss_step2": 6e-5,
    "loss_step3": 8e-5,
    "first_gradient_worst_matrix": 0.002,
    "first_gradient_global": 0.0004,
    "param_change_worst_matrix": 0.006,
    "param_change_median_matrix": 0.00035,
    "param_change_global": 0.0025,
}
# At the rehearsal's tiny sizes on the CPU (hidden 64, 3 of 8 experts held,
# sequence 1024; 8 seeds, both controls on each) the numbers lie elsewhere:
# sound runs reach 0.0049 by the worst matrix's first gradient where fp8
# gives no less than 0.0155 and int8 0.111; 0.00050 by the parameters'
# change over all leaves against int8's 0.0033 and fp8's 0.0089; 0.0046 by
# its worst matrix against 0.0107 and 0.0188; 0.00030 by its median matrix
# against 0.0018 and 0.0085. Each limit lies between its two readings. The
# losses (3.4e-6, 5.3e-6, 9.7e-6 sound) and the gradient's norm over all
# leaves (0.00089) hardly move under a lower precision and stand at three
# times the sound runs' largest.
REHEARSAL_LIMITS = {
    "loss_step1": 1e-5,
    "loss_step2": 1.6e-5,
    "loss_step3": 3e-5,
    "first_gradient_worst_matrix": 0.009,
    "first_gradient_global": 0.0027,
    "param_change_worst_matrix": 0.007,
    "param_change_median_matrix": 0.0008,
    "param_change_global": 0.0013,
}
CONTROL = "int8"
