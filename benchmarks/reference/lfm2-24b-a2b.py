"""Plain reference for the ``lfm2-24b-a2b`` configuration: the LFM2 block
with a next-token loss and AdamW, written out in ``jax.numpy`` float32 at
``highest`` matmul precision. It imports nothing of the program: no flax
module, no kernel, no ``ragged_dot``, no ``shard_map``, no
``DistributedOptimizer``, no optax, and none of ``models/lfm2.py``,
``ops/linear_attention.py`` or ``parallel/moe.py``.

It follows ``LiquidAI/LFM2-24B-A2B``'s public ``config.json``; each
reading of a key the config leaves open is in the configuration file's
``assumed``. For a layer with input ``x`` (tokens x 2048), RMSNorm eps
1e-5, every product without bias:

* ``z = RMSNorm(x)``; a convolution layer: ``[B, C, u] = split3(z
  W_in)`` (``W_in`` 2048 x 6144), ``v[t] = w[0] (B u)[t - 2] + w[1] (B
  u)[t - 1] + w[2] (B u)[t]`` channel by channel with what lies before
  the sequence read as zero (three shifted sums, no activation), ``h = x
  + (C v) W_out``;
* an attention layer: ``q = z W_q`` as 32 heads of 64, ``k, v = z W_k, z
  W_v`` as 8; ``q`` and ``k`` RMS-normed over each head's 64 entries with
  one learned scale of 64; then rotate-half over the whole head at
  ``1e6^(-2i/64)``, positions 0..S-1; causal softmax at ``1/8``, a query
  head reading the key/value head of its group of 4; ``h = x + ctx W_o``;
* ``z = RMSNorm(h)``; a dense layer ``out = h + W_2(silu(W_1 z) * (W_3
  z))``, 11776 wide; a sparse one ``s = sigmoid(z W_r)`` (64 wide), the
  chosen set the 4 largest of ``s + b`` (``b`` the expert bias: in the
  CHOICE and nowhere else), ``w_e = s_e / (sum of the chosen s + 1e-6) x
  routed_scaling_factor``, ``out = h + sum over the chosen experts HELD
  HERE of w_e E_e(z)``, ``E_e`` a SiLU-gated MLP 1536 wide;
* a final RMSNorm, then the head, which is the embedding transposed.

Attention is an explicit masked softmax in blocks of queries so that it
fits; every held expert is applied densely to every token and weighed,
with zero where it was not chosen. Only the routed experts the
configuration holds (ids 0-7 of 64) add to a layer's result, and that
partial result goes on to the next layer: the chip's share of an
eight-chip deployment, with nothing standing in for the other chips; the
vocabulary is the held slice, the depth one dense layer and one period.
No auxiliary loss. AdamW as optax's default with decay on every
parameter but the expert bias, whose gradient is zero (the choice is no
differentiable function of it) and which no step changes: the update
that would train it by the loads is left out, here as in the program.

The loss is a mean over every position but each sequence's last, so a
replica's shard is taken sequence by sequence inside one gradient. Data
parallelism is Horovod's: each replica's own mean, gradients averaged, one
update. AdamW's two moments live on the host between steps and the first
gradient is returned on the host, as in the other decoder references.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference import precision as precision_of

QUERY_BLOCK = 512       # x 8192 keys x 32 heads x 4 bytes: 0.5 GB of scores
HEAD_BLOCK = 2048
HIGHEST = lax.Precision.HIGHEST
mm = functools.partial(jnp.einsum, precision=HIGHEST)
NORM_EPS_OF_WEIGHTS = 1e-6      # in the routing weights' normalisation


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
        seen = jnp.arange(seq)[None, :] <= start + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", rnd(probs), rnd(v))

    out = lax.map(queries, jnp.arange(0, seq, block))
    return out.reshape(seq, heads, width)


def _short_conv(rnd, x, taps):
    """``v[t] = sum_i taps[i] x[t - (K - 1) + i]`` channel by channel on
    (S, C), what lies before the sequence read as zero: K shifted sums."""
    count, seq = taps.shape[0], x.shape[0]
    x, taps = rnd(x), rnd(taps)
    out = 0.0
    for i in range(count):
        back = count - 1 - i
        shifted = x if back == 0 else jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:seq - back]], axis=0)
        out = out + taps[i] * shifted
    return out


def _gated_mlp(rnd, h, gate, up, down):
    hidden = jax.nn.silu(mm("sd,df->sf", rnd(h), rnd(gate))) \
        * mm("sd,df->sf", rnd(h), rnd(up))
    return mm("sf,fd->sd", rnd(hidden), rnd(down))


def routing_weights(scores, bias, config):
    """(S, E) weights from sigmoid ``scores``: zero off the chosen set,
    which is the largest ``num_experts_per_tok`` of ``scores + bias``; on
    it the expert's own score over the chosen scores' sum plus 1e-6,
    times the routed scale."""
    choice = scores + bias
    kth = jnp.sort(choice, axis=-1)[:, -config["num_experts_per_tok"]]
    chosen = lax.stop_gradient(choice >= kth[:, None])
    weights = jnp.where(chosen, scores, 0.0)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                         + NORM_EPS_OF_WEIGHTS)
    return weights * config["routed_scaling_factor"]


def _layer(rnd, p, x, config, kind, sparse):
    """One block on one sequence ``x`` (S, hidden): ``kind`` the mixer's
    type, ``sparse`` whether its FFN is the expert layer."""
    eps = config["norm_eps"]
    z = _rms_norm(x, p["operator_norm"]["scale"], eps)
    if kind == "conv":
        c = p["conv"]
        b_gate, c_gate, u = jnp.split(
            mm("sd,de->se", rnd(z), rnd(c["in_proj"]["kernel"])), 3, axis=-1)
        v = _short_conv(rnd, b_gate * u, c["taps"]["kernel"])
        x = x + mm("sd,de->se", rnd(c_gate * v),
                   rnd(c["out_proj"]["kernel"]))
    else:
        a = p["attention"]
        q, k, v = (mm("sd,dhk->shk", rnd(z), rnd(a[n]["kernel"]))
                   for n in ("wq", "wk", "wv"))
        theta = config["rope_parameters"]["rope_theta"]
        q = _rotate(_rms_norm(q, a["q_norm"]["scale"], eps), theta)
        k = _rotate(_rms_norm(k, a["k_norm"]["scale"], eps), theta)
        x = x + mm("shk,hkd->sd", rnd(_attention(rnd, q, k, v)),
                   rnd(a["wo"]["kernel"]))
    z = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    if not sparse:
        m = p["mlp"]
        return x + _gated_mlp(rnd, z, m["w_gate"]["kernel"],
                              m["w_up"]["kernel"], m["w_down"]["kernel"])
    scores = jax.nn.sigmoid(
        mm("sd,de->se", rnd(z), rnd(p["router"]["kernel"])))
    weights = routing_weights(scores, p["expert_bias"]["kernel"], config)
    held = jnp.asarray(config["deployment"]["experts_held"], jnp.int32)
    return x + _held_experts(rnd, z, weights[:, held], p["w_gate"]["kernel"],
                             p["w_up"]["kernel"], p["w_down"]["kernel"])


@functools.partial(jax.checkpoint, static_argnums=(0,))
def _held_experts(rnd, h, weights, gate, up, down):
    """Every held expert applied densely to every token of ``h`` (S,
    hidden) and weighed: ``weights`` (S, held) is zero where a token did
    not choose the expert; ``gate`` / ``up`` (held, hidden, width),
    ``down`` (held, width, hidden)."""
    hidden = jax.nn.silu(mm("sd,edf->esf", rnd(h), rnd(gate))) \
        * mm("sd,edf->esf", rnd(h), rnd(up))
    return mm("se,esd->sd", weights,
              mm("esf,efd->esd", rnd(hidden), rnd(down)))


def layer_kinds(config):
    """``(mixer kind, sparse)`` of each layer that is run: the published
    layers ``deployment.layers_run``, the first ``num_dense_layers``
    dense."""
    return [(config["layer_types"][published],
             i >= config["num_dense_layers"])
            for i, published in enumerate(config["deployment"]["layers_run"])]


def sequence_hidden(params, ids, rnd, config):
    """The final norm's output for one sequence of ids, (S, hidden)."""
    x = params["tok_embeddings"]["embedding"][ids]
    for i, (kind, sparse) in enumerate(layer_kinds(config)):
        x = jax.checkpoint(functools.partial(
            _layer, rnd, config=config, kind=kind, sparse=sparse))(
            params[f"layer_{i}"], x)
    return _rms_norm(x, params["final_norm"]["scale"], config["norm_eps"])


def sequence_nll_sum(params, ids, rnd, config):
    """Sum over one sequence's positions but the last of the next token's
    negative log-likelihood; the head is the embedding."""
    x = sequence_hidden(params, ids, rnd, config)
    head = params["tok_embeddings"]["embedding"]
    block = math.gcd(ids.shape[0], HEAD_BLOCK)
    targets = jnp.roll(ids, -1)
    scored = jnp.arange(ids.shape[0]) < ids.shape[0] - 1

    @jax.checkpoint
    def positions(start):
        xb = lax.dynamic_slice_in_dim(x, start, block, axis=0)
        tb = lax.dynamic_slice_in_dim(targets, start, block, axis=0)
        logits = mm("sd,vd->sv", rnd(xb), rnd(head))
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * lax.dynamic_slice_in_dim(scored, start, block))

    return jnp.sum(lax.map(positions, jnp.arange(0, ids.shape[0], block)))


def _untrained(path):
    return any(getattr(k, "key", None) == "expert_bias" for k in path)


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

    # The compiler's least effort, as the other decoder references: the
    # program runs once, and how fast is not measured.
    @functools.partial(jax.jit, compiler_options={
        "exec_time_optimization_effort": -1.0})
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

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    trained = [not _untrained(path) for path, _ in flat]
    moments = [None] * len(flat)        # per leaf (mu, nu), on the host
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
            if not trained[i]:
                new.append(jnp.array(p))
                continue
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
# were set from, taken on the chip at the cell's own sizes and at the
# configuration's rate 1e-7 (my chip runs, PR 38): the largest that sound
# runs of the program gave over 9 seeds, and the smallest that the control
# gave on 2 seeds (the reference in the program's place in int8, this
# chip's faster matmul type).
#
# Five numbers separate, and each limit lies between its two readings with
# room on both sides. first_gradient_worst_matrix: sound 0.00116 at most (a
# convolution's taps), int8 no less than 0.0106: three times of room either
# way. first_gradient_global: 5.4e-5 against 0.00158, five times. The
# parameters' change by its worst matrix 0.0336 against 0.104, by its
# median matrix 0.00645 against 0.0454, over all leaves 0.0195 against
# 0.0716: about twice either way for the first and the last, over twice
# and a half for the median. At 1e-7 a step three steps move a weight of
# 0.02 by a hundred and sixty units in its last place, and where a
# gradient is near AdamW's eps of 1e-8 the step's size hangs on it: that
# is what the sound runs' change reads, alike on every seed (0.0302 to
# 0.0336 the worst matrix); int8's is three times it. The losses do not
# separate (sound up to 2.8e-5, 2.6e-5, 2.6e-5; int8 3.3e-5, 5.3e-6, 8.3e-6
# on one seed and 1.9e-5, 8.7e-5, 5.9e-5 on the other: the head is the
# embedding in bf16 and moves a loss of 9.45 by 3e-4 whatever the rest is
# computed in) and stand at three times the sound runs' largest, against
# the fault each is there to catch: a forward pass that is another
# function. On the CPU at the rehearsal's sizes every broken step of
# tests/benchmark/test_control_lfm2.py reads first_gradient_worst_matrix
# five times its limit or more.
LIMITS = {
    "loss_step1": 8.5e-5,
    "loss_step2": 8e-5,
    "loss_step3": 8e-5,
    "first_gradient_worst_matrix": 0.0035,
    "first_gradient_global": 0.0003,
    "param_change_worst_matrix": 0.06,
    "param_change_median_matrix": 0.017,
    "param_change_global": 0.037,
}
# At the rehearsal's tiny sizes on the CPU (hidden 64, 3 of 8 experts held,
# two sequences of 1024, head width 32; 8 seeds, the three controls on each,
# at the configuration's rate 1e-7) three numbers separate, and each limit
# lies between its two readings: the worst matrix's first gradient (sound
# 0.0036 at most, the reference in bf16 0.0029; fp8 no less than 0.0098,
# int8 0.0100); the parameters' change by its median matrix (0.00069 and
# 0.00058 against int8's 0.0040 and fp8's 0.0045) and over all leaves
# (0.0022, bf16 0.0016, against int8's 0.0048 and fp8's 0.0056). The
# parameters' change by its worst matrix does not (sound 0.0109, bf16
# 0.0081, int8 from 0.0102: at 1e-7 a step three steps move a weight by a
# hundred and sixty units in its last place) and stands at four times the
# sound runs' largest against a step that returns its state unchanged
# (reads 1). The losses (5.0e-6, 4.7e-6, 4.8e-6 sound) and the gradient's
# norm over all leaves (0.0010 sound, less under every lower precision)
# hardly move and stand at three times the sound runs' largest. Every
# broken step of tests/benchmark/test_control_lfm2.py reads
# first_gradient_worst_matrix five times the limit or more.
REHEARSAL_LIMITS = {
    "loss_step1": 1.5e-5,
    "loss_step2": 1.5e-5,
    "loss_step3": 1.5e-5,
    "first_gradient_worst_matrix": 0.006,
    "first_gradient_global": 0.003,
    "param_change_worst_matrix": 0.044,
    "param_change_median_matrix": 0.0017,
    "param_change_global": 0.0032,
}
CONTROL = "int8"
