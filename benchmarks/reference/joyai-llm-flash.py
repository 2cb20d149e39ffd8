"""Plain reference for the ``joyai-llm-flash`` configuration: the
JoyAI-LLM-Flash block (multi-head latent attention, a dense or an expert
FFN), its multi-token-prediction module, both losses and AdamW, written
out in ``jax.numpy`` float32 at ``highest`` matmul precision. It imports
nothing of the program: no flax module, no kernel, no ``ragged_dot``, no
``shard_map``, no ``DistributedOptimizer``, no optax, and none of
``models/joyai.py``, ``models/llama.py``, ``ops/attention.py`` or
``parallel/moe.py``.

It follows ``jdopensource/JoyAI-LLM-Flash``'s public ``config.json``,
whose keys are DeepSeek-V3's; each reading of a key the config leaves
open is in the configuration file's ``assumed``. For a layer with input
``x`` (tokens x 2048), RMSNorm eps 1e-6, every product without bias:

* ``z = RMSNorm(x)``; ``c_q = RMSNorm_1536(z W_qa)``, ``[q_n | q_r] = c_q
  W_qb`` as 32 heads of (128 | 64); ``[c_kv | k_r] = z W_kva`` (512 |
  64), ``c_kv <- RMSNorm_512(c_kv)``, ``[k_n | v] = c_kv W_kvb`` as 32
  heads of (128 | 128). ``k_r`` is ONE 64-wide vector a token: rotated
  once and copied by hand to all 32 heads. The rotation is of the
  interleaved pairs ``(x[2i], x[2i + 1])`` by ``pos x theta^(-2i/64)``,
  ``theta`` 3.2e7, positions 0..S-1, written out (the program rotates
  halves after a de-interleaving: the same scores). ``q = [q_n |
  rot(q_r)]``, ``k = [k_n | rot(k_r)]``, 192 wide; causal softmax at
  ``1/sqrt(192)`` over values 128 wide; ``h = x + ctx W_o`` (4096 x
  2048);
* ``z = RMSNorm(h)``; a dense layer ``out = h + W_2(silu(W_1 z) * (W_3
  z))``, 7168 wide; a sparse one ``s = sigmoid(z W_r)`` (256 wide), the
  chosen set the 8 largest of ``s + b`` (``b`` the expert bias: in the
  CHOICE and nowhere else), ``w_e = 2.5 x s_e / (sum of the chosen s +
  1e-20)``, ``out = h + E_shared(z) + sum over the chosen experts HELD
  HERE of w_e E_e(z)``, every ``E`` a SiLU-gated MLP 768 wide;
* a final RMSNorm gives ``g``, then the head (2048 x V);
* the multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437,
  section 2.2; depth 1): ``u_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(g_i)]`` (``W_eh`` 4096 x 2048), one more sparse block over
  ``u``, a norm, then the SAME head and the SAME embedding; it scores
  ``t_{i+2}``. The last position has no ``t_{i+1}``: it is given
  ``t_0``, which under the causal mask no other position reads, and it
  has no target.

``L = L_main + lambda x L_MTP``: ``L_main`` the mean over every position
but each sequence's last of the next token's negative log-likelihood,
``L_MTP`` the mean over the ``S - 2`` positions a sequence that have a
token two ahead, ``lambda`` the file's ``mtp_loss_weight``.

Attention is an explicit masked softmax in blocks of queries so that it
fits; the shared and every held expert are applied densely to every token
and the routed ones weighed, with zero where not chosen. Only the routed
experts the configuration holds (ids 0-7 of 256) add to a layer's result,
and that partial result goes on to the next layer: the chip's share of a
thirty-two-chip deployment, with nothing standing in for the other chips;
the vocabulary is the held slice, the depth one dense layer, the four
after it and the module. No auxiliary loss. AdamW as optax's default with
decay on every parameter but the expert bias, whose gradient is zero (the
choice is no differentiable function of it) and which no step changes.

A replica's shard is taken sequence by sequence inside one gradient. Data
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
NORM_EPS_OF_WEIGHTS = 1e-20     # in the routing weights' normalisation


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rotate_pairs(x, theta):
    """Rotate the interleaved pairs ``(x[..., 2i], x[..., 2i + 1])`` of
    (S, ..., W) by ``pos x theta^(-2i/W)`` at positions 0..S-1."""
    seq, width = x.shape[0], x.shape[-1]
    inv_freq = float(theta) ** (
        -2.0 * np.arange(width // 2, dtype=np.float64) / width)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    angles = angles.reshape((seq,) + (1,) * (x.ndim - 2) + (width // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(rnd, q, k, v):
    """Causal softmax attention of one sequence: q and k (S, H, D), v
    (S, H, Dv), a block of queries at a time against every key; the
    scale is q's width's."""
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
    return out.reshape(seq, heads, v.shape[-1])


def latent_attention(rnd, a, z, config):
    """The mixer on one sequence's normed input ``z`` (S, hidden), up to
    and including ``W_o``."""
    eps, nope = config["rms_norm_eps"], config["qk_nope_head_dim"]
    theta = config["rope_theta"]
    c_q = _rms_norm(mm("sd,dr->sr", rnd(z), rnd(a["wq_a"]["kernel"])),
                    a["q_a_norm"]["scale"], eps)
    q = mm("sr,rhk->shk", rnd(c_q), rnd(a["wq_b"]["kernel"]))
    kv_a = mm("sd,dr->sr", rnd(z), rnd(a["wkv_a"]["kernel"]))
    c_kv, k_r = (kv_a[:, :config["kv_lora_rank"]],
                 kv_a[:, config["kv_lora_rank"]:])
    c_kv = _rms_norm(c_kv, a["kv_a_norm"]["scale"], eps)
    kv = mm("sr,rhk->shk", rnd(c_kv), rnd(a["wkv_b"]["kernel"]))
    k_n, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)],
                        axis=-1)
    # One rotary key a token, copied to every head by hand.
    k_r = rotate_pairs(k_r, theta)
    k = jnp.concatenate(
        [k_n, jnp.stack([k_r] * k_n.shape[1], axis=1)], axis=-1)
    return mm("shk,hkd->sd", rnd(_attention(rnd, q, k, v)),
              rnd(a["wo"]["kernel"]))


def _gated_mlp(rnd, h, m):
    hidden = jax.nn.silu(mm("sd,df->sf", rnd(h), rnd(m["w_gate"]["kernel"]))) \
        * mm("sd,df->sf", rnd(h), rnd(m["w_up"]["kernel"]))
    return mm("sf,fd->sd", rnd(hidden), rnd(m["w_down"]["kernel"]))


def routing_weights(scores, bias, config):
    """(S, E) weights from sigmoid ``scores``: zero off the chosen set,
    which is the largest ``num_experts_per_tok`` of ``scores + bias``; on
    it the expert's own score over the chosen scores' sum plus 1e-20,
    times the routed scale."""
    choice = scores + bias
    kth = jnp.sort(choice, axis=-1)[:, -config["num_experts_per_tok"]]
    chosen = lax.stop_gradient(choice >= kth[:, None])
    weights = jnp.where(chosen, scores, 0.0)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                         + NORM_EPS_OF_WEIGHTS)
    return weights * config["routed_scaling_factor"]


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


def _layer(rnd, p, x, config, sparse):
    """One block on one sequence ``x`` (S, hidden); ``sparse``: its FFN is
    the expert layer."""
    eps = config["rms_norm_eps"]
    x = x + latent_attention(
        rnd, p["attention"], _rms_norm(x, p["attention_norm"]["scale"], eps),
        config)
    z = _rms_norm(x, p["ffn_norm"]["scale"], eps)
    if not sparse:
        return x + _gated_mlp(rnd, z, p["mlp"])
    scores = jax.nn.sigmoid(
        mm("sd,de->se", rnd(z), rnd(p["router"]["kernel"])))
    weights = routing_weights(scores, p["expert_bias"]["kernel"], config)
    held = jnp.asarray(config["deployment"]["experts_held"], jnp.int32)
    return x + _gated_mlp(rnd, z, p["shared"]) + _held_experts(
        rnd, z, weights[:, held], p["w_gate"]["kernel"], p["w_up"]["kernel"],
        p["w_down"]["kernel"])


def sequence_hidden(params, ids, rnd, config):
    """``(g, m)`` for one sequence of ids, both (S, hidden): the final
    norm's output, and the multi-token-prediction module's (None where
    the configuration has none)."""
    embedding = params["tok_embeddings"]["embedding"]
    eps = config["rms_norm_eps"]
    block = lambda sparse: jax.checkpoint(functools.partial(  # noqa: E731
        _layer, rnd, config=config, sparse=sparse))
    x = embedding[ids]
    for i in range(config["num_layers"]):
        x = block(i >= config["first_k_dense_replace"])(
            params[f"layer_{i}"], x)
    g = _rms_norm(x, params["final_norm"]["scale"], eps)
    if not config["num_nextn_predict_layers"]:
        return g, None
    p = params["mtp"]
    # Position i is given t_{i+1}; the last is given t_0 and has no
    # target.
    following = embedding[jnp.roll(ids, -1)]
    u = mm("se,ed->sd", rnd(jnp.concatenate(
        [_rms_norm(following, p["enorm"]["scale"], eps),
         _rms_norm(g, p["hnorm"]["scale"], eps)], axis=-1)),
        rnd(p["eh_proj"]["kernel"]))
    u = block(True)(p["block"], u)
    return g, _rms_norm(u, p["norm"]["scale"], eps)


def _nll_sum(rnd, x, head, ids, ahead):
    """Sum over one sequence's positions that have a token ``ahead``
    ahead of that token's negative log-likelihood under ``x head``."""
    seq = ids.shape[0]
    block = math.gcd(seq, HEAD_BLOCK)
    targets = jnp.roll(ids, -ahead)
    scored = jnp.arange(seq) < seq - ahead

    @jax.checkpoint
    def positions(start):
        xb = lax.dynamic_slice_in_dim(x, start, block, axis=0)
        tb = lax.dynamic_slice_in_dim(targets, start, block, axis=0)
        logits = mm("sd,dv->sv", rnd(xb), rnd(head))
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * lax.dynamic_slice_in_dim(scored, start, block))

    return jnp.sum(lax.map(positions, jnp.arange(0, seq, block)))


def sequence_nll_sums(params, ids, rnd, config):
    """``(main, mtp)`` of one sequence: the next token's summed negative
    log-likelihood over S - 1 positions, and the module's for the token
    two ahead over S - 2 (0 where there is no module), both through the
    one head."""
    g, m = sequence_hidden(params, ids, rnd, config)
    head = params["lm_head"]["kernel"]
    main = _nll_sum(rnd, g, head, ids, 1)
    return main, (jnp.zeros(()) if m is None
                  else _nll_sum(rnd, m, head, ids, 2))


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
    weight = config["mtp_loss_weight"]
    one = functools.partial(sequence_nll_sums,
                            rnd=precision_of.rounder(precision),
                            config=config)

    # The compiler's least effort, as the other decoder references: the
    # program runs once, and how fast is not measured.
    @functools.partial(jax.jit, compiler_options={
        "exec_time_optimization_effort": -1.0})
    def shard_grad(params, ids):
        def loss(params):
            main, mtp = lax.map(
                jax.checkpoint(lambda row: one(params, row)), ids)
            batch, seq = ids.shape
            return jnp.sum(main) / (batch * (seq - 1)) \
                + weight * jnp.sum(mtp) / (batch * (seq - 2))

        return jax.value_and_grad(loss)(params)

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
# configuration's rate 1e-6 (my chip runs, PR 40): the largest that sound
# runs of the program gave over 13 seeds (six of them read before the
# limits were set, seven after: the losses' limits are from all thirteen,
# the others moved by a twentieth at most), and the smallest that the
# control gave on 2 seeds (the reference in the program's place in int8, this
# chip's faster matmul type).
#
# Seven numbers separate, five of them by a factor of ten or more, and each
# limit lies between its two readings with three to four times of room on
# both sides: first_gradient_worst_matrix sound 0.00307 at most (a held
# expert's w_down or a router), int8 no less than 0.0281;
# first_gradient_global 8.5e-5 against 0.00145; the parameters' change by
# its worst matrix 0.0131 against 0.197, by its median matrix 0.00061
# against 0.0126, over all leaves 0.0030 against 0.0322. The two numbers of
# the first gradient's DIFFERENCE from the reference's (its norm: what
# sees a gradient of the right size that points elsewhere, as a wrong
# lambda or a target one ahead leaves the module's leaves) separate by less
# and have less room: over all leaves sound 0.0117 against 0.0625, twice
# and a third either way; by the worst matrix (a held expert's matrix, 512
# rows a step in bf16) 0.122 against 0.311, a factor of 1.6 either way: it
# reads 0.114 to 0.122 on every seed, which is bf16's own rounding (the
# reference computed in bf16 reads as much at the rehearsal's sizes; 0.126
# the largest of thirteen). The losses do not separate (int8 1.1e-5,
# 9.2e-6, 1.6e-5 at most against sound 1.28e-5, 8.3e-6, 9.4e-6 over the
# thirteen seeds; the first six alone read 8.3e-6, 5.7e-6, 3.6e-6: a loss
# of 11.1 in float32 has a last place of 1e-6 and a seed decides how many
# of them the two sums differ by) and stand at three times the sound
# runs' largest, against the fault each is there to catch: a forward pass
# that is another function. The leaves only the module's loss
# reaches (its projection, its two norms, its block) are leaves like any
# other in every number above. On the CPU at the rehearsal's sizes every
# broken step of tests/benchmark/test_control_joyai.py reads not correct.
LIMITS = {
    "loss_step1": 3.8e-5,
    "loss_step2": 2.5e-5,
    "loss_step3": 2.8e-5,
    "first_gradient_worst_matrix": 0.0093,
    "first_gradient_global": 0.00035,
    "first_gradient_difference": 0.027,
    "first_gradient_difference_worst_matrix": 0.195,
    "param_change_worst_matrix": 0.05,
    "param_change_median_matrix": 0.0028,
    "param_change_global": 0.0098,
}
# At the rehearsal's tiny sizes on the CPU (hidden 64, 3 of 8 experts held,
# two sequences of 1024, two heads with q and k 48 wide over v 32, a dense
# layer, two sparse ones and the module; matrices drawn at 0.1, not 0.02,
# so that scores and router logits are as wide as at the published widths:
# at 0.02 over 64 inputs attention is a uniform average and a fault in the
# rotation moves nothing; 8 seeds, the three controls on each, at the
# configuration's rate) six numbers separate sound from int8, and each
# limit lies between its two readings: the worst matrix's first gradient
# (sound 0.0100 at most, the reference in bf16 0.0202; int8 no less than
# 0.0533); the parameters' change by its worst matrix (0.0127 against
# 0.0256: the limit a third above the one and a quarter under the other),
# by its median matrix (0.00138 against 0.0091) and over all leaves (0.0028
# against 0.0102); and the first gradient's DIFFERENCE from the
# reference's (its norm, which sees what a norm's gap cannot: a gradient of
# the right size that points elsewhere) over all leaves (0.0412 against
# 0.0687) and by the worst matrix (0.185 against 0.318). The losses (sound
# 3.0e-5, 3.2e-5, 3.1e-5 at most; int8 from 7e-6) do not and stand at three
# times the sound runs' largest against a forward pass that is another
# function; the gradient's norm over all leaves (sound 0.00039 at most,
# int8 from 0.00082, fp8 from 0.0024) at three times it against a gradient
# scaled or not averaged. Every broken step of
# tests/benchmark/test_control_joyai.py fails by at least one of the first
# step's five.
REHEARSAL_LIMITS = {
    "loss_step1": 1e-4,
    "loss_step2": 1e-4,
    "loss_step3": 1e-4,
    "first_gradient_worst_matrix": 0.033,
    "first_gradient_global": 0.0012,
    "first_gradient_difference": 0.053,
    "first_gradient_difference_worst_matrix": 0.245,
    "param_change_worst_matrix": 0.019,
    "param_change_median_matrix": 0.0035,
    "param_change_global": 0.0055,
}
CONTROL = "int8"
