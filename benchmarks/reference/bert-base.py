"""Plain reference for the ``bert-base`` configuration: BERT's encoder
with a masked-LM loss and AdamW, written out in ``jax.numpy`` float32 at
``highest`` matmul precision. It imports nothing of the program: no flax
module, no kernel, no ``shard_map``, no ``DistributedOptimizer``, no optax.

It follows Devlin et al. (arXiv:1810.04805): learned token, position and
segment embeddings, post-layer-norm blocks of multi-head attention and a
GELU feed-forward. Departures, each taken from what the program under
test computes so that both see one function (the configuration file
lists them under ``assumed``):

* GELU by its tanh approximation, layer-norm epsilon 1e-6;
* the masked-LM head is one untied projection with a bias, with no
  transform layer before it; no next-sentence head; no dropout;
* AdamW as optax's default: decay on every parameter, no schedule.

The masked mean is a weighted sum over sequences, so a replica's shard is
taken in chunks of sequences whose gradients are added: the reference
then fits the device at the cell's own batch. Data parallelism is
Horovod's: each replica's own mean, gradients averaged, one update.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from reference import precision as precision_of

LN_EPS = 1e-6
CHUNK_TOKENS = 4096
HIGHEST = lax.Precision.HIGHEST



def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(q, p, x):
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    a = p["SelfAttention_0"]
    heads = {n: mm("bsd,dhk->bshk", q(x), q(a[n]["kernel"])) + a[n]["bias"]
             for n in ("query", "key", "value")}
    width = heads["query"].shape[-1]
    scores = mm("bqhk,bshk->bhqs", q(heads["query"]),
                q(heads["key"])) / math.sqrt(width)
    probs = jax.nn.softmax(scores, axis=-1)
    context = mm("bhqs,bshk->bqhk", q(probs), q(heads["value"]))
    out = mm("bqhk,hkd->bqd", q(context),
             q(a["out"]["kernel"])) + a["out"]["bias"]
    x = _layer_norm(x + out, p["LayerNorm_0"])
    h = mm("bsd,df->bsf", q(x), q(p["Dense_0"]["kernel"])) \
        + p["Dense_0"]["bias"]
    h = mm("bsf,fd->bsd", q(_gelu_tanh(h)), q(p["Dense_1"]["kernel"])) \
        + p["Dense_1"]["bias"]
    return _layer_norm(x + h, p["LayerNorm_1"])


def masked_nll_sum(params, ids, labels, mask, q, layers):
    """Sum over the masked positions of the label's negative
    log-likelihood."""
    seq = ids.shape[1]
    x = (params["token_embeddings"]["embedding"][ids]
         + params["position_embeddings"]["embedding"][jnp.arange(seq)][None]
         + params["type_embeddings"]["embedding"][0])
    x = _layer_norm(x, params["embed_norm"])
    for i in range(layers):
        x = _layer(q, params[f"layer_{i}"], x)
    logits = jnp.einsum("bsd,dv->bsv", q(x), q(params["mlm_head"]["kernel"]),
                        precision=HIGHEST) + params["mlm_head"]["bias"]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask.astype(jnp.float32))


def follow(params, shards, steps, config, precision="f32"):
    """Train ``steps`` steps from ``params`` on the fixed batch.

    ``shards`` is a list of ``(ids, labels, mask)``, one per replica.
    Returns ``(losses, first_gradient, params)``: per step the list of
    every replica's loss, the averaged gradient of step one as the
    optimizer gets it, and the parameters after the last step."""
    opt = config["optimizer"]
    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, decay = opt["eps"], opt["weight_decay"]
    grad = jax.jit(jax.value_and_grad(functools.partial(
        masked_nll_sum, q=precision_of.rounder(precision),
        layers=config["model"]["num_hidden_layers"])))
    add = jax.jit(lambda a, b, w: jax.tree.map(
        lambda x, y: x + w * y, a, b))

    @jax.jit
    def update(params, mu, nu, grads, t):
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

        def leaf(p, m, v):
            step = (m / (1 - b1 ** t)) / (
                jnp.sqrt(v / (1 - b2 ** t)) + eps)
            return p - lr * (step + decay * p)

        return jax.tree.map(leaf, params, mu, nu), mu, nu

    def shard_grad(params, ids, labels, mask):
        rows = ids.shape[0]
        chunk = max(1, min(rows, CHUNK_TOKENS // ids.shape[1]))
        while rows % chunk:
            chunk -= 1
        count = float(mask.sum())
        total, grads = 0.0, None
        for at in range(0, rows, chunk):
            part = slice(at, at + chunk)
            value, g = grad(params, ids[part], labels[part], mask[part])
            total += float(value)
            grads = g if grads is None else add(grads, g, 1.0)
        return total / count, jax.tree.map(lambda g: g / count, grads)

    zeros = jax.tree.map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    losses, first = [], None
    for t in range(1, steps + 1):
        step_losses, grads = [], zeros
        for shard in shards:
            loss, g = shard_grad(params, *shard)
            step_losses.append(loss)
            grads = add(grads, g, 1.0 / len(shards))
        losses.append(step_losses)
        if first is None:
            first = grads
        params, mu, nu = update(params, mu, nu, grads, float(t))
    return losses, first, params


# Limits of the numbers compared. PERF.md, section 2, has the readings they
# were set from, taken on the chip at both cells' own sizes: the largest
# that sound runs of the program gave on 33 seeds, and the smallest that
# the control gave on 3 seeds a cell (the reference in the program's
# place in int8, this chip's faster matmul type, and in fp8).
#
# first_gradient_worst_matrix is the number that separates: sound runs
# reach 0.0034, fp8 no lower than 0.0186, int8 0.0317. The others move
# little under a lower precision and are held, at about three times the
# sound runs' largest, against the fault each is there to catch: the
# losses against a part of the batch left out (1e-5, 3e-5, 5e-5 sound),
# the norm over all leaves against a gradient scaled or not averaged
# (0.0023), the parameters' change against a step that returns its state
# unchanged (0.0045 by the worst matrix; over all leaves 0.00026, where
# int8's zeroed gradients leave Adam's updates 0.067 short).
LIMITS = {
    "loss_step1": 3e-5,
    "loss_step2": 9e-5,
    "loss_step3": 1.5e-4,
    "first_gradient_worst_matrix": 0.008,
    "first_gradient_global": 0.007,
    "param_change_worst_matrix": 0.0135,
    "param_change_global": 0.001,
}
# At the rehearsal's tiny sizes on the CPU sound runs stay inside the same
# limits (12 seeds: 0.0048, 0.0038, 0.0085, 0.00027) and both controls fail
# param_change_global (int8 from 0.0079, fp8 from 0.0064).
REHEARSAL_LIMITS = LIMITS
CONTROL = "int8"
