"""The losses the models are trained with.

``token_nll`` (the lse form BERT's, ViT's and the LMs' losses share),
``causal_lm_loss`` on full logits, ``chunked_causal_lm_loss`` with the
head fused in and both gradients computed in its one sweep (under
``hvd.loss.head``), ``weighted_chunked_causal_lm_loss`` (that sweep under
weights that are differentiated), ``sp_causal_lm_loss`` across shards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import profiler


def token_nll(logits, targets):
    """Per-token negative log-likelihood via the lse formulation:
    ``lse(logits) - logits[target]``. Unlike ``log_softmax`` +
    ``take_along_axis`` this never materializes a (..., V) f32 array —
    the f32 upcast fuses into the logsumexp reduction and the target
    logit is a gather — which cuts ~1 GiB of peak HBM at
    (B=8, S=1024, V=32000) and is what lets larger batches fit."""
    # Gather BEFORE the upcast: astype-then-gather would force the f32
    # copy this formulation exists to avoid (the upcast inside logsumexp
    # fuses into the reduction; a gather consumer would not).
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    target_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
    return lse - target_logit


def causal_lm_loss(logits, input_ids):
    """Next-token cross entropy (shifted)."""
    return token_nll(logits[:, :-1], input_ids[:, 1:]).mean()


def _loss_chunks(hidden, head_kernel, input_ids, num_chunks, ahead):
    """The operands of one sweep over the sequence's chunks, chunk-major:
    hidden states (n, B, c, D), targets shifted by ``ahead`` (n, B, c) and
    the head kernel in ``hidden``'s dtype."""
    b, s, d = hidden.shape
    c = s // num_chunks
    # Shifted targets over the FULL sequence; the final ``ahead`` positions
    # have no target: they wrap to garbage values and are masked out.
    targets = jnp.concatenate([input_ids[:, ahead:], input_ids[:, :ahead]],
                              axis=1)
    h = hidden.reshape(b, num_chunks, c, d).transpose(1, 0, 2, 3)
    t = targets.reshape(b, num_chunks, c).transpose(1, 0, 2)
    return h, t, head_kernel.astype(hidden.dtype)


def _mean_nll(nll, b, s, ahead):
    """The mean over every position that has a target (all but each
    sequence's last ``ahead``), of the chunk-major (n, B, c) per-token
    nll."""
    return nll.transpose(1, 0, 2).reshape(b, s)[:, :-ahead].mean()


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _chunked_loss(hidden, head_kernel, input_ids, num_chunks, ahead):
    # The call nobody differentiates: the loss alone, one product a chunk.
    with jax.named_scope(profiler.SCOPE_LOSS_HEAD):
        b, s, _ = hidden.shape
        h, t, w = _loss_chunks(hidden, head_kernel, input_ids, num_chunks,
                               ahead)
        # Same matmul dtype as the in-model lm_head (MXU f32 accumulate).
        nll = jax.lax.map(lambda args: token_nll(args[0] @ w, args[1]),
                          (h, t))
        return _mean_nll(nll, b, s, ahead)


def _chunked_loss_fwd(hidden, head_kernel, input_ids, num_chunks, ahead):
    with jax.named_scope(profiler.SCOPE_LOSS_HEAD):
        b, s, d = hidden.shape
        h, t, w = _loss_chunks(hidden, head_kernel, input_ids, num_chunks,
                               ahead)
        vocab = w.shape[1]
        # d(mean)/d(nll) of every position: 0 where there is no target.
        scale = (jnp.arange(s) < s - ahead).astype(jnp.float32) \
            / (b * (s - ahead))
        scale = scale.reshape(num_chunks, 1, s // num_chunks)

        def chunk(dw, args):
            h_c, t_c, scale_c = args
            logits = h_c @ w
            # token_nll's sweep (the gather before the upcast), with the
            # softmax's gradient taken while the logits are in hand.
            z = logits.astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(z, axis=-1)
            target_logit = jnp.take_along_axis(
                logits, t_c[..., None], axis=-1)[..., 0].astype(jnp.float32)
            onehot = jnp.arange(vocab) == t_c[..., None]
            dlogits = ((jnp.exp(z - lse[..., None]) - onehot)
                       * scale_c[..., None]).astype(logits.dtype)
            dh_c = jnp.einsum("bcv,dv->bcd", dlogits, w)
            dw = dw + jnp.einsum("bcd,bcv->dv", h_c, dlogits,
                                 preferred_element_type=jnp.float32)
            return dw, (lse - target_logit, dh_c)

        dw, (nll, dh) = jax.lax.scan(
            chunk, jnp.zeros((d, vocab), jnp.float32), (h, t, scale))
        dh = dh.transpose(1, 0, 2, 3).reshape(b, s, d)
        return _mean_nll(nll, b, s, ahead), (
            dh, dw.astype(head_kernel.dtype))


def _chunked_loss_bwd(num_chunks, ahead, residuals, g):
    del num_chunks, ahead
    with jax.named_scope(profiler.SCOPE_LOSS_HEAD):
        dh, dw = residuals
        return ((g * dh.astype(jnp.float32)).astype(dh.dtype),
                (g * dw.astype(jnp.float32)).astype(dw.dtype), None)


_chunked_loss.defvjp(_chunked_loss_fwd, _chunked_loss_bwd)


def chunked_causal_lm_loss(hidden, head_kernel, input_ids,
                           num_chunks: int = 8, ahead: int = 1):
    """:func:`causal_lm_loss` with the lm_head fused in, applied one
    sequence chunk at a time in ONE sweep (a ``lax.scan``, one ``while``
    of the compiled step): the full (B, S, V) logits — and their
    same-sized cotangent — never exist; peak extra HBM is
    O(B * S/num_chunks * V). At Llama-300M S=16384 that's the ~2 GiB that
    makes single-chip training fit where the fused-head path OOMs.

    ``hidden``: final-norm hidden states from
    ``model.apply(..., return_hidden=True)``, shape (B, S, dim);
    ``head_kernel``: ``params["lm_head"]["kernel"]`` (dim, V).
    The LOSS matches ``causal_lm_loss`` on the full logits exactly (each
    logit row is the same dot product; the mean is reassembled exactly).

    Differentiated (a ``jax.custom_vjp``), the same sweep also computes
    both GRADIENTS: the loss ends the step, so with a chunk's logits in
    hand ``dlogits = (softmax - onehot) / count`` is known (zero at each
    sequence's last position) and the chunk does its three
    vocabulary-wide products at once — ``h_c @ w``, ``dlogits @ w.T``,
    ``h_c.T @ dlogits`` — in the operands' dtype with float32
    accumulation; nothing is recomputed. Stored for the backward rule,
    which only multiplies them by the incoming scalar: ``dh`` (B, S, dim)
    in ``hidden``'s dtype and ``dW`` (dim, V), summed over the chunks in
    float32 and rounded once to ``head_kernel``'s dtype. Against autodiff
    of ``causal_lm_loss`` on the full logits both agree to 1e-6 in
    float32 and, under bf16, to the rounding of the logits' cotangent
    (grad-norm deltas under 1%, ``tests/test_llama.py``). Called
    undifferentiated it computes the loss alone. Forward mode
    (``jax.jvp``) is not defined.

    ``ahead``: how far ahead of a position its target lies. 1 is the next
    token; a multi-token-prediction head at depth k passes k + 1
    (``models/joyai.py``: 2), and the mean is over the ``S - ahead``
    positions a sequence that have a target."""
    s = hidden.shape[1]
    if s % num_chunks:
        raise ValueError(
            f"chunked_causal_lm_loss: seq len {s} must be divisible by "
            f"num_chunks {num_chunks}")
    if not 1 <= ahead < s:
        raise ValueError(
            f"chunked_causal_lm_loss: ahead={ahead} must lie in [1, {s})")
    return _chunked_loss(hidden, head_kernel, input_ids, num_chunks, ahead)


def _weighted_sum(nll, weights, sequences, ahead):
    """``sum(weights * nll)`` over every position that has a target, over
    the count of such positions in ``sequences`` sequences."""
    s = nll.shape[1]
    return (weights.astype(jnp.float32) * nll)[:, :-ahead].sum() \
        / (sequences * (s - ahead))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _weighted_chunked_loss(hidden, head_kernel, input_ids, weights,
                           num_chunks, ahead, sequences):
    # The call nobody differentiates: the loss alone, one product a chunk.
    with jax.named_scope(profiler.SCOPE_LOSS_HEAD):
        n, s, _ = hidden.shape
        h, t, w = _loss_chunks(hidden, head_kernel, input_ids, num_chunks,
                               ahead)
        nll = jax.lax.map(lambda args: token_nll(args[0] @ w, args[1]),
                          (h, t))
        return _weighted_sum(nll.transpose(1, 0, 2).reshape(n, s), weights,
                             sequences, ahead)


def _weighted_chunked_loss_fwd(hidden, head_kernel, input_ids, weights,
                               num_chunks, ahead, sequences):
    with jax.named_scope(profiler.SCOPE_LOSS_HEAD):
        n, s, d = hidden.shape
        h, t, w = _loss_chunks(hidden, head_kernel, input_ids, num_chunks,
                               ahead)
        vocab = w.shape[1]
        # d(loss)/d(weight * nll) of every position: 0 where there is no
        # target; d(loss)/d(nll) is the position's weight times it.
        counted = (jnp.arange(s) < s - ahead).astype(jnp.float32) \
            / (sequences * (s - ahead))
        scale = (weights.astype(jnp.float32) * counted).reshape(
            n, num_chunks, s // num_chunks).transpose(1, 0, 2)

        def chunk(dw, args):
            # ``_chunked_loss_fwd``'s chunk, a scale a row and position.
            h_c, t_c, scale_c = args
            logits = h_c @ w
            z = logits.astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(z, axis=-1)
            target_logit = jnp.take_along_axis(
                logits, t_c[..., None], axis=-1)[..., 0].astype(jnp.float32)
            onehot = jnp.arange(vocab) == t_c[..., None]
            dlogits = ((jnp.exp(z - lse[..., None]) - onehot)
                       * scale_c[..., None]).astype(logits.dtype)
            dh_c = jnp.einsum("bcv,dv->bcd", dlogits, w)
            dw = dw + jnp.einsum("bcd,bcv->dv", h_c, dlogits,
                                 preferred_element_type=jnp.float32)
            return dw, (lse - target_logit, dh_c)

        dw, (nll, dh) = jax.lax.scan(
            chunk, jnp.zeros((d, vocab), jnp.float32), (h, t, scale))
        dh = dh.transpose(1, 0, 2, 3).reshape(n, s, d)
        nll = nll.transpose(1, 0, 2).reshape(n, s)
        # A weight's gradient is its position's own nll over the count.
        return _weighted_sum(nll, weights, sequences, ahead), (
            dh, dw.astype(head_kernel.dtype),
            (nll * counted).astype(weights.dtype))


def _weighted_chunked_loss_bwd(num_chunks, ahead, sequences, residuals, g):
    del num_chunks, ahead, sequences
    with jax.named_scope(profiler.SCOPE_LOSS_HEAD):
        dh, dw, dweights = residuals
        return ((g * dh.astype(jnp.float32)).astype(dh.dtype),
                (g * dw.astype(jnp.float32)).astype(dw.dtype), None,
                (g * dweights.astype(jnp.float32)).astype(dweights.dtype))


_weighted_chunked_loss.defvjp(_weighted_chunked_loss_fwd,
                              _weighted_chunked_loss_bwd)


def weighted_chunked_causal_lm_loss(hidden, head_kernel, input_ids, weights,
                                    num_chunks: int = 8, ahead: int = 1):
    """:func:`chunked_causal_lm_loss` under PER-POSITION WEIGHTS THAT ARE
    DIFFERENTIATED, for one or several exits through one head:
    ``sum(weights * nll) / (B (S - ahead))`` over the positions that have
    a target, ``B, S = input_ids.shape``.

    ``hidden`` is ``(E B, S, dim)`` and ``weights`` ``(E B, S)``, the
    ``E`` exits stacked on the batch axis, exit-major (row ``e B + b`` is
    exit ``e`` of sequence ``b``; ``E = 1`` is one weighted loss); every
    exit has ``input_ids``' targets. The count stays ``B (S - ahead)``:
    with an exit distribution as the weights the value is the expected
    task loss. All exits go through the one sweep, so the head kernel is
    read once a chunk and its gradient summed once.

    The one sweep also gives the three GRADIENTS, nothing recomputed:
    ``d/d hidden = weight * (softmax - onehot) / count`` and ``d/d
    head_kernel`` as the unweighted rule has them, and ``d/d weights``,
    which is the position's own nll over the count (zero where there is
    no target): the sweep has it in hand. Weights of all ones over ``B``
    rows give the unweighted loss and gradients bit for bit
    (``tests/test_losses_weighted.py``). Forward mode is not defined."""
    b, s = input_ids.shape
    exits, left = divmod(hidden.shape[0], b)
    if left or not exits or hidden.shape[1] != s \
            or weights.shape != hidden.shape[:2]:
        raise ValueError(
            "weighted_chunked_causal_lm_loss: hidden "
            f"{hidden.shape} and weights {weights.shape} must hold a whole "
            f"number of exits of input_ids {input_ids.shape}")
    if s % num_chunks:
        raise ValueError(
            f"weighted_chunked_causal_lm_loss: seq len {s} must be "
            f"divisible by num_chunks {num_chunks}")
    if not 1 <= ahead < s:
        raise ValueError(
            f"weighted_chunked_causal_lm_loss: ahead={ahead} must lie in "
            f"[1, {s})")
    return _weighted_chunked_loss(
        hidden, head_kernel, jnp.tile(input_ids, (exits, 1)), weights,
        num_chunks, ahead, b)


def sp_causal_lm_loss(logits, input_ids, axis_name: str):
    """Sequence-parallel twin of :func:`causal_lm_loss`: ``logits`` /
    ``input_ids`` are the LOCAL (contiguous-layout) sequence shards inside
    ``shard_map``. The next-token shift crosses shard boundaries, so each
    shard fetches its right neighbor's first token over one ``ppermute``
    (riding ICI) and the global final position is masked out; the result
    is the same global mean on every shard — numerically identical to the
    single-device loss on the gathered sequence."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    nxt = jax.lax.ppermute(
        input_ids[:, :1], axis_name,
        [(i, (i - 1) % n) for i in range(n)])
    targets = jnp.concatenate([input_ids[:, 1:], nxt], axis=1)
    nll = token_nll(logits, targets)
    valid = jnp.ones(input_ids.shape, bool).at[:, -1].set(idx != n - 1)
    total = jax.lax.psum(jnp.where(valid, nll, 0.0).sum(), axis_name)
    count = jax.lax.psum(valid.sum(), axis_name)
    return total / count
