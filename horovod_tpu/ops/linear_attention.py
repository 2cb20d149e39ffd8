"""Linear attention with a decaying, rank-one-corrected state: the gated
delta rule (Gated DeltaNet, arXiv:2412.06464; the layer of
flash-linear-attention's ``GatedDeltaNet``), with the two pointwise parts
that stand around it in a layer.

For one head, with keys of width ``d_k`` and values of width ``d_v``, the
state ``S`` is a ``d_v x d_k`` matrix that starts at zero and every token
first forgets a part of, then corrects along its key::

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
        = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

``alpha_t = exp(g_t)`` in (0, 1] is the decay, ``beta_t`` in [0, 2) the
strength of the write (above 1 the correction overshoots: a negative
eigenvalue along ``k_t``). :func:`reference_gated_delta_rule` is exactly
that, token by token.

:func:`gated_delta_rule` is the same function in the chunked form a
training step can afford. Inside a chunk of ``C`` tokens, with ``b_i`` the
running sum of ``g`` from the chunk's first token and ``S_0`` the state
the chunk starts from, every token's write ``u_i = beta_i (v_i - alpha_i
S_{i-1} k_i)`` obeys::

    (I + A) U = diag(beta) V - diag(beta e^b) K S_0^T
    A_ij = beta_i e^{b_i - b_j} (k_i . k_j)   for j < i, else 0

a unit lower-triangular system of size ``C``, whose inverse ``T`` comes
from matrix products alone (:func:`_inverse_unit_lower`: the Neumann
series, which ends since ``A^C = 0``, on the diagonal's blocks of 16, then
two joins of neighbouring blocks). With ``U0 = T
diag(beta) V`` and ``W = T diag(beta e^b) K``, both batched products over
all chunks at once, only the state crosses chunks, in a scan over ``S/C``
steps::

    U   = U0 - W S_0^T
    S_C = e^{b_C} S_0 + U^T diag(e^{b_C - b}) K
    O   = diag(e^b) Q S_0^T + (tril(e^{b_i - b_j}) * Q K^T) U

Every exponent is a decay over a stretch of the chunk and at most 0. The
running sums, the decays, the triangular system and the state are float32
whatever the operands; the products take the operands' own type (bfloat16
operands accumulate in float32, float32 operands multiply at ``highest``).

**The backward pass** is autodiff through this form, with one rule of its
own: the inverse's gradient is ``-T^T dT T^T``, from ``T`` alone, so
nothing inside the inverse is kept. The scan keeps the state each chunk
started from (``S/C`` states of ``d_v x d_k`` float32 a head) and the
chunk's ``U``; ``T``, ``W``, ``U0`` and the decay matrices are kept as the
batched arrays they are. The pointwise parts keep their inputs and
recompute their float32 insides. Under a recomputed block (``remat``) all
of it lives for one block's backward pass only. No Pallas kernel: XLA
operations under the caller's scope.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["gated_delta_rule", "reference_gated_delta_rule",
           "causal_conv", "causal_conv_silu", "gated_head_norm",
           "l2_normalize"]


def _dot(spec, a, b, dtype):
    """``einsum`` with both operands in ``dtype`` and a float32 result:
    float32 operands at ``highest``, lower ones as they are."""
    precision = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      precision=precision,
                      preferred_element_type=jnp.float32)


_LEAF = 16


def _inverse(a):
    mm = lambda x, y: _dot("...ij,...jk->...ik", x, y,   # noqa: E731
                           jnp.float32)
    size = a.shape[-1]
    row = jnp.arange(size)[:, None]
    col = jnp.arange(size)[None, :]

    def on_diagonal(block):     # the block-diagonal part, blocks of `block`
        return jnp.where(row // block == col // block, a, 0.0)

    block = min(_LEAF, size)
    eye = jnp.eye(size, dtype=a.dtype)
    power = on_diagonal(block)
    inverse = eye - power
    for _ in range(int(math.log2(block)) - 1):
        power = mm(power, power)
        inverse = mm(inverse, eye + power)
    while block < size:
        below = on_diagonal(2 * block) - on_diagonal(block)
        inverse = inverse - mm(mm(inverse, below), inverse)
        block *= 2
    return inverse


@jax.custom_vjp
def _inverse_unit_lower(a):
    """``(I + a)^-1`` for a strictly lower-triangular ``a`` of size C (the
    last two axes, C a power of two), float32, by matrix products alone,
    every one of them C wide (a 16-wide array pads to 128 lanes on a TPU).
    With ``D_b`` the part of ``a`` inside the diagonal's blocks of ``b``:
    ``(I + D_16)^-1`` is the Neumann series ``sum (-D)^n``, which ends at
    ``n = 15`` and is the product ``(I - D)(I + D^2)(I + D^4)(I + D^8)``;
    then ``X = (I + D_b)^-1`` gives ``(I + D_2b)^-1 = X - X (D_2b - D_b)
    X``, since ``X (D_2b - D_b)`` holds only the blocks below the
    diagonal's and squares to zero; twice, and ``D_64 = a``. (The series
    over the whole of a 64-token chunk is exact too, but its middle powers
    outgrow the result where the keys are alike, and float32 loses three
    digits to the cancellation.) The backward pass needs the inverse
    alone: ``dA = -T^T dT T^T``."""
    return _inverse(a)


def _inverse_forward(a):
    inverse = _inverse(a)
    return inverse, inverse


def _inverse_backward(inverse, g):
    t = jnp.swapaxes(inverse, -1, -2)
    return (-_dot("...ij,...jk->...ik", _dot(
        "...ij,...jk->...ik", t, g, jnp.float32), t, jnp.float32),)


_inverse_unit_lower.defvjp(_inverse_forward, _inverse_backward)


# Tokens a chunk where the caller names none (flash-linear-attention's
# own): what ``models/olmo_hybrid.py`` runs, and what the benchmark's
# builder counts the scan's work with.
CHUNK = 64


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK,
                     output_final_state: bool = False):
    """The gated delta rule over whole sequences, chunked.

    ``q``, ``k``: ``(B, S, H, d_k)``, as they enter the recurrence (the
    caller has normalised and scaled them); ``v``: ``(B, S, H, d_v)``;
    ``g`` (the log of the decay, at most 0) and ``beta``: ``(B, S, H)``.
    Returns ``o`` of ``v``'s shape in ``q``'s type, and with
    ``output_final_state`` also the float32 state ``(B, H, d_v, d_k)``
    after the last token. A sequence that is no multiple of ``chunk`` is
    padded with rows of ``beta = 0, g = 0``, which change no state."""
    b, s, h, _ = q.shape
    dtype = q.dtype
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"gated_delta_rule: chunk {chunk} is no power of "
                         "two")
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (s + pad) // chunk

    def chunks(x):      # (B, S, H, ...) -> (B, H, N, C, ...)
        x = x.reshape((b, n, chunk, h) + x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(jnp.float32))
    cum = jnp.cumsum(chunks(g.astype(jnp.float32)), axis=-1)    # b_i
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # e^{b_i - b_j} for j <= i, else 0; masked before the exponential, as
    # above the diagonal the exponent is positive and may overflow.
    decay = jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    a = jnp.where(jnp.tril(lower, -1), beta[..., None] * decay * _dot(
        "bhnid,bhnjd->bhnij", k, k, dtype), 0.0)
    t = _inverse_unit_lower(a)
    u0 = _dot("bhnij,bhnjd->bhnid", t, beta[..., None] * v, dtype)
    w = _dot("bhnij,bhnjd->bhnid", t,
             (beta * jnp.exp(cum))[..., None] * k, dtype)
    to_end = jnp.exp(cum[..., -1:] - cum)[..., None] * k     # e^{b_C - b} K
    through = jnp.exp(cum[..., -1])                          # e^{b_C}

    def step(state, xs):
        w_c, u0_c, k_c, through_c = xs
        u_c = (u0_c - _dot("bhid,bhed->bhie", w_c, state, dtype)).astype(
            dtype)
        new = through_c[..., None, None] * state + _dot(
            "bhie,bhid->bhed", u_c, k_c, dtype)
        return new, (state, u_c)

    by_chunk = lambda x: jnp.moveaxis(x, 2, 0)      # noqa: E731
    final, (states, u) = lax.scan(
        step, jnp.zeros((b, h, v.shape[-1], k.shape[-1]), jnp.float32),
        (by_chunk(w.astype(dtype)), by_chunk(u0), by_chunk(to_end),
         by_chunk(through)))
    states, u = jnp.moveaxis(states, 0, 2), jnp.moveaxis(u, 0, 2)
    o = _dot("bhnid,bhned->bhnie", jnp.exp(cum)[..., None] * q, states,
             dtype) + _dot(
        "bhnij,bhnje->bhnie",
        decay * _dot("bhnid,bhnjd->bhnij", q, k, dtype), u, dtype)
    o = jnp.moveaxis(o, 1, 3).reshape(b, s + pad, h, -1)[:, :s].astype(dtype)
    return (o, final) if output_final_state else o


def reference_gated_delta_rule(q, k, v, g, beta,
                               output_final_state: bool = False):
    """:func:`gated_delta_rule` token by token, float32 at ``highest``:
    the recurrence as the module's first lines write it, one ``lax.scan``
    step a token. What the chunked form is tested against."""
    dtype = q.dtype
    q, k, v, g, beta = (jnp.moveaxis(x.astype(jnp.float32), 1, 0)
                        for x in (q, k, v, g, beta))

    def step(state, xs):        # state (B, H, d_v, d_k)
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[..., None, None] * state
        write = beta_t[..., None] * (v_t - _dot(
            "bhed,bhd->bhe", state, k_t, jnp.float32))
        state = state + write[..., :, None] * k_t[..., None, :]
        return state, _dot("bhed,bhd->bhe", state, q_t, jnp.float32)

    _, batch, heads, d_k = q.shape
    final, o = lax.scan(
        step, jnp.zeros((batch, heads, v.shape[-1], d_k), jnp.float32),
        (q, k, v, g, beta))
    o = jnp.moveaxis(o, 0, 1).astype(dtype)
    return (o, final) if output_final_state else o


def causal_conv(x, w, activation=None):
    """A causal depthwise convolution along the sequence: ``x``
    ``(B, S, C)``, ``w`` ``(K, C)``; channel ``c`` of token ``t`` is
    ``sum_i w[i, c] x[t - (K - 1) + i, c]``, tokens before the sequence
    read as zero, no bias. The last tap is the token's own: no later token
    is seen. ``activation`` (float32 -> float32, pointwise) is the
    caller's and is applied to the sums; ``None`` leaves them as they are
    (the gated short convolution of ``models/lfm2.py``). Float32 inside,
    ``x``'s type out; the backward pass keeps ``x`` alone and recomputes
    the float32 sums."""
    taps, s = w.shape[0], x.shape[1]

    @jax.checkpoint
    def conv(x, w):
        padded = jnp.pad(x.astype(jnp.float32),
                         ((0, 0), (taps - 1, 0), (0, 0)))
        y = sum(padded[:, i:i + s] * w[i].astype(jnp.float32)
                for i in range(taps))
        return (y if activation is None else activation(y)).astype(x.dtype)

    return conv(x, w)


def causal_conv_silu(x, w):
    """``silu`` of :func:`causal_conv`: channel ``c`` of token ``t`` is
    ``silu(sum_i w[i, c] x[t - (K - 1) + i, c])`` (the gated delta-rule
    layer's convolutions, ``models/olmo_hybrid.py``)."""
    return causal_conv(x, w, jax.nn.silu)


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32 inside
    (recomputed in the backward pass)."""
    @jax.checkpoint
    def normalize(x):
        x32 = x.astype(jnp.float32)
        return (x32 * lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                                + eps)).astype(x.dtype)

    return normalize(x)


def gated_head_norm(o, gate, scale, eps: float = 1e-6):
    """``RMSNorm(o) * scale * silu(gate)`` over the last axis (one head's
    values), with one learned ``scale`` of that width shared by the heads;
    float32 inside (recomputed in the backward pass), ``gate``'s type
    out."""
    @jax.checkpoint
    def norm(o, gate, scale):
        o32, gate32 = o.astype(jnp.float32), gate.astype(jnp.float32)
        normed = o32 * lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True)
                                 + eps)
        return (normed * scale * jax.nn.silu(gate32)).astype(gate.dtype)

    return norm(o, gate, scale)
