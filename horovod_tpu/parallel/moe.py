"""Expert parallelism: mixture-of-experts dispatch over a mesh axis.

The reference has no MoE (2019 CNN-era, SURVEY.md §2.3); this is a TPU
extension on the same substrate: experts live along an ``"expert"`` mesh
axis, and token dispatch/return ride ``jax.lax.all_to_all`` over ICI — the
canonical TPU MoE layout (GShard/Switch): tokens are packed into
``[experts, capacity, d_model]`` buffers by index-based routing — int32
cumsum capacity slots (``_route``) and gather-only row permutations
whose custom_vjps route the transposes through the inverse
slot→assignment map (``_pack_rows``/``_combine_rows``; the one-hot mask
einsums this replaces cost more FLOPs than the experts at LM scale, and
autodiff's scatter-add transposes cost ~2.3x a gather on TPU) —
exchanged all-to-all so each device holds its expert's tokens from
every peer, transformed, and exchanged back.

Routing is top-k with capacity dropping (Switch for ``k=1``, GShard for
``k=2``): per expert at most ``capacity = ceil(k*T/E * capacity_factor)``
assignments survive (scaled by ``k`` because top-k routing emits ``k*T``
assignments in total); overflow tokens pass through with zero expert
output (the standard residual-passthrough convention). The Switch load-balancing
auxiliary loss is returned alongside the output.

A second layer, below the capacity path, is dropless and is told which
experts its device holds (:func:`moe_apply_held`): it routes over all the
router's experts, sorts the assignments that land here by expert and runs
grouped products over them (:func:`grouped_gated_mlp`), by the routing
rule its model gives (:func:`softmax_top_k`, :func:`sigmoid_top_k`), under
the scopes
``hvd.moe.route`` / ``.dispatch`` / ``.experts`` / ``.combine``. It is the
share of an expert-parallel deployment one device computes between the two
exchanges; the exchange is not in it. ``MoeLM`` keeps the capacity path:
``all_to_all`` needs its fixed buffers, and a dropless exchange is queued
(``ROADMAP.md`` R1).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import profiler
from .mesh import axis_size


def switch_aux_loss(probs: jax.Array, expert_mask: jax.Array) -> jax.Array:
    """Load-balancing loss (Switch Transformer eq. 4): E * sum_e
    fraction_of_tokens(e) * mean_router_prob(e). Minimised at uniform
    routing, where it equals 1. Accumulated in float32: a bf16 mean over
    many tokens would round the fractions."""
    num_experts = probs.shape[-1]
    fraction = expert_mask.astype(jnp.float32).mean(axis=0)
    mean_prob = probs.astype(jnp.float32).mean(axis=0)
    return num_experts * jnp.sum(fraction * mean_prob)


class _Routing:
    """Index bundle from :func:`_route` (per round, all int32/f-dtype
    lists of length ``num_selected``): each round's per-token expert id,
    capacity slot (``>= capacity`` == dropped) and combine weight."""

    def __init__(self, expert_idx, slot, combine_w):
        self.expert_idx = expert_idx    # k x [T]
        self.slot = slot                # k x [T]
        self.combine_w = combine_w      # k x [T]


def _route(probs: jax.Array, capacity: int, num_selected: int,
           normalize_gates: bool, dtype
           ) -> Tuple[_Routing, jax.Array]:
    """Top-k routing with capacity dropping — index-based (round 3).

    The round-2 implementation built one-hot ``[T, E, C]`` dispatch/combine
    masks and moved tokens with ``tec,td->ecd`` einsums; at LM scale that
    matmul costs ~2.6x the expert FLOPs themselves (T x (E*C) x D) and
    capped MoE MFU at ~23%. This version keeps the cheap part of that
    scheme — each round's capacity slot from an int32 cumsum over the
    [T, E] one-hot, filling in (round, token) order with a cross-round
    carry — and replaces the einsums with gather-only row permutations
    (``_pack_to_experts``/``_gather_from_experts`` via ``_pack_rows``/
    ``_combine_rows``): O(T*D + E*C*D) memory traffic, no O(T*E*C)
    anything, and no argsort (measured slower than the cumsum on the
    v5e vector unit).

    Routing decisions (argmax, gates) are computed from f32 probs;
    combine weights drop to ``dtype`` at the end so y doesn't silently
    promote bf16 streams. Returns ``(routing, aux)``.
    """
    tokens, num_experts = probs.shape
    choices, slots, gates = [], [], []
    avail = jnp.ones_like(probs)          # experts still choosable per token
    total_mask = jnp.zeros_like(probs)
    # Tokens already assigned per expert (slots fill in round-major,
    # token-ascending order; int32 — a bf16 cumsum cannot count past 256).
    fill = jnp.zeros((num_experts,), jnp.int32)
    for _ in range(num_selected):
        masked = jnp.where(avail > 0, probs, -jnp.inf)
        choice = jnp.argmax(masked, axis=-1)              # [T]
        gate = jnp.take_along_axis(probs, choice[:, None], axis=-1)[:, 0]
        onehot_i = jax.nn.one_hot(choice, num_experts, dtype=jnp.int32)
        pos = jnp.cumsum(onehot_i, axis=0) - 1 + fill[None, :]  # [T, E]
        slot = jnp.sum(pos * onehot_i, axis=-1)           # [T]
        fill = fill + jnp.sum(
            onehot_i * (slot < capacity)[:, None], axis=0)
        avail = avail * (1 - onehot_i).astype(probs.dtype)
        total_mask = total_mask + onehot_i.astype(probs.dtype)
        choices.append(choice)
        slots.append(slot)
        gates.append(gate)

    if normalize_gates and num_selected > 1:
        # GShard convention: the selected gates are renormalised to sum to
        # 1 per token (over ALL k choices, dropped or not).
        denom = jnp.maximum(sum(gates), 1e-9)
        gates = [g / denom for g in gates]
    combine_w = [
        jnp.where(s < capacity, g, 0.0).astype(dtype)
        for s, g in zip(slots, gates)
    ]

    aux = switch_aux_loss(probs, total_mask / num_selected)
    return _Routing(choices, slots, combine_w), aux


# ---------------------------------------------------------------------------
# Gather-only permutation (round 3): dispatch/combine and BOTH their
# transposes run as row gathers. XLA's autodiff of a gather emits a
# scatter-add, and TPU row scatters cost ~2.3x a gather (round-3 chip
# microbench) — but a capacity slot is owned by at
# most ONE assignment, so every transpose is itself a gather through the
# inverse slot->assignment map. The custom_vjps below encode that.


def _routing_indices(routing: _Routing, num_experts: int, capacity: int,
                     tokens: int):
    """Stacked per-round destination indices plus the inverse map.

    ``dests/keeps [k, T]``: each assignment's flat buffer slot (clamped
    when dropped) and liveness. ``inv_token/inv_round/inv_valid [E*C]``:
    which (round, token) assignment owns each buffer slot. Building the
    inverse IS a scatter, but of int32 scalars (k*T * 4 bytes), not of
    D-wide rows — the 768x-smaller payload is the whole trick. Dropped
    assignments get the out-of-range flat index ec and fall out via
    ``mode="drop"`` (clamping would corrupt a neighbouring expert's
    slot 0); kept slots are unique by construction (the cumsum carry
    counts kept assignments only), so ``.set`` cannot collide."""
    ec = num_experts * capacity
    dests, keeps = [], []
    inv = jnp.full((ec,), -1, jnp.int32)
    for r, (e_idx, slot) in enumerate(zip(routing.expert_idx,
                                          routing.slot)):
        keep = slot < capacity
        flat = jnp.where(keep, e_idx * capacity + slot, ec)
        ids = (r * tokens
               + jax.lax.iota(jnp.int32, tokens))
        inv = inv.at[flat].set(ids, mode="drop")
        dests.append(jnp.where(keep, flat, 0))
        keeps.append(keep)
    inv_valid = inv >= 0
    safe_inv = jnp.where(inv_valid, inv, 0)
    return (jnp.stack(dests), jnp.stack(keeps),
            safe_inv % tokens, safe_inv // tokens, inv_valid)


@jax.custom_vjp
def _pack_rows(x, inv_token, inv_valid, dests, keeps):
    """[T, D] token rows -> [E*C, D] buffer rows (zeros in unowned
    slots): a single gather through the inverse map."""
    return jnp.where(inv_valid[:, None], x[inv_token], 0)


def _pack_rows_fwd(x, inv_token, inv_valid, dests, keeps):
    return _pack_rows(x, inv_token, inv_valid, dests, keeps), (dests, keeps)


def _pack_rows_bwd(res, g):
    dests, keeps = res
    # dx[t] = sum over the <=k slots that read token t — per-round
    # gathers, NOT the scatter-add autodiff would emit.
    dx = None
    for r in range(dests.shape[0]):
        term = jnp.where(keeps[r][:, None], g[dests[r]], 0)
        dx = term if dx is None else dx + term
    return dx, None, None, None, None


_pack_rows.defvjp(_pack_rows_fwd, _pack_rows_bwd)


@jax.custom_vjp
def _combine_rows(out_flat, w, dests, keeps, inv_token, inv_round,
                  inv_valid):
    """Gate-weighted combine: y[t] = sum_r w[r,t] * out_flat[dests[r,t]]
    (dropped assignments carry weight 0 already)."""
    y = None
    for r in range(dests.shape[0]):
        term = out_flat[dests[r]] * w[r][:, None]
        y = term if y is None else y + term
    return y


def _combine_fwd(out_flat, w, dests, keeps, inv_token, inv_round,
                 inv_valid):
    y = _combine_rows(out_flat, w, dests, keeps, inv_token, inv_round,
                      inv_valid)
    return y, (out_flat, w, dests, keeps, inv_token, inv_round, inv_valid)


def _combine_bwd(res, dy):
    out_flat, w, dests, keeps, inv_token, inv_round, inv_valid = res
    # d_out[ec] = w of the assignment owning the slot * dy of its token —
    # one gather through the inverse map (the scatter-free transpose).
    w_at_slot = w[inv_round, inv_token]                  # [E*C]
    dout = jnp.where(inv_valid[:, None],
                     dy[inv_token] * w_at_slot[:, None], 0)
    # dw[r, t] = <dy[t], out_flat[dests[r, t]]> for kept assignments —
    # recomputes the forward gather instead of carrying [k, T, D]
    # residuals (memory-flat; gathers are the cheap primitive here).
    dw = jnp.stack([
        jnp.where(keeps[r],
                  jnp.sum(dy * out_flat[dests[r]].astype(dy.dtype), -1),
                  0).astype(w.dtype)
        for r in range(dests.shape[0])
    ])
    return dout.astype(out_flat.dtype), dw, None, None, None, None, None


_combine_rows.defvjp(_combine_fwd, _combine_bwd)


def _pack_to_experts(x: jax.Array, idx, num_experts: int,
                     capacity: int) -> jax.Array:
    dests, keeps, inv_token, inv_round, inv_valid = idx
    buf = _pack_rows(x, inv_token, inv_valid, dests, keeps)
    return buf.reshape(num_experts, capacity, x.shape[1])


def _gather_from_experts(expert_out: jax.Array, routing: _Routing,
                         idx) -> jax.Array:
    num_experts, capacity, d = expert_out.shape
    dests, keeps, inv_token, inv_round, inv_valid = idx
    w = jnp.stack(routing.combine_w)                     # [k, T]
    return _combine_rows(expert_out.reshape(num_experts * capacity, d),
                         w, dests, keeps, inv_token, inv_round, inv_valid)


def _capacity(tokens: int, num_experts: int, capacity_factor: float,
              num_selected: int) -> int:
    # GShard top-k convention: top-k routing emits k*T assignments, so
    # capacity provisions k*T/E * factor slots per expert — otherwise even
    # perfectly uniform top-2 routing would capacity-drop ~37% of
    # assignments at the default capacity_factor of 1.25.
    return max(int(-(-tokens * num_selected * capacity_factor
                     // num_experts)),
               num_selected)


def moe_apply(expert_fn: Callable[[Any, jax.Array], jax.Array],
              expert_params: Any,
              x: jax.Array,
              gate_logits: jax.Array,
              axis_name: str = "expert",
              capacity_factor: float = 1.25,
              num_selected: int = 1,
              normalize_gates: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Run an MoE layer. MUST be called inside ``shard_map`` with
    ``expert_params`` sharded over ``axis_name`` (leading expert axis, one
    expert per device) and ``x``/``gate_logits`` carrying this device's
    tokens (``[T, D]`` / ``[T, E]``).

    Returns ``(y, aux_loss)``: ``y[T, D]`` is the gate-weighted expert
    output per token (zero for capacity-dropped tokens — add the residual
    outside), ``aux_loss`` the local Switch balancing loss (pmean it with
    the data loss).
    """
    num_experts = axis_size(axis_name)
    tokens, d_model = x.shape
    capacity = _capacity(tokens, num_experts, capacity_factor, num_selected)

    probs = jax.nn.softmax(gate_logits, axis=-1)  # [T, E]
    routing, aux = _route(
        probs, capacity, num_selected, normalize_gates, x.dtype)
    idx = _routing_indices(routing, num_experts, capacity, tokens)

    # Pack assignment rows into [E, C, D]; all-to-all so each device
    # receives its expert's buffer from every peer: [E_src, C, D].
    expert_in = _pack_to_experts(x, idx, num_experts, capacity)
    expert_in = jax.lax.all_to_all(expert_in, axis_name,
                                   split_axis=0, concat_axis=0)
    local_params = jax.tree.map(lambda a: jnp.squeeze(a, axis=0),
                                expert_params)
    expert_out = expert_fn(
        local_params, expert_in.reshape(num_experts * capacity, d_model))
    expert_out = expert_out.reshape(num_experts, capacity, -1)
    expert_out = jax.lax.all_to_all(expert_out, axis_name,
                                    split_axis=0, concat_axis=0)
    y = _gather_from_experts(expert_out, routing, idx)
    return y, aux


def moe_apply_dense(expert_fn: Callable[[Any, jax.Array], jax.Array],
                    stacked_params: Any,
                    x: jax.Array,
                    gate_logits: jax.Array,
                    capacity_factor: float = 1.25,
                    num_selected: int = 1,
                    normalize_gates: bool = True
                    ) -> Tuple[jax.Array, jax.Array]:
    """Single-device twin of :func:`moe_apply`: identical routing (same
    masks, same capacity drops), but every expert is resident — the expert
    dimension runs under ``vmap`` instead of ``all_to_all``. Use it outside
    ``shard_map`` (tests, single-chip runs, reference numerics)."""
    leaves = jax.tree.leaves(stacked_params)
    num_experts = leaves[0].shape[0]
    tokens, _ = x.shape
    capacity = _capacity(tokens, num_experts, capacity_factor, num_selected)

    probs = jax.nn.softmax(gate_logits, axis=-1)
    routing, aux = _route(
        probs, capacity, num_selected, normalize_gates, x.dtype)
    idx = _routing_indices(routing, num_experts, capacity, tokens)

    expert_in = _pack_to_experts(x, idx, num_experts,
                                 capacity)                  # [E, C, D]
    expert_out = jax.vmap(expert_fn)(stacked_params, expert_in)
    y = _gather_from_experts(expert_out, routing, idx)
    return y, aux


# ---------------------------------------------------------------------------
# Dropless routing over the experts a device HOLDS. The capacity path above
# packs fixed [E, C, D] buffers because ``all_to_all`` needs them, keeps one
# expert per device (or all of them) and drops what overflows. The layer
# below is told which experts it holds, routes over all of them, and gives
# the part of the layer's result that its own experts give: the share of an
# expert-parallel deployment one device computes between the two exchanges.
# Nothing here stands in for the other devices or for the exchange.


#: The largest source, in bytes, from which a row gather runs at the speed
#: of its output's writes: one the compiler can keep in the chip's 128 MiB
#: on-chip memory (``S(1)`` on the source in the compiled text) beside what
#: else lives there. From a source left in HBM every row costs five times
#: as much whatever the order of the indices. Measured on the TPU v5e
#: alone, bf16 rows 2560 wide, the step between 110 and 120 MiB
#: (``PERF.md`` section 6, PR 27): on another generation measure it again,
#: a block more than needed costs a slice and a concatenation of the block.
#: Two read it, through :func:`_gather_blocks`: :func:`_gather_sum` for the
#: sorted rows and :func:`_gather_rows` for the token rows (PR 46).
_GATHER_SOURCE_BYTES = 96 * 2 ** 20


def _gather_blocks(size: int, width: int) -> int:
    """Into how many blocks of columns :func:`_gather_sum` and
    :func:`_gather_rows` split a source of ``size`` bytes whose rows are
    ``width`` wide: the fewest whole 128-lane blocks that bring each under
    ``_GATHER_SOURCE_BYTES``."""
    lanes = width // 128 if width % 128 == 0 else 1
    return next((n for n in range(1, lanes + 1) if lanes % n == 0
                 and size <= n * _GATHER_SOURCE_BYTES), lanes)


def _gather_sum(rows, place, scale):
    """``y[t] = sum_j scale[j, t] * rows[place[j, t]]`` over the leading
    axis of ``place`` / ``scale`` ``[k, T]``, added in float32, in
    ``rows``' dtype. Where ``scale`` is 0 the row is not read for its
    value (it may hold anything). ``rows`` is gathered in blocks of
    columns small enough for the fast gather."""
    blocks = _gather_blocks(rows.size * rows.dtype.itemsize, rows.shape[-1])
    used = (scale != 0)[..., None]
    return jnp.concatenate([
        jnp.einsum("ktd,kt->td", jnp.where(used, block[place], 0), scale,
                   preferred_element_type=jnp.float32).astype(rows.dtype)
        for block in jnp.split(rows, blocks, axis=-1)], axis=-1)


def _gather_rows(source, index):
    """``source[index]`` for rows ``source`` ``[T, D]`` and ``index``
    ``[R]``, the same values to the bit. A source the chip keeps on chip
    is gathered whole. A larger one is gathered in blocks of columns small
    enough for the fast gather, each by the same ``index`` and written at
    its static column offset into one result that is never zeroed. A
    block is sliced only after the block before it is written (the
    barrier): sliced together, the blocks come out of one fusion of which
    the compiler keeps one on chip and leaves the others in HBM
    (``PERF.md`` section 6, PR 46)."""
    size, width = index.shape[0], source.shape[1]
    blocks = _gather_blocks(source.size * source.dtype.itemsize, width)
    if blocks == 1:
        return source[index]
    rows = jax.lax.empty((size, width), source.dtype)
    for start in range(0, width, width // blocks):
        if start:
            rows, source = jax.lax.optimization_barrier((rows, source))
        block = source[:, start:start + width // blocks]
        rows = jax.lax.dynamic_update_slice(rows, block[index], (0, start))
    return rows


class _Places(NamedTuple):
    """Where the sorted rows and the tokens' assignments find each other
    (int32 / bool; assignment ``[j, t]``, flat ``j * T + t``, is token
    t's j-th choice; the held experts' assignments sort first)."""
    mine: jax.Array     # [k*T] the assignment of each sorted row
    token: jax.Array    # [k*T] its token
    live: jax.Array     # [k*T] whether the row belongs to a group
    place: jax.Array    # [k, T] the sorted row of each assignment
    here: jax.Array     # [k, T] whether the assignment landed here
    landed: jax.Array   # [] how many rows belong to a group: the first


# ---------------------------------------------------------------------------
# The two movements of rows, each with its gradient written by hand. Both
# are sized for the worst case, every token choosing only experts held
# here: ``k x T`` rows each way. On a device that holds a small part of
# the experts most of those rows belong to no group (94% at a sixteenth
# held), and the sort keeps the landed rows first. So where
# :func:`walk_chunk` gives a chunk, the three rules that would gather and
# mask all rows (dispatch backward, combine forward and backward) walk
# the sorted order in static chunks instead and stop after the last
# chunk that holds a landed row: a ``while`` whose trip count the device
# reads from ``landed``, nothing chosen by a branch, the experts called
# once on the buffer they had. If everything lands here they walk every
# chunk: exact under any imbalance, as the one-pass rules are.


def walk_chunk(rows: int, n_held: int, num_experts: int) -> int:
    """Rows a chunk when :func:`moe_apply_held` walks a sorted order of
    ``rows`` (tokens x chosen) on a device that holds ``n_held`` of
    ``num_experts`` experts, or 0 where it does not walk but moves all
    rows at once: above an eighth of the experts held. (At a quarter
    held the whole prize was 0.7% of a step, and a router that collapses
    onto the held experts makes every chunk live: a walked row adds into
    its token by a scatter, at three times a gathered row's cost;
    ``PERF.md`` section 6, PRs 27 and 41.) A chunk is a quarter of the
    even share ``rows x n_held / num_experts``, in whole tiles of 8
    rows: an even router walks five chunks at most, and less than a
    quarter of a share for nothing."""
    if 8 * n_held > num_experts:
        return 0
    quarter = max(1, -(-rows * n_held // (4 * num_experts)))
    return min(rows, -(-quarter // 8) * 8)


def chunks_walked(landed, chunk: int):
    """How many chunks of ``chunk`` rows hold the ``landed`` first rows:
    the trip count of every walk (a device scalar there; the benchmark's
    ``moe_held_walked_pct`` calls it on the loads a step returned)."""
    return (landed + chunk - 1) // chunk


class _Chunk(NamedTuple):
    """One chunk of the sorted order."""
    start: jax.Array    # [] its first row
    token: jax.Array    # [Q] ``_Places.token`` of its rows
    mine: jax.Array     # [Q] ``_Places.mine`` of its rows
    live: jax.Array     # [Q] whether the row belongs to a group
    fresh: jax.Array    # [Q] and no earlier chunk held it


def _walk(at, chunk, step, init):
    """``step(_Chunk, carry) -> carry`` over the chunks that hold a
    landed row, in order. The last chunk of an order that is no whole
    number of chunks starts early enough to end with it and overlaps
    the one before: what ``step`` writes there it writes twice, the
    same; what it adds it adds where ``fresh``."""
    size = at.token.shape[0]

    def body(i, carry):
        start = jnp.minimum(i * chunk, size - chunk)
        rows = start + jnp.arange(chunk, dtype=jnp.int32)
        live = rows < at.landed
        return step(_Chunk(
            start=start,
            token=jax.lax.dynamic_slice(at.token, (start,), (chunk,)),
            mine=jax.lax.dynamic_slice(at.mine, (start,), (chunk,)),
            live=live, fresh=live & (rows >= i * chunk)), carry)

    return jax.lax.fori_loop(0, chunks_walked(at.landed, chunk), body, init)


def _rows_at(rows, c):
    """The rows of chunk ``c`` of a sorted buffer."""
    return jax.lax.dynamic_slice(
        rows, (c.start, 0), (c.token.shape[0], rows.shape[1]))


def _weights_at(weights, c):
    """``[Q, 1]`` the weight of each row of chunk ``c``'s assignment."""
    return weights.reshape(-1)[c.mine][:, None]


def _walk_sum(rows, at, chunk, weights=None):
    """``_gather_sum`` over the chunks that hold a landed row: each
    landed row, times its assignment's weight where ``weights`` are
    given, added into its token in float32 (the same terms in another
    order), in ``rows``' dtype. What the other rows hold is not read for
    its value."""
    def add(c, acc):
        terms = _rows_at(rows, c).astype(jnp.float32)
        if weights is not None:
            terms = terms * _weights_at(weights, c).astype(jnp.float32)
        return acc.at[c.token].add(jnp.where(c.fresh[:, None], terms, 0))

    return _walk(at, chunk, add, jnp.zeros(
        (at.place.shape[1], rows.shape[1]), jnp.float32)).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rows_of_tokens(chunk, x, at):
    """``[T, D]`` token rows -> the sorted rows: row i is token
    ``at.token[i]``. Rows outside every group are read by no one. All
    rows in one pass whatever ``chunk``, by :func:`_gather_rows`: one
    gather from token rows the chip keeps on chip, one a block of columns
    from larger ones. Such a gather runs at the speed of its writes, which
    a walk into a zeroed buffer does not beat (``PERF.md`` section 6, PRs
    41 and 46)."""
    return _gather_rows(x, at.token)


def _rows_of_tokens_fwd(chunk, x, at):
    return _gather_rows(x, at.token), at


def _rows_of_tokens_bwd(chunk, at, g):
    # A token's rows add into it: as k gathers and a sum, not the
    # scatter-add autodiff would emit (row by row, and over three times a
    # gather's cost on the v5e); walked, as a scatter-add of the landed
    # rows alone.
    if chunk:
        return _walk_sum(g, at, chunk), None
    return _gather_sum(g, at.place, at.here.astype(g.dtype)), None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tokens_of_rows(chunk, out, weights, at):
    """The weighted sum back into token order: ``y[t]`` adds
    ``weights[j, t] * out[at.place[j, t]]`` over token t's assignments
    that are ``at.here``."""
    if chunk:
        return _walk_sum(out, at, chunk, weights)
    return _gather_sum(out, at.place, jnp.where(at.here, weights, 0))


def _tokens_of_rows_fwd(chunk, out, weights, at):
    return _tokens_of_rows(chunk, out, weights, at), (out, weights, at)


def _tokens_of_rows_bwd(chunk, res, dy):
    if chunk:
        return _walked_tokens_of_rows_bwd(chunk, res, dy)
    out, weights, at = res
    live = at.live[:, None]
    g = _gather_rows(dy, at.token)
    d_out = jnp.where(live, g * weights.reshape(-1)[at.mine][:, None], 0)
    d_row = jnp.sum(jnp.where(
        live, g.astype(jnp.float32) * out.astype(jnp.float32), 0), axis=-1)
    d_weights = jnp.where(at.here, d_row[at.place], 0).astype(weights.dtype)
    return d_out, d_weights, None


def _walked_tokens_of_rows_bwd(chunk, res, dy):
    """The same three results chunk by chunk: ``d_out`` written in place
    into zeros, so it is zero wherever a row is not live; a row's sum
    set at its assignment (a sorted row is one assignment's: the indices
    are unique)."""
    out, weights, at = res

    def step(c, carry):
        d_out, d_row = carry
        live, g = c.live[:, None], dy[c.token]
        d_out = jax.lax.dynamic_update_slice(d_out, jnp.where(
            live, g * _weights_at(weights, c), 0).astype(out.dtype),
            (c.start, 0))
        d_row = d_row.at[c.mine].set(jnp.sum(jnp.where(
            live, g.astype(jnp.float32)
            * _rows_at(out, c).astype(jnp.float32), 0), axis=-1),
            unique_indices=True)
        return d_out, d_row

    d_out, d_row = _walk(at, chunk, step, (
        jnp.zeros_like(out), jnp.zeros((at.mine.shape[0],), jnp.float32)))
    return (d_out, d_row.reshape(weights.shape).astype(weights.dtype),
            None)


_tokens_of_rows.defvjp(_tokens_of_rows_fwd, _tokens_of_rows_bwd)


def grouped_gated_mlp(params: Any, rows: jax.Array, group_sizes: jax.Array,
                      activation: Callable = jax.nn.relu) -> jax.Array:
    """``w_down_e (act(w_gate_e h) * (w_up_e h))`` for rows sorted by
    expert, ``group_sizes[e]`` of them for the e-th expert held: three
    grouped products (``jax.lax.ragged_dot``, which the TPU compiler runs
    tile by tile over the rows the groups cover and no further).
    ``params``: ``w_gate`` / ``w_up`` ``[n, D, F]`` and ``w_down``
    ``[n, F, D]``, cast to the rows' dtype here."""
    def grouped(a, w):
        return jax.lax.ragged_dot(a, w.astype(a.dtype), group_sizes)

    hidden = activation(grouped(rows, params["w_gate"])) \
        * grouped(rows, params["w_up"])
    return grouped(hidden, params["w_down"])


def softmax_top_k(gate_logits: jax.Array, num_selected: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """The routing rule of :func:`moe_apply_held`'s first models: a token
    chooses its ``num_selected`` largest logits and weighs them by a
    softmax over the chosen logits (softmax over all, top k, renormalised:
    the same numbers). ``gate_logits`` ``[T, E]`` float32; returns
    ``(top_ids, weights)``, both ``[T, k]``."""
    top_logits, top_ids = jax.lax.top_k(gate_logits, num_selected)
    return top_ids, jax.nn.softmax(top_logits, axis=-1)


#: In the normalisation of :func:`sigmoid_top_k`'s weights, where the
#: model names no other.
_WEIGHT_SUM_EPS = 1e-6


def sigmoid_top_k(bias: jax.Array, eps: float = _WEIGHT_SUM_EPS) -> Callable:
    """A routing rule for :func:`moe_apply_held` whose choice is made on
    one array and whose weights are taken from another: the scores are
    ``s = sigmoid(gate_logits)``; a token chooses the ``num_selected``
    largest of ``s + bias`` (``bias`` ``[E]``, one float an expert, as an
    auxiliary-loss-free balancing rule keeps one: it enters the CHOICE
    only, takes no gradient and gives none); the weights are the chosen
    experts' own ``s`` over their sum plus ``eps``, which is the model's
    (1e-6 by default, 1e-20 in ``models/joyai.py``)."""
    def rule(gate_logits, num_selected):
        scores = jax.nn.sigmoid(gate_logits)
        _, top_ids = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(scores.dtype)),
            num_selected)
        # A chosen expert's own score, as a select over the router's
        # width: pointwise forward and backward, no gather along the
        # minor axis and no scatter-add behind it.
        chosen = top_ids[..., None] == jnp.arange(
            scores.shape[-1], dtype=top_ids.dtype)              # [T, k, E]
        weights = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0),
                          axis=-1)
        return top_ids, weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + eps)

    return rule


def moe_apply_held(expert_fn: Callable[[Any, jax.Array, jax.Array],
                                       jax.Array],
                   expert_params: Any,
                   x: jax.Array,
                   gate_logits: jax.Array,
                   held: Sequence[int],
                   num_selected: int,
                   route: Callable[[jax.Array, int],
                                   Tuple[jax.Array, jax.Array]]
                   = softmax_top_k) -> Tuple[jax.Array, jax.Array]:
    """A dropless top-k expert layer on the device that holds the experts
    ``held`` (ids into the router's ``gate_logits[T, E]``, static, in the
    order of ``expert_params``' leading axis).

    Every token chooses ``num_selected`` of ALL ``E`` experts and weighs
    them by the model's routing rule: ``route(gate_logits in float32,
    num_selected) -> (top_ids, weights)``, both ``[T, k]``, traced under
    ``hvd.moe.route``. :func:`softmax_top_k` (the default: the largest
    logits, a softmax over them) and :func:`sigmoid_top_k` (sigmoid
    scores, a bias an expert in the choice only, weights normalised by
    their sum) are the two the models here give; the rule is the only
    thing that differs between them, and dispatch, experts and combine
    are one code. The assignments
    whose expert is held here are sorted by expert, their rows gathered,
    ``expert_fn(expert_params, rows, group_sizes)`` applied (see
    :func:`grouped_gated_mlp`), and the weighted results summed per token.
    Returns ``(y, load)``: ``y[T, D]`` is the part of the layer's result
    that the held experts give (all of it when every expert is held; the
    parts of disjoint shares add up to it), ``load[len(held)]`` the
    assignments each held expert received.

    No capacity, no dropped assignment: the sorted rows have room for
    all ``T * num_selected`` assignments, the worst case of every token
    choosing only experts held here, so the result is exact under any
    imbalance. ``expert_fn`` is given ``group_sizes`` that cover the
    landed rows. The rows past them belong to no group: they hold the
    token rows of assignments that landed elsewhere (not zeros), what
    ``expert_fn`` returns for them is not read, and the gradient it is
    handed for them is zero. So ``expert_fn`` works row by row: a row's
    result hangs on that row and its expert's parameters alone.

    The sort keeps the landed rows first. Where ``held`` is an eighth of
    the router's width or less (:func:`walk_chunk`, from the two static
    sizes: no option), the rules that bring rows back to their tokens
    (the gradient of the dispatch, the combine and its gradient) walk the
    sorted order in static chunks of a quarter of the even share and stop
    after the last chunk that holds a landed row, instead of gathering
    all ``T * num_selected`` rows and masking most away; the dispatch
    itself stays one pass over all rows (one gather, or one a block of
    columns where the token rows are more than the chip keeps on chip:
    :func:`_gather_rows`), and ``expert_fn`` is called once on the
    buffer it had. The trip count is read on the device from ``load``: no
    branch, and every chunk is walked if everything lands here, so the
    result is as exact. Above an eighth held the program is the one-pass
    form, with no loop in it."""
    tokens, _ = x.shape
    num_experts = gate_logits.shape[-1]
    held = tuple(int(e) for e in held)
    if len(set(held)) != len(held) or not all(
            0 <= e < num_experts for e in held):
        raise ValueError(f"moe_apply_held: held={held} must be distinct "
                         f"expert ids below {num_experts}")
    n_held = len(held)
    slot_of = np.full((num_experts,), n_held, np.int32)     # not here
    slot_of[list(held)] = np.arange(n_held, dtype=np.int32)

    with jax.named_scope(profiler.SCOPE_MOE_ROUTE):
        top_ids, weights = route(gate_logits.astype(jnp.float32),
                                 num_selected)                  # [T, k]
        slots = jnp.asarray(slot_of)[top_ids]                   # [T, k]
        weights = jnp.where(slots < n_held, weights, 0.0).astype(x.dtype)
        # Round-major: assignment j * T + t, so that a token's choices
        # are a leading axis and never a 6-row tile.
        weights = weights.T                                     # [k, T]
        flat = slots.T.reshape(-1)                              # [k*T]

    with jax.named_scope(profiler.SCOPE_MOE_DISPATCH):
        # Held experts' assignments first, by expert, token order kept;
        # the others (slot n_held) sort to the end.
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        place = jnp.argsort(order).astype(jnp.int32).reshape(
            num_selected, tokens)
        load = jnp.sum(
            flat[None, :] == jnp.arange(n_held, dtype=jnp.int32)[:, None],
            axis=1, dtype=jnp.int32)
        landed = jnp.sum(load)
        at = _Places(mine=order, token=order % tokens,
                     live=jnp.arange(order.shape[0]) < landed,
                     place=place, here=place < landed, landed=landed)
        chunk = walk_chunk(order.shape[0], n_held, num_experts)
        rows = _rows_of_tokens(chunk, x, at)

    with jax.named_scope(profiler.SCOPE_MOE_EXPERTS):
        out = expert_fn(expert_params, rows, load)

    with jax.named_scope(profiler.SCOPE_MOE_COMBINE):
        y = _tokens_of_rows(chunk, out, weights, at)
    return y, load
