"""SmallThinker (``models/smallthinker.py``) and the dropless expert layer
it forced (``parallel/moe.py::moe_apply_held``), at a tiny size on seeded
weights (the model against the benchmark's plain float32 reference is
``test_smallthinker_reference.py``): the parts the four shares give add
up to the whole layer; nothing is dropped under a router forced onto one
expert, nor whatever part of the assignments lands on a share."""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import SMALLTHINKER_TINY, SmallThinkerLM
from horovod_tpu.models.smallthinker import SmallThinkerBlock
from horovod_tpu.ops.attention import make_attention_fn
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.moe import grouped_gated_mlp, moe_apply_held
from decoder_helpers import (assert_shares_add_up,
                             assert_the_benchmarks_step_is_the_plain_model)
from smallthinker_helpers import (SEQ, _config, _reference_config,  # noqa: F401
                                  reference, seeded)


def test_flash_kernels_remat_and_chunked_loss_change_nothing(seeded):
    """The step the benchmark runs (flash attention by the program's own
    rule, each block recomputed, the loss in chunks) against the plain
    model: one function."""
    ids, params = seeded
    ids = jnp.concatenate([ids] * 4, axis=1)      # 512: four blocks a side
    plain = SmallThinkerLM(_config())
    fast = SmallThinkerLM(
        _config(remat=True),
        attention_fn=make_attention_fn(causal=True, use_flash=True,
                                       block_q=128, block_k=128),
        window_attention_fn=make_attention_fn(
            causal=True, use_flash=True, block_q=128, block_k=128,
            window=SMALLTHINKER_TINY.sliding_window))
    assert_the_benchmarks_step_is_the_plain_model(plain, fast, params, ids)


def test_parts_of_the_four_shares_add_up_to_the_whole_layer(seeded,
                                                            reference):
    """One layer: each share's output is ``a + (its experts' part)``, so
    the four parts, with attention and the residual counted once, are the
    whole-layer reference."""
    cfg = _config()
    window_fn = make_attention_fn(causal=True, use_flash=False,
                                  window=cfg.sliding_window)
    # Layer 1 is windowed and rotated; alike on every chip: a, attention
    # and the residual.
    assert_shares_add_up(
        lambda held: SmallThinkerBlock(_config(held), rope=True,
                                       attention_fn=window_fn),
        seeded[1]["layer_1"], lambda p, rows, rcfg: reference._layer(
            lambda a: a, p, rows, rcfg, True, True), _reference_config(cfg),
        [(0, 1), (2, 3), (4, 5), (6, 7)], cfg, SEQ)


def _experts(key, n, d=16, f=24):
    ks = jax.random.split(key, 3)
    return {"w_gate": jax.random.normal(ks[0], (n, d, f)),
            "w_up": jax.random.normal(ks[1], (n, d, f)),
            "w_down": jax.random.normal(ks[2], (n, f, d))}


def _dense_experts(params, x, weights):
    """Every expert on every token, weighted: the plain form."""
    hidden = jax.nn.relu(jnp.einsum("td,edf->etf", x, params["w_gate"])) \
        * jnp.einsum("td,edf->etf", x, params["w_up"])
    return jnp.einsum("etd,te->td",
                      jnp.einsum("etf,efd->etd", hidden, params["w_down"]),
                      weights)


@pytest.mark.parametrize("held", [(0, 1, 2, 3, 4, 5), (3,), (1, 4)],
                         ids=["all", "the-one", "one-of-two"])
def test_nothing_is_dropped_when_every_token_goes_to_one_expert(held):
    """A router forced onto experts 3 and 1, in that order, for every
    token: the capacity path would drop all but a buffer's worth; here
    expert 3 gets all 64 rows and the result is exact."""
    tokens, experts, k = 64, 6, 2
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, 16))
    logits = jnp.tile(jnp.array([-3.0, 4.0, -2.0, 5.0, -1.0, -4.0]),
                      (tokens, 1))
    params = _experts(jax.random.PRNGKey(1), experts)
    mine = jax.tree.map(lambda w: w[jnp.array(held)], params)
    y, load = jax.jit(lambda mine, x, logits: moe_apply_held(
        grouped_gated_mlp, mine, x, logits, held, k))(mine, x, logits)
    chosen = jnp.zeros((tokens, experts)).at[:, jnp.array([3, 1])].set(
        jax.nn.softmax(jnp.array([5.0, 4.0])))
    here = jnp.zeros((experts,)).at[jnp.array(held)].set(1.0)
    np.testing.assert_allclose(
        y, jax.jit(_dense_experts)(params, x, chosen * here), rtol=2e-5,
        atol=2e-5)
    assert load.tolist() == [tokens if e in (1, 3) else 0 for e in held]


def test_held_layer_gradients_match_the_dense_form():
    tokens, experts, k, held = 48, 8, 3, (1, 2, 6)
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, 16))
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(3),
                                     (tokens, experts))
    params = _experts(jax.random.PRNGKey(4), experts)
    mine = jax.tree.map(lambda w: w[jnp.array(held)], params)
    target = jax.random.normal(jax.random.PRNGKey(5), (tokens, 16))

    def ours(mine, x, logits):
        y, _ = moe_apply_held(grouped_gated_mlp, mine, x, logits, held, k)
        return jnp.sum(y * target)

    def dense(mine, x, logits):
        top, ids = jax.lax.top_k(logits, k)
        weights = jnp.zeros_like(logits).at[
            jnp.arange(tokens)[:, None], ids].set(jax.nn.softmax(top, -1))
        return jnp.sum(_dense_experts(mine, x, weights[:, jnp.array(held)])
                       * target)

    got = jax.jit(jax.grad(ours, argnums=(0, 1, 2)))(mine, x, logits)
    want = jax.jit(jax.grad(dense, argnums=(0, 1, 2)))(mine, x, logits)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_held_ids_are_checked():
    x, logits = np.zeros((4, 16), np.float32), np.zeros((4, 6), np.float32)
    params = {name: np.zeros((2,) + shape, np.float32) for name, shape in (
        ("w_gate", (16, 24)), ("w_up", (16, 24)), ("w_down", (24, 16)))}
    with pytest.raises(ValueError, match="distinct expert ids"):
        moe_apply_held(grouped_gated_mlp, params, x, logits, (1, 1), 2)
    with pytest.raises(ValueError, match="distinct expert ids"):
        moe_apply_held(grouped_gated_mlp, params, x, logits, (1, 6), 2)


HELD, EXPERTS, CHOSEN, TOKENS = (5, 2), 32, 2, 64
# A sixteenth of the experts held: the combine and the gradients of both
# movements walk the sorted order in chunks of this many rows (the even
# share, 8 of 128).
CHUNK = moe.walk_chunk(TOKENS * CHOSEN, len(HELD), EXPERTS)
# The sigmoid rule's bias: it takes experts 8 to 11 out of the choice of
# the tokens that would have chosen them (no held expert among them nor
# among what they choose next, so the landed count stands) and moves the
# normalisation of their weights.
BIAS = (1e-4 * jax.random.normal(jax.random.PRNGKey(8), (EXPERTS,))
        ).at[8:12].set(-0.5)


def _landing(landed, tokens=TOKENS, seed=0):
    """Logits under which exactly ``landed`` of the ``tokens * 2``
    assignments choose an expert of ``HELD``: the first tokens choose both
    held experts, one more chooses one if ``landed`` is odd, the rest
    none. Noise far below the forced gaps keeps every logit apart from
    its neighbours, so no choice hangs on a tie."""
    rng = np.random.RandomState(seed)
    logits = 0.2 * rng.randn(tokens, EXPERTS)
    logits[:, list(HELD)] -= 8.0
    logits[np.arange(tokens), rng.randint(8, 16, tokens)] += 6.0
    logits[np.arange(tokens), rng.randint(16, 24, tokens)] += 6.0
    both, one = divmod(landed, 2)
    logits[:both, list(HELD)] += 20.0
    logits[both:both + one, HELD[0]] += 20.0
    return jnp.asarray(rng.permutation(logits), jnp.float32)


def _softmax_choice(logits):
    top, ids = jax.lax.top_k(logits, CHOSEN)
    return ids, jax.nn.softmax(top, -1)


def _sigmoid_choice(logits):
    """The sigmoid rule with ``BIAS`` in the choice, written out plainly."""
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + BIAS, CHOSEN)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)


RULES = {"softmax": (moe.softmax_top_k, _softmax_choice),
         "sigmoid": (moe.sigmoid_top_k(BIAS), _sigmoid_choice)}


def _poisoned_experts(params, rows, group_sizes):
    """The grouped products, and NaN for every row past the groups: what
    an ``expert_fn`` may return where it is read by no one."""
    out = grouped_gated_mlp(params, rows, group_sizes)
    return jnp.where((jnp.arange(rows.shape[0])
                      < jnp.sum(group_sizes))[:, None], out, jnp.nan)


def _value_and_grads(f):
    """``((loss, (y, load)), grads)`` of ``f(mine, x, logits, target)``
    for the gradients of the matrices, ``x`` and the logits, jitted."""
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))


@functools.lru_cache(maxsize=None)
def _dense_program(rule):
    """The plain form under a routing rule: every held expert on every
    token in float32, weighed by the rule written out."""
    choice = RULES[rule][1]

    def dense(mine, x, logits, target):
        ids, chosen = choice(logits)
        weights = jnp.zeros_like(logits).at[
            jnp.arange(logits.shape[0])[:, None], ids].set(chosen)
        y = _dense_experts(mine, x, weights[:, jnp.array(HELD)])
        return jnp.sum(y * target), (y, jnp.sum(
            ids[:, :, None] == jnp.array(HELD), axis=(0, 1)))

    return _value_and_grads(dense)


@functools.lru_cache(maxsize=None)
def _held_program(rule, expert_fn):
    """The layer with the share ``HELD`` under a routing rule and an
    ``expert_fn``."""
    def ours(mine, x, logits, target):
        y, load = moe_apply_held(expert_fn, mine, x, logits, HELD, CHOSEN,
                                 route=RULES[rule][0])
        return jnp.sum(y * target), (y, load)

    return _value_and_grads(ours)


def _held_and_dense(logits, rule="softmax", dtype=jnp.float32,
                    expert_fn=grouped_gated_mlp):
    """``[ours, dense]``, each ``((loss, (y, load)), grads)``. One jitted
    program a rule (and an ``expert_fn``) for the module, compiled once a
    shape and a dtype of its arguments: the logits are an argument (PR 39's
    rule 1). The plain form is given the same rows in float32."""
    shape = (logits.shape[0], 16)
    mine = jax.tree.map(lambda w: w[jnp.array(HELD)],
                        _experts(jax.random.PRNGKey(4), EXPERTS))
    x = jax.random.normal(jax.random.PRNGKey(2), shape, dtype)
    target = jax.random.normal(jax.random.PRNGKey(5), shape)
    return [_held_program(rule, expert_fn)(mine, x, logits, target),
            _dense_program(rule)(mine, x.astype(jnp.float32), logits, target)]


def _assert_the_dense_form(logits, landed, rule="softmax",
                           expert_fn=grouped_gated_mlp):
    """``y``, ``load`` and every gradient of the layer with the share
    ``HELD`` are the plain form's, ``landed`` assignments on the share."""
    ((_, (y, load)), got), ((_, (want_y, want_load)), want) = \
        _held_and_dense(logits, rule, expert_fn=expert_fn)
    assert int(load.sum()) == landed
    assert load.tolist() == want_load.tolist()
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    return y, got


@pytest.mark.parametrize("landed", [
    0, 13, 16, 17, 32, 33, TOKENS * CHOSEN,
    CHUNK - 1, CHUNK, CHUNK + 1, 5 * CHUNK + 3],
    ids=["none", "a-share", "a-tile", "a-tile-and-a-row", "a-quarter",
         "a-quarter-and-a-row", "every-assignment", "a-chunk-less-a-row",
         "a-chunk", "a-chunk-and-a-row", "five-chunks-and-three-rows"])
def test_a_share_is_exact_whatever_lands(landed):
    """2 of 32 experts held: an even router lands 8 of the 128
    assignments here, one chunk of the walk. Whatever lands, up to all of
    them, ``y``, ``load`` and every gradient are the dense form's."""
    _assert_the_dense_form(_landing(landed), landed)


@pytest.mark.parametrize("landed", [93, 97, 100],
                         ids=["the-last-whole-chunk", "a-row-of-the-last",
                              "every-assignment"])
def test_a_share_is_exact_where_the_order_is_no_whole_number_of_chunks(
        landed):
    """50 tokens: 100 sorted rows in chunks of 8. The last chunk starts
    at row 92 to end with the order and overlaps the one before by four
    rows, which are added once."""
    assert (50 * CHOSEN) % moe.walk_chunk(50 * CHOSEN, len(HELD), EXPERTS)
    _assert_the_dense_form(_landing(landed, tokens=50), landed)


@pytest.mark.parametrize("landed", [13, TOKENS * CHOSEN],
                         ids=["a-share", "every-assignment"])
def test_a_share_is_exact_under_the_sigmoid_rule_with_its_bias(landed):
    """Sigmoid scores, ``BIAS`` in the choice and not in the weights: the
    bias moves the choice of tokens that land elsewhere, where there are
    any, and with it what their weights are normalised by."""
    logits = _landing(landed)
    unbiased = jax.lax.top_k(jax.nn.sigmoid(logits), CHOSEN)[1]
    moved = jnp.any(jnp.sort(_sigmoid_choice(logits)[0]) != jnp.sort(unbiased))
    assert bool(moved) == (landed < TOKENS * CHOSEN)
    _assert_the_dense_form(logits, landed, rule="sigmoid")


def test_a_share_of_bf16_rows_is_the_dense_form_to_bf16s_rounding():
    """The rows in bfloat16, as the models hand them over: the walk adds
    the same products in float32 in another order and rounds once. The
    plain form runs in float32 on the same rounded rows; the layer rounds
    three products deep and its sums once: eight half units in the last
    place of the largest entry, 2**-6 of it."""
    ((_, (y, load)), got), ((_, (want_y, want_load)), want) = \
        _held_and_dense(_landing(13), dtype=jnp.bfloat16)
    assert load.tolist() == want_load.tolist() and int(load.sum()) == 13
    for g, w in zip([y] + jax.tree.leaves(got),
                    [want_y] + jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g.astype(jnp.float32), w, rtol=0,
            atol=2.0 ** -6 * float(np.max(np.abs(w))))


def test_rows_no_one_reads_may_hold_anything():
    """An ``expert_fn`` that returns NaN for the rows past the groups:
    ``y`` and every gradient are finite and the dense form's."""
    y, got = _assert_the_dense_form(_landing(13), 13,
                                    expert_fn=_poisoned_experts)
    assert all(bool(jnp.all(jnp.isfinite(a)))
               for a in [y] + jax.tree.leaves(got))


def _biased_experts(params, rows, group_sizes):
    """An ``expert_fn`` that is no grouped product: a row norm, a dense
    product with the row's expert's matrix and a bias. Rows past the
    groups are given the last expert, so what they hold is computed with
    and would show in the parameters' gradients if it were handed any."""
    ends = jnp.cumsum(group_sizes)
    expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(rows.shape[0]), side="right"), ends.shape[0] - 1)
    normed = rows / jnp.sqrt(jnp.mean(rows ** 2, axis=-1, keepdims=True)
                             + 1e-6)
    return jnp.tanh(jnp.einsum("rd,rdf->rf", normed, params["w"][expert])
                    + params["b"][expert])


@functools.lru_cache(maxsize=None)
def _biased_programs():
    def ours(params, x, logits, target):
        y, _ = moe_apply_held(_biased_experts, params, x, logits, HELD,
                              CHOSEN)
        return jnp.sum(y * target)

    def dense(params, x, logits, target):
        ids, chosen = _softmax_choice(logits)
        weights = jnp.zeros_like(logits).at[
            jnp.arange(TOKENS)[:, None], ids].set(chosen)
        every = jnp.full((1,), TOKENS)
        return sum(jnp.sum(
            weights[:, e, None] * target * _biased_experts(
                jax.tree.map(lambda p: p[i:i + 1], params), x, every))
            for i, e in enumerate(HELD))

    return [jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))
            for f in (ours, dense)]


@pytest.mark.parametrize("landed", [0, 13, 64, TOKENS * CHOSEN],
                         ids=["none", "a-share", "half", "every-assignment"])
def test_a_row_wise_expert_fn_with_a_bias_gets_its_gradients(landed):
    """The rows past the groups hold other experts' token rows, not zeros
    (PR 27). For any ``expert_fn`` that works row by row that changes
    nothing: what it returns there is not read and the gradient it is
    handed there is zero (walked or not: PR 41), so its parameters'
    gradients, a bias's too, are those of each held expert applied to its
    own tokens."""
    logits = _landing(landed)
    x = jax.random.normal(jax.random.PRNGKey(2), (TOKENS, 16))
    params = {"w": 0.3 * jax.random.normal(jax.random.PRNGKey(6),
                                           (len(HELD), 16, 16)),
              "b": 0.5 + jax.random.normal(jax.random.PRNGKey(7),
                                           (len(HELD), 16))}
    target = jax.random.normal(jax.random.PRNGKey(5), (TOKENS, 16))
    got, want = (f(params, x, logits, target) for f in _biased_programs())
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


@functools.lru_cache(maxsize=None)
def _lowered(held):
    """The layer's value and gradients with ``held`` of the 32 experts,
    lowered for the TPU."""
    x = jax.ShapeDtypeStruct((TOKENS, 16), jnp.float32)
    logits = jax.ShapeDtypeStruct((TOKENS, EXPERTS), jnp.float32)
    mine = {name: jax.ShapeDtypeStruct((len(held),) + shape, jnp.float32)
            for name, shape in (("w_down", (24, 16)), ("w_gate", (16, 24)),
                                ("w_up", (16, 24)))}

    def loss(mine, x, logits):
        y, _ = moe_apply_held(grouped_gated_mlp, mine, x, logits, held,
                              CHOSEN)
        return jnp.sum(y)

    # For the TPU, where a grouped product is one operation and not the
    # masked dense products the CPU is given.
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).trace(
        mine, x, logits).lower(lowering_platforms=("tpu",)).as_text()


def _grouped_products(text):
    return len(re.findall(r"= \"?(?:stablehlo|chlo)\.ragged_dot", text))


# The first 16 hexadecimal digits of the SHA-256 of ``_lowered(held)``
# recorded on the parent commit 5d95678, whose layer had the one form.
PARENTS_TEXT = {
    "all-held": (tuple(range(EXPERTS)), "3df94f166fd0b8c8"),
    "half-held": (tuple(range(EXPERTS // 2)), "52eab87544e6d318"),
    "a-quarter-held": (tuple(range(EXPERTS // 4)), "8f697dd4e4e8501c"),
}


@pytest.mark.parametrize("name", sorted(PARENTS_TEXT))
def test_the_layer_lowers_to_one_path(name):
    """With more than an eighth of the experts held the program has one
    path: no branch chosen on the device, no loop, nothing lowered twice;
    the text is the parent's rule's, letter for letter."""
    held, digest = PARENTS_TEXT[name]
    text = _lowered(held)
    for branching in ("stablehlo.case", '"stablehlo.if"', "stablehlo.while"):
        assert branching not in text
    assert not moe.walk_chunk(TOKENS * CHOSEN, len(held), EXPERTS)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_a_share_lowers_to_loops_and_no_branch():
    """With an eighth held or less three of the four rules that move
    rows are loops whose trip count the device reads (the dispatch stays
    one gather); still no branch, and the
    experts' grouped products are lowered as many times as with every
    expert held: ``expert_fn`` is called once."""
    text = _lowered(HELD)
    assert text.count("stablehlo.while") == 3
    for branching in ("stablehlo.case", '"stablehlo.if"'):
        assert branching not in text
    assert _grouped_products(text) == _grouped_products(
        _lowered(tuple(range(EXPERTS)))) == 9


@pytest.mark.parametrize("size,width,blocks", [
    # The cell: 98,304 sorted rows of 2560 bf16 are exactly five times the
    # largest fast source; 36,864 rows are two; 16,384 token rows are one.
    (98304 * 2560 * 2, 2560, 5),
    (36864 * 2560 * 2, 2560, 2),
    (16384 * 2560 * 2, 2560, 1),
    # On the limit one block, a byte over it two.
    (96 * 2 ** 20, 2560, 1),
    (96 * 2 ** 20 + 1, 2560, 2),
    # Only whole 128-lane blocks that divide the width: 768 = 6 lanes has
    # no five, and rows that are no multiple of 128 wide are not split.
    (98304 * 768 * 4, 768, 3),
    (450_000_000, 768, 6),
    (98304 * 2000 * 2, 2000, 1),
    # More than the lanes can bring under the limit: one lane a block.
    (2 ** 32, 256, 2),
])
def test_gather_blocks_by_hand(size, width, blocks):
    assert moe._gather_blocks(size, width) == blocks


def _sorted_places(slots, n_held):
    """``moe._Places`` of the assignments ``slots`` ``[k, T]`` (the held
    slot of each, ``n_held`` where it landed elsewhere), as
    ``moe_apply_held`` makes them."""
    flat = slots.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32).reshape(slots.shape)
    landed = jnp.sum(flat < n_held)
    return moe._Places(
        mine=order, token=order % slots.shape[1],
        live=jnp.arange(order.shape[0]) < landed, place=place,
        here=place < landed, landed=landed)


def _with_no_limit(program):
    """``program()`` traced as if every source fitted on chip."""
    with pytest.MonkeyPatch.context() as unlimited:
        unlimited.setattr(moe, "_GATHER_SOURCE_BYTES", 2 ** 40)
        return program()


def _gathered(width, index):
    """``_gather_rows`` of 40 float32 rows ``width`` wide by ``index``
    beside ``source[index]``."""
    source = jnp.asarray(np.random.RandomState(3).randn(40, width),
                         jnp.float32)
    return moe._gather_rows(source, index), source[index]


def _dispatched(chunk):
    """Value and gradient through ``_rows_of_tokens`` for 24 tokens of 256
    float32 (24 KiB), two choices each over two held slots and elsewhere,
    under the limit in force beside the same under no limit."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(24, 256), jnp.float32)
    slots = jnp.asarray(rng.randint(0, 3, (2, 24)), jnp.int32)
    cot = jnp.asarray(rng.randn(48, 256), jnp.float32)

    def value_and_grad():
        return jax.value_and_grad(lambda x: jnp.sum(
            moe._rows_of_tokens(chunk, x, _sorted_places(slots, 2)) * cot)
        )(x)

    return value_and_grad(), _with_no_limit(value_and_grad)


OUT_OF_ORDER = np.random.RandomState(5).permutation(40)[:33]
REPEATED = np.random.RandomState(6).randint(0, 40, 100)


@pytest.mark.parametrize("limit,blocks,case", [
    # 40 rows of 768 float32 are 122,880 bytes in six lanes: whole, in
    # halves and in thirds by the limit alone.
    (122_880, 1, functools.partial(_gathered, 768, REPEATED)),
    (122_879, 2, functools.partial(_gathered, 768, REPEATED)),
    (61_439, 3, functools.partial(_gathered, 768, OUT_OF_ORDER)),
    (61_440, 2, functools.partial(_gathered, 768, OUT_OF_ORDER)),
    # Rows that are no whole lanes wide are not split, however large.
    (1_000, 1, functools.partial(_gathered, 200, REPEATED)),
    (1_000, 1, functools.partial(_gathered, 200, OUT_OF_ORDER)),
    # Through the dispatch and its gradient, not walked and walked.
    (24 * 256 * 4 - 1, 2, functools.partial(_dispatched, 0)),
    (24 * 256 * 4 - 1, 2, functools.partial(_dispatched, 8)),
], ids=["whole-on-the-limit", "halves", "thirds", "halves-on-the-limit",
        "no-whole-lanes-repeated", "no-whole-lanes-out-of-order",
        "dispatch-and-gradient", "dispatch-and-gradient-walked"])
def test_rows_gathered_in_blocks_are_the_rows_gathered_whole(
        limit, blocks, case, monkeypatch):
    """``_gather_rows`` moves rows and computes nothing: in however many
    blocks of columns the limit asks for, the result is ``source[index]``
    to the bit, and so are the dispatch's rows and their gradient. One
    compiled program a case: what the limit makes beside what no limit
    makes."""
    monkeypatch.setattr(moe, "_GATHER_SOURCE_BYTES", limit)
    lowered = jax.jit(case).lower()
    # The row gathers alone, a ``[rows, width]`` result each (the walked
    # gradient's gathers of chunk indices give vectors): one a block, and
    # the one gathered whole beside them.
    assert len(re.findall(r"stablehlo\.gather.*-> tensor<\d+x\d+xf32>",
                          lowered.as_text())) == blocks + 1
    got, want = lowered.compile()()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, w)
