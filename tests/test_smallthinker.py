"""SmallThinker (``models/smallthinker.py``) and the dropless expert layer
it forced (``parallel/moe.py::moe_apply_held``), at a tiny size on seeded
weights (the model against the benchmark's plain float32 reference is
``test_smallthinker_reference.py``): the parts the four shares give add
up to the whole layer; nothing is dropped under a router forced onto one
expert, nor whatever part of the assignments lands on a share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (SMALLTHINKER_TINY, SmallThinkerLM,
                                causal_lm_loss, chunked_causal_lm_loss)
from horovod_tpu.models.smallthinker import SmallThinkerBlock
from horovod_tpu.ops.attention import make_attention_fn
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.moe import grouped_gated_mlp, moe_apply_held
from smallthinker_helpers import (SEQ, _config, _reference_config,  # noqa: F401
                                  _share, reference, seeded)


def test_flash_kernels_remat_and_chunked_loss_change_nothing(seeded):
    """The step the benchmark runs (flash attention by the program's own
    rule, each block recomputed, the loss in chunks) against the plain
    model: one function."""
    ids, params = seeded
    ids = jnp.concatenate([ids] * 4, axis=1)      # 512: four blocks a side
    plain = SmallThinkerLM(_config(num_layers=4))   # one period
    fast = SmallThinkerLM(
        _config(num_layers=4, remat=True),
        attention_fn=make_attention_fn(causal=True, use_flash=True,
                                       block_q=128, block_k=128),
        window_attention_fn=make_attention_fn(
            causal=True, use_flash=True, block_q=128, block_k=128,
            window=SMALLTHINKER_TINY.sliding_window))
    params = {k: params[k] for k in sorted(params)
              if k not in ("layer_4", "layer_5", "layer_6", "layer_7")}

    def plain_loss(p):
        return causal_lm_loss(plain.apply({"params": p}, ids)[0], ids)

    def fast_loss(p):
        hidden, _ = fast.apply({"params": p}, ids, return_hidden=True)
        return chunked_causal_lm_loss(hidden, p["lm_head"]["kernel"], ids,
                                      num_chunks=4)

    a, ga = jax.jit(jax.value_and_grad(plain_loss))(params)
    b, gb = jax.jit(jax.value_and_grad(fast_loss))(params)
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(x, y, rtol=0, atol=5e-3 * float(
            jnp.max(jnp.abs(x)) + 1e-12))


def test_parts_of_the_four_shares_add_up_to_the_whole_layer(seeded,
                                                            reference):
    """One layer: each share's output is ``a + (its experts' part)``, so
    the four parts, with attention and the residual counted once, are the
    whole-layer reference."""
    ids, params = seeded
    cfg = _config()
    layer = params["layer_1"]           # a windowed, rotated layer
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(11), (1, SEQ, cfg.dim))
    window_fn = make_attention_fn(causal=True, use_flash=False,
                                  window=cfg.sliding_window)

    def block(held, p):
        out, load = SmallThinkerBlock(
            _config(held), rope=True, attention_fn=window_fn).apply(
            {"params": p}, x)
        return out[0], load

    whole = reference._layer(lambda a: a, layer, x[0],
                             _reference_config(cfg), True, True)
    nothing_held = reference._layer(
        lambda a: a, layer, x[0],
        {**_reference_config(cfg), "deployment": {"experts_held": []}},
        True, True)                     # a: attention and the residual
    shares = [(0, 1), (2, 3), (4, 5), (6, 7)]
    parts, landed = 0.0, 0
    for held in shares:
        out, load = block(held, _share({"layer_1": layer}, held)["layer_1"])
        parts = parts + (out - nothing_held)
        landed += int(load.sum())
    assert landed == SEQ * cfg.num_selected     # every assignment, once
    np.testing.assert_allclose(nothing_held + parts, whole, rtol=0,
                               atol=2e-5 * float(jnp.max(jnp.abs(whole))))
    # The same from the layer that holds all eight.
    np.testing.assert_allclose(block(None, layer)[0], whole, rtol=0,
                               atol=2e-5 * float(jnp.max(jnp.abs(whole))))


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
    y, load = moe_apply_held(grouped_gated_mlp, mine, x, logits, held, k)
    chosen = jnp.zeros((tokens, experts)).at[:, jnp.array([3, 1])].set(
        jax.nn.softmax(jnp.array([5.0, 4.0])))
    here = jnp.zeros((experts,)).at[jnp.array(held)].set(1.0)
    np.testing.assert_allclose(
        y, _dense_experts(params, x, chosen * here), rtol=2e-5, atol=2e-5)
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
    x, logits = jnp.zeros((4, 16)), jnp.zeros((4, 6))
    params = _experts(jax.random.PRNGKey(0), 2)
    with pytest.raises(ValueError, match="distinct expert ids"):
        moe_apply_held(grouped_gated_mlp, params, x, logits, (1, 1), 2)
    with pytest.raises(ValueError, match="distinct expert ids"):
        moe_apply_held(grouped_gated_mlp, params, x, logits, (1, 6), 2)


HELD, EXPERTS, CHOSEN, TOKENS = (5, 2), 32, 2, 64


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


def _held_and_dense(logits, dtype=jnp.float32):
    """``(y, load, grads)`` of the layer with the share ``HELD`` and of
    the plain form, for the gradients of the matrices, ``x`` and the
    logits."""
    tokens = logits.shape[0]
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, 16), dtype)
    params = _experts(jax.random.PRNGKey(4), EXPERTS)
    mine = jax.tree.map(lambda w: w[jnp.array(HELD)], params)
    target = jax.random.normal(jax.random.PRNGKey(5), (tokens, 16))

    def ours(mine, x, logits):
        y, load = moe_apply_held(grouped_gated_mlp, mine, x, logits, HELD,
                                 CHOSEN)
        return jnp.sum(y * target), (y, load)

    def dense(mine, x, logits):
        top, ids = jax.lax.top_k(logits, CHOSEN)
        weights = jnp.zeros_like(logits).at[
            jnp.arange(tokens)[:, None], ids].set(jax.nn.softmax(top, -1))
        y = _dense_experts(mine, x, weights[:, jnp.array(HELD)])
        return jnp.sum(y * target), (y, jnp.sum(
            ids[:, :, None] == jnp.array(HELD), axis=(0, 1)))

    return [jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(
        mine, x, logits) for f in (ours, dense)]


@pytest.mark.parametrize("landed", [0, 13, 16, 17, 32, 33, TOKENS * CHOSEN],
                         ids=["none", "a-share", "a-tile", "a-tile-and-a-row",
                              "a-quarter", "a-quarter-and-a-row",
                              "every-assignment"])
def test_a_share_is_exact_whatever_lands(landed):
    """2 of 32 experts held: an even router lands 8 of the 128
    assignments here. Whatever lands, up to all of them, ``y``, ``load``
    and every gradient are the dense form's."""
    ((_, (y, load)), got), ((_, (want_y, want_load)), want) = \
        _held_and_dense(_landing(landed))
    assert int(load.sum()) == landed
    assert load.tolist() == want_load.tolist()
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


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


@pytest.mark.parametrize("landed", [0, 13, 64, TOKENS * CHOSEN],
                         ids=["none", "a-share", "half", "every-assignment"])
def test_a_row_wise_expert_fn_with_a_bias_gets_its_gradients(landed):
    """The rows past the groups hold other experts' token rows, not zeros
    (PR 27). For any ``expert_fn`` that works row by row that changes
    nothing: what it returns there is not read and the gradient it is
    handed there is zero, so its parameters' gradients, a bias's too, are
    those of each held expert applied to its own tokens."""
    logits = _landing(landed)
    x = jax.random.normal(jax.random.PRNGKey(2), (TOKENS, 16))
    params = {"w": 0.3 * jax.random.normal(jax.random.PRNGKey(6),
                                           (len(HELD), 16, 16)),
              "b": 0.5 + jax.random.normal(jax.random.PRNGKey(7),
                                           (len(HELD), 16))}
    target = jax.random.normal(jax.random.PRNGKey(5), (TOKENS, 16))

    def ours(params, x, logits):
        y, _ = moe_apply_held(_biased_experts, params, x, logits, HELD,
                              CHOSEN)
        return jnp.sum(y * target)

    def dense(params, x, logits):
        top, ids = jax.lax.top_k(logits, CHOSEN)
        weights = jnp.zeros_like(logits).at[
            jnp.arange(TOKENS)[:, None], ids].set(jax.nn.softmax(top, -1))
        every = jnp.full((TOKENS,), TOKENS)
        return sum(jnp.sum(
            weights[:, e, None] * target * _biased_experts(
                jax.tree.map(lambda p: p[i:i + 1], params), x,
                every[:1])) for i, e in enumerate(HELD))

    got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
        params, x, logits) for f in (ours, dense))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("held", [
    tuple(range(EXPERTS)), tuple(range(EXPERTS // 2)), HELD],
    ids=["all-held", "half-held", "a-share"])
def test_the_layer_lowers_to_one_path(held):
    """Whatever part of the experts is held, the program has one path:
    no branch chosen on the device, no loop, nothing lowered twice."""
    x = jnp.zeros((TOKENS, 16))
    logits = jnp.zeros((TOKENS, EXPERTS))
    mine = _experts(jax.random.PRNGKey(4), len(held))

    def loss(mine, x, logits):
        y, _ = moe_apply_held(grouped_gated_mlp, mine, x, logits, held,
                              CHOSEN)
        return jnp.sum(y)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        mine, x, logits).as_text()
    for branching in ("stablehlo.case", '"stablehlo.if"', "stablehlo.while"):
        assert branching not in text


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
