"""What the decoder configurations' test files share: the benchmark's
plain reference loaded by path, and the cut of a whole model's routed
experts to the share one device holds. Each configuration's own
``*_helpers.py`` keeps its mapping onto the reference's keys and its
seeded scales."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import causal_lm_loss, chunked_causal_lm_loss

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def load_reference(name):
    """``benchmarks/reference/<name>.py``, loaded by path (the names hold
    ``-`` and ``.``) with ``benchmarks`` on the path for its own
    import."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            name.split("-")[0] + "_reference",
            os.path.join(BENCH, "reference", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


def reference_fixture(name):
    """The module-scoped fixture ``reference`` of a configuration's test
    files: ``reference = reference_fixture("lfm2-24b-a2b")``."""
    return pytest.fixture(scope="module", name="reference")(
        lambda: load_reference(name))


def seeded_ids_and_params(model, seq, leaf):
    """Two rows of ``seq`` token ids and ``model``'s parameters drawn on
    them with ``leaf(path, x)`` applied to each: two programs, so that
    XLA does not fold a scale into the initializer's own and the weights
    are bit for bit what scaling leaf by leaf gave."""
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, seq), 0,
                             model.config.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), ids)["params"]
    return ids, jax.jit(lambda p: jax.tree_util.tree_map_with_path(
        leaf, p))(params)


def assert_same_loss_and_gradients(want_loss, loss, params, tolerance):
    """Two losses of ``params``, each ONE jitted program: the values
    agree to 1e-5 and every leaf of the gradient to ``tolerance`` of the
    largest entry of ``want_loss``'s leaf."""
    want, want_grads = jax.jit(jax.value_and_grad(want_loss))(params)
    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(grads))
    for (path, g), w in zip(flat, jax.tree.leaves(
            jax.device_get(want_grads))):
        scale = float(np.max(np.abs(w))) + 1e-12
        assert float(np.max(np.abs(g - w))) <= tolerance * scale, path


def _lm_loss(model, ids):
    return lambda p: causal_lm_loss(model.apply({"params": p}, ids)[0], ids)


def assert_matches_the_plain_reference(model, params, ids, reference, rcfg,
                                       tolerance):
    """``model``'s loss on ``ids`` and every gradient against the
    reference's mean loss over the rows, a sequence at a time."""
    def reference_loss(p):
        total = sum(reference.sequence_nll_sum(
            p, row, rnd=lambda a: a, config=rcfg) for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    assert_same_loss_and_gradients(reference_loss, _lm_loss(model, ids),
                                   params, tolerance)


def assert_the_benchmarks_step_is_the_plain_model(plain, fast, params, ids):
    """``fast`` (flash kernels, each block recomputed) with the loss in
    four chunks against ``plain`` with the full logits: one function."""
    def fast_loss(p):
        hidden, _ = fast.apply({"params": p}, ids, return_hidden=True)
        return chunked_causal_lm_loss(hidden, p["lm_head"]["kernel"], ids,
                                      num_chunks=4)

    assert_same_loss_and_gradients(_lm_loss(plain, ids), fast_loss, params,
                                   5e-3)


def assert_three_adamw_steps_match(loss, params, ids, reference, rcfg, tx,
                                   size, check=lambda name, r: None,
                                   steps=3):
    """Three steps (or ``steps``) of ``tx`` on ``loss``, ONE jitted step, against
    ``reference.follow`` with a replica a row of ``ids`` (Horovod's mean
    of the replicas' means): each step's loss, every leaf's first gradient
    to a part in a thousand of its largest entry (float32 through a few
    layers of weights scaled up), the parameters after to a twentieth of
    how far the reference moved them by ``size``. An ``expert_bias`` has a
    zero gradient and comes out bit for bit as it went in, on both sides;
    ``check(name, r)`` sees the other leaves' reference gradients."""
    @jax.jit
    def step(p, opt_state):
        value, grads = jax.value_and_grad(loss)(p)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, value, grads

    ours, opt_state, losses, first = params, jax.jit(tx.init)(params), [], None
    for _ in range(steps):
        ours, opt_state, value, grads = step(ours, opt_state)
        losses.append(float(value))
        first = grads if first is None else first
    their_losses, their_first, theirs = reference.follow(
        params, [(np.asarray(row)[None],) for row in ids], steps, rcfg)
    np.testing.assert_allclose(
        losses, [np.mean(step) for step in their_losses], rtol=2e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(params))
    for (path, start), g, r, a, b in zip(flat, *map(
            jax.tree.leaves, jax.device_get((first, their_first, ours,
                                             theirs)))):
        name = jax.tree_util.keystr(path)
        scale = float(np.max(np.abs(r))) + 1e-12
        assert float(np.max(np.abs(g - r))) <= 3e-3 * scale, name
        if "expert_bias" in name:
            assert not np.any(g) and not np.any(r)
            np.testing.assert_array_equal(a, start)
            np.testing.assert_array_equal(b, start)
            continue
        check(name, r)
        moved = float(size(b - start))
        assert moved > 0, name
        assert float(size(a - b)) <= 0.05 * moved, name


def assert_shares_add_up(block_of, p, their_layer, rcfg, shares, cfg, seq):
    """The block ``block_of(held)`` on ``share(p, held)`` less what every
    chip adds alike (the reference's ``their_layer(p, rows, rcfg)`` with no
    expert held), summed over the disjoint ``shares`` of the layer ``p``,
    is the routed part: with ``alike`` it is the uncut reference's layer,
    every assignment landing once and the routed part far above the
    tolerance; and so is the layer that holds every expert. One jitted
    program a call."""
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(11), (1, seq, cfg.dim))

    def block(held):
        out, load = jax.jit(lambda p: block_of(held).apply({"params": p}, x))(
            share(p, held))
        return out[0], load

    whole = jax.jit(lambda p: their_layer(p, x[0], rcfg))(p)
    alike = jax.jit(lambda p: their_layer(
        p, x[0], {**rcfg, "deployment": {"experts_held": []}}))(share(p, ()))
    parts, landed = 0.0, 0
    for held in shares:
        out, load = block(held)
        parts = parts + (out - alike)
        landed += int(load.sum())
    assert landed == seq * cfg.num_selected
    scale = float(np.max(np.abs(whole)))
    assert float(np.max(np.abs(parts))) > 100 * 2e-5 * scale
    np.testing.assert_allclose(alike + parts, whole, rtol=0,
                               atol=2e-5 * scale)
    np.testing.assert_allclose(block(None)[0], whole, rtol=0,
                               atol=2e-5 * scale)


def share(params, held):
    """``params`` of the model that holds every routed expert, cut to
    ``held``: the leading axis of every ``w_gate`` / ``w_up`` / ``w_down``
    that has one (a dense MLP's and a shared expert's are matrices);
    what every chip holds alike is left whole; ``None`` holds all."""
    if held is None:
        return params
    held = jnp.array(held, jnp.int32)

    def cut(path, x):
        names = {getattr(k, "key", None) for k in path}
        routed = x.ndim == 3 and names & {"w_gate", "w_up", "w_down"}
        return x[held] if routed else x

    return jax.tree_util.tree_map_with_path(cut, params)
