"""Ouro (``models/ouro.py``), the looped stack
(``decoder.looped_decoder_layers``) and the loss under weights the gate
learns, at a tiny size on seeded weights: the model against the
benchmark's plain float32 reference (loss, first gradient, the parameters
after three AdamW steps, leaf by leaf); a shared leaf's gradient is the
sum of the gradients of four unshared copies; with one pass the loss is
the plain next-token mean and the gate takes no gradient; the exit
distribution by hand; the benchmark's step (flash kernels, every
application recomputed, the exits in one sweep) is the plain model; and
models broken underneath are other functions. The parameter tree is
written out in ``test_decoder_parts.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import (OURO_TINY, OuroLM, causal_lm_loss,
                                exit_distribution, ouro_lm_loss)
from horovod_tpu.models import ouro
from horovod_tpu.ops.attention import make_attention_fn
from decoder_helpers import (assert_same_loss_and_gradients,
                             assert_three_adamw_steps_match,
                             reference_fixture, seeded_ids_and_params)

SEQ = 64
OPTIMIZER = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.1)

reference = reference_fixture("ouro-2.6b")


def _config(**over):
    return dataclasses.replace(OURO_TINY, dtype=jnp.float32, **over)


def _reference_config(cfg, **optimizer):
    """The model's sizes under the keys the configuration file has."""
    return {"num_layers": cfg.num_layers, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "total_ut_steps": cfg.total_ut_steps,
            "assumed": {"exit_entropy_beta": cfg.exit_entropy_beta},
            "optimizer": optimizer}


def _loss(model, ids, num_chunks=4):
    """The training loss of ``model`` on ``ids``, as the builder takes
    it."""
    def loss(p):
        states, gates = model.apply({"params": p}, ids, return_hidden=True)
        return ouro_lm_loss(states, gates, p["lm_head"]["kernel"], ids,
                            num_chunks=num_chunks,
                            beta=model.config.exit_entropy_beta)[0]

    return loss


def _reference_loss(reference, rcfg, ids):
    def loss(p):
        total = sum(reference.sequence_loss_sum(
            p, row, rnd=lambda a: a, config=rcfg) for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    return loss


def _scaled(path, x):
    """Scales at which every path matters: a gate whose logits are a few
    units apart from position to position, a bias that is not zero, and
    matrices half again as large as drawn. No larger: eight applications
    of a block deep, with a unit-sized addition to the stream from every
    sublayer, float32's own rounding reads 2e-3 in a gradient at three
    times the draw, 6e-5 at twice and 4e-6 here."""
    names = {str(getattr(k, "key", k)) for k in path}
    if "early_exit_gate" in names:
        return x * 10.0 if x.ndim > 1 else x + 0.3
    return x * 1.5 if x.ndim > 1 else x


@pytest.fixture(scope="module")
def seeded():
    return seeded_ids_and_params(OuroLM(_config()), SEQ, _scaled)


def test_three_adamw_steps_match_the_plain_reference(seeded, reference):
    """Each step's loss, every leaf's first gradient and the parameters
    after three steps, the gate and its bias among them."""
    ids, params = seeded
    model = OuroLM(_config())
    moved = {}
    assert_three_adamw_steps_match(
        _loss(model, ids), params, ids, reference,
        _reference_config(model.config, **OPTIMIZER),
        optax.adamw(**OPTIMIZER), size=lambda x: np.max(np.abs(x)),
        check=lambda name, r: moved.setdefault(name, float(np.abs(r).max())))
    # The gate learns: its kernel and its bias have gradients.
    assert moved["['early_exit_gate']['kernel']"] > 0
    assert moved["['early_exit_gate']['bias']"] > 0


def test_a_shared_leafs_gradient_is_the_sum_over_four_unshared_copies(
        seeded, reference):
    """The program's gradient of every leaf against the reference run
    with a copy of the parameters a pass, the four copies' gradients
    summed: what ``hvd.DistributedOptimizer`` is handed is one gradient a
    shared leaf. The lookup reads the first copy and the last pass's gate
    is not read, so those copies' gradients of them are zero."""
    ids, params = seeded
    model = OuroLM(_config())
    rcfg = _reference_config(model.config)
    passes = model.config.total_ut_steps

    def unshared(copies):
        total = sum(reference.sequence_loss_sum(
            None, row, rnd=lambda a: a, config=rcfg, passes=copies)
            for row in ids)
        return total / (ids.shape[0] * (ids.shape[1] - 1))

    by_copy = jax.device_get(jax.jit(jax.grad(unshared))([params] * passes))
    ours = jax.device_get(jax.jit(jax.grad(_loss(model, ids)))(params))
    summed = jax.tree.map(lambda *g: sum(g), *by_copy)
    flat, _ = jax.tree_util.tree_flatten_with_path(ours)
    for (path, g), want, *copies in zip(
            flat, jax.tree.leaves(summed),
            *(jax.tree.leaves(c) for c in by_copy)):
        name = jax.tree_util.keystr(path)
        scale = float(np.abs(want).max())
        assert scale > 0, name
        assert float(np.abs(g - want).max()) <= 3e-3 * scale, name
        live = [bool(np.any(c)) for c in copies]
        if "tok_embeddings" in name:
            assert live == [True, False, False, False], name
        elif "early_exit_gate" in name:
            assert live == [True, True, True, False], name
        else:
            # Every pass adds its part, and no one pass is the sum.
            assert all(live), name
            assert max(float(np.abs(c).max()) for c in copies) < \
                float(sum(np.abs(c).max() for c in copies)), name


def test_the_references_sweep_a_pass_at_a_time_is_its_own_gradient(
        seeded, reference):
    """``loss_sum_and_grad``, which the benchmark's check runs on the chip
    (four small programs, a pass's part of a shared gradient added before
    the pass before it starts), against ``jax.value_and_grad`` of
    ``sequence_loss_sum``, the one program it stands for."""
    ids, params = seeded
    rcfg = _reference_config(_config())
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.sequence_loss_sum(
            p, ids[0], rnd=lambda a: a, config=rcfg)))(params)
    got, grads = reference.loss_sum_and_grad(lambda a: a, rcfg)(
        params, ids[0])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=1e-5 * float(np.abs(w).max()))


def test_with_one_pass_the_loss_is_the_plain_mean_and_the_gate_is_idle(
        seeded):
    ids, params = seeded
    model = OuroLM(_config(total_ut_steps=1))
    plain, grads = jax.jit(jax.value_and_grad(_loss(model, ids)))(params)

    def next_token_mean(p):
        logits, _ = model.apply({"params": p}, ids)
        return causal_lm_loss(logits, ids)

    want, want_grads = jax.jit(jax.value_and_grad(next_token_mean))(params)
    np.testing.assert_allclose(plain, want, rtol=1e-6)
    gate = grads.pop("early_exit_gate")
    want_grads.pop("early_exit_gate")
    assert not np.any(gate["kernel"]) and not np.any(gate["bias"])
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2e-5 * float(np.abs(w).max()))


def test_the_exit_distribution_by_hand(reference):
    half = jax.jit(exit_distribution)(jnp.zeros((4, 3, 5)))
    np.testing.assert_allclose(
        half, np.broadcast_to(np.array([0.5, 0.25, 0.125, 0.125])[
            :, None, None], (4, 3, 5)), rtol=1e-6)
    logits = 4.0 * jax.random.normal(jax.random.PRNGKey(5), (4, 2, 9))
    p = np.asarray(jax.jit(exit_distribution)(logits), np.float64)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=0, atol=1e-6)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-5)
    # The last exit takes what is left: its own logit is not read.
    np.testing.assert_allclose(p[3], np.prod(1 - lam[:3], axis=0),
                               rtol=1e-5)
    other = logits.at[3].set(-logits[3])
    np.testing.assert_array_equal(jax.jit(exit_distribution)(other), p.astype(
        np.float32))
    # One pass: one exit, weight 1. A gate that is sure stays finite.
    np.testing.assert_array_equal(exit_distribution(logits[:1]), 1.0)
    sure = jax.jit(lambda g: ouro.exit_entropy(
        ouro.exit_log_distribution(g)))(jnp.full((4, 2), 200.0))
    np.testing.assert_allclose(sure, 0.0, atol=1e-6)
    # The reference's running products are the same distribution.
    np.testing.assert_allclose(
        jax.jit(reference.exit_distribution)(logits[:, 0]), p[:, 0],
        rtol=1e-5, atol=1e-7)


def test_the_benchmarks_step_is_the_plain_model(seeded):
    """Flash kernels (sequence 512: the interpreter), every application
    of a block recomputed, the four exits in one sweep of four chunks:
    the same function as the plain model with the exits' full logits."""
    cfg = _config()
    ids, params = seeded_ids_and_params(OuroLM(cfg), 512, _scaled)
    ids = ids[:1]
    fast = OuroLM(dataclasses.replace(cfg, remat=True),
                  attention_fn=make_attention_fn(causal=True))
    plain = OuroLM(cfg)

    def full_logits_loss(p):
        states, gates = plain.apply({"params": p}, ids, return_hidden=True)
        log_p = ouro.exit_log_distribution(gates)
        nll = jnp.stack([
            -jnp.take_along_axis(
                jax.nn.log_softmax(h @ p["lm_head"]["kernel"]),
                ids[:, 1:, None], axis=-1)[..., 0][:, :] for h in
            states[:, :, :-1]])
        per_position = (jnp.exp(log_p)[:, :, :-1] * nll).sum(0) \
            - cfg.exit_entropy_beta * ouro.exit_entropy(log_p)[:, :-1]
        return per_position.mean()

    assert_same_loss_and_gradients(full_logits_loss, _loss(fast, ids),
                                   params, 5e-3)


# ---- models broken underneath are other functions ------------------------

def _no_norm_after_a_sublayer(monkeypatch):
    real = ouro.RMSNorm.__call__
    monkeypatch.setattr(ouro.RMSNorm, "__call__", lambda self, x: (
        x if self.name.endswith("layernorm_2") else real(self, x)))
    return OuroLM(_config())


def _final_norm_not_fed_on(monkeypatch):
    def looped(cfg, block, layers, x, passes, *args):
        blocks = [block(cfg, name=f"layer_{i}", **built_with)
                  for i, built_with in enumerate(layers)]
        final_norm = ouro.RMSNorm(cfg.norm_eps, cfg.dtype, name="final_norm")
        states = []
        for _ in range(passes):
            for layer in blocks:
                x, _ = layer(x, *args)
            states.append(final_norm(x))    # and x goes on un-normed
        return states

    monkeypatch.setattr(ouro, "looped_decoder_layers", looped)
    return OuroLM(_config())


def _uniform_weights(monkeypatch):
    monkeypatch.setattr(ouro, "exit_log_distribution", lambda g: jnp.full(
        g.shape, -np.log(g.shape[0]), jnp.float32))
    return OuroLM(_config())


def _entropy_sign_turned(monkeypatch):
    real = ouro.exit_entropy
    monkeypatch.setattr(ouro, "exit_entropy", lambda log_p: -real(log_p))
    return OuroLM(_config())


def _three_passes(monkeypatch):
    return OuroLM(_config(total_ut_steps=3))


@pytest.fixture(scope="module")
def reference_loss(seeded, reference):
    """The reference's loss on the seeded problem, which the sound model
    reads to 1e-5."""
    ids, params = seeded
    want = float(jax.jit(_reference_loss(
        reference, _reference_config(_config()), ids))(params))
    sound = float(jax.jit(_loss(OuroLM(_config()), ids))(params))
    assert abs(sound - want) <= 1e-5 * abs(want)
    return want


@pytest.mark.parametrize("broken", [
    _no_norm_after_a_sublayer, _final_norm_not_fed_on, _uniform_weights,
    _entropy_sign_turned, _three_passes], ids=lambda f: f.__name__[1:])
def test_a_broken_model_is_another_function(broken, seeded, reference_loss,
                                            monkeypatch):
    """By the loss alone, thirty times further from the reference than
    the sound model may be; at the benchmark's limits each reads not
    correct (``tests/benchmark/test_control_ouro.py``)."""
    ids, params = seeded
    got = float(jax.jit(_loss(broken(monkeypatch), ids))(params))
    assert abs(got - reference_loss) > 3e-4 * abs(reference_loss), got
