"""The benchmark's plain reference for ``joyai-llm-flash``
(``benchmarks/reference/joyai-llm-flash.py``) against cases written out
by hand at sizes a page holds: the mixer token by token and head by head
in numpy with ONE rotary key a token for all heads; the pair rotation
against complex multiplication; the bias in the choice only, the 1e-20
and the 2.5 on counted numbers; the module's input and its targets two
ahead. The model is held against this reference in ``test_joyai.py``."""

import jax
import jax.numpy as jnp
import numpy as np

from joyai_helpers import _config, _reference_config, reference  # noqa: F401

SAME = lambda a: a  # noqa: E731 - the reference's rounding at f32


def _rms(x, eps=1e-6):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotate_by_hand(x, pos, theta):
    """The pairs ``(x[2i], x[2i + 1])`` as complex numbers times
    ``exp(1j pos theta^(-2i/W))``."""
    width = x.shape[-1]
    z = (x[0::2] + 1j * x[1::2]) * np.exp(
        1j * pos * theta ** (-2.0 * np.arange(width // 2) / width))
    out = np.empty(width)
    out[0::2], out[1::2] = z.real, z.imag
    return out


def test_the_mixer_token_by_token_with_one_rotary_key_for_all_heads(
        reference):
    seq, dim, heads, nope, rope, vo, q_rank, kv_rank = 6, 8, 2, 4, 4, 3, 5, 6
    theta = 100.0
    rng = np.random.RandomState(0)
    draw = lambda *shape: rng.randn(*shape).astype(np.float32)  # noqa: E731
    a = {"wq_a": draw(dim, q_rank), "wq_b": draw(q_rank, heads, nope + rope),
         "wkv_a": draw(dim, kv_rank + rope),
         "wkv_b": draw(kv_rank, heads, nope + vo),
         "wo": draw(heads, vo, dim),
         "q_a_norm": 1 + 0.1 * draw(q_rank),
         "kv_a_norm": 1 + 0.1 * draw(kv_rank)}
    z = draw(seq, dim)
    config = {"rms_norm_eps": 1e-6, "qk_nope_head_dim": nope,
              "kv_lora_rank": kv_rank, "rope_theta": theta}
    got = reference.latent_attention(
        SAME, {k: {"scale" if k.endswith("norm") else "kernel":
                   jnp.asarray(a[k])} for k in sorted(a)},
        jnp.asarray(z), config)

    want = np.zeros((seq, dim))
    c_q = _rms(z @ a["wq_a"]) * a["q_a_norm"]
    kv_a = z @ a["wkv_a"]
    c_kv = _rms(kv_a[:, :kv_rank]) * a["kv_a_norm"]
    # ONE rotary key a token, whatever the head.
    k_r = np.stack([_rotate_by_hand(kv_a[t, kv_rank:], t, theta)
                    for t in range(seq)])
    for h in range(heads):
        q = c_q @ a["wq_b"][:, h]
        kv = c_kv @ a["wkv_b"][:, h]
        k_n, v = kv[:, :nope], kv[:, nope:]
        for t in range(seq):
            q_t = np.concatenate(
                [q[t, :nope], _rotate_by_hand(q[t, nope:], t, theta)])
            scores = np.array([
                q_t @ np.concatenate([k_n[j], k_r[j]])
                for j in range(t + 1)]) / np.sqrt(nope + rope)
            probs = np.exp(scores - scores.max())
            probs /= probs.sum()
            want[t] += (probs @ v[:t + 1]) @ a["wo"][h]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_bias_enters_the_choice_only_with_1e_20_and_2_5(reference):
    config = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.5}
    scores = jnp.array([[0.9, 0.5, 0.4, 0.1],
                        [0.2, 0.3, 0.25, 0.1]], jnp.float32)
    bias = jnp.array([0.0, 0.0, 0.2, 0.0], jnp.float32)
    # Row 0: s + b = [0.9, 0.5, 0.6, 0.1] chooses {0, 2}, not {0, 1}; the
    # weights are 2.5 x the chosen experts' OWN scores over their sum.
    # Row 1: s + b = [0.2, 0.3, 0.45, 0.1] chooses {1, 2} with or without.
    got = reference.routing_weights(scores, bias, config)
    np.testing.assert_allclose(got, 2.5 * np.array(
        [[0.9 / 1.3, 0, 0.4 / 1.3, 0], [0, 0.3 / 0.55, 0.25 / 0.55, 0]]),
        rtol=1e-6)
    plain = reference.routing_weights(scores, 0.0 * bias, config)
    np.testing.assert_allclose(plain[0], 2.5 * np.array(
        [0.9 / 1.4, 0.5 / 1.4, 0, 0]), rtol=1e-6)
    np.testing.assert_allclose(plain[1], got[1], rtol=1e-6)
    # 1e-20, not 1e-6: scores of 1e-9 still weigh 2.5 together.
    tiny = reference.routing_weights(1e-9 * scores, 1e-9 * bias, config)
    np.testing.assert_allclose(tiny.sum(-1), 2.5, rtol=1e-5)
    assert reference.NORM_EPS_OF_WEIGHTS == 1e-20
    # The bias takes no gradient; the scores do.
    d_scores, d_bias = jax.grad(lambda s, b: jnp.sum(
        reference.routing_weights(s, b, config) ** 2), (0, 1))(scores, bias)
    assert not np.any(np.asarray(d_bias)) and np.any(np.asarray(d_scores))


def test_the_pair_rotation_is_complex_multiplication(reference):
    x = np.random.RandomState(1).randn(5, 3, 8).astype(np.float32)
    got = reference.rotate_pairs(jnp.asarray(x), 3.2e7)
    for t in range(5):
        for h in range(3):
            np.testing.assert_allclose(
                got[t, h], _rotate_by_hand(x[t, h], t, 3.2e7), atol=1e-5)
    # With no head axis: the rotary key's.
    np.testing.assert_allclose(
        reference.rotate_pairs(jnp.asarray(x[:, 0]), 3.2e7), got[:, 0],
        atol=1e-6)


def test_the_module_reads_the_next_token_and_scores_the_one_after(
        reference):
    cfg = _config()
    from horovod_tpu.models import JoyAILM
    from model_helpers import jit_init

    seq = 12
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (seq,), 0,
                                        cfg.vocab_size))
    params = jit_init(JoyAILM(cfg), jnp.asarray(ids)[None],
                      rngs=jax.random.PRNGKey(4))["params"]
    rcfg = _reference_config(cfg)
    hidden = jax.jit(lambda ids: reference.sequence_hidden(
        params, ids, SAME, rcfg))
    g, m = hidden(jnp.asarray(ids))
    other = ids.copy()
    other[7] = (other[7] + 1) % cfg.vocab_size
    g2, m2 = hidden(jnp.asarray(other))
    moved = lambda a, b: np.flatnonzero(  # noqa: E731
        np.any(np.asarray(a) != np.asarray(b), axis=-1)).tolist()
    # Token 7 reaches the main model from position 7 on, and the module,
    # which reads t_{i+1} at position i, from position 6 on.
    assert moved(g, g2) == list(range(7, seq))
    assert moved(m, m2) == list(range(6, seq))
    # The targets: t_{i+1} under the main head over S - 1 positions,
    # t_{i+2} under the SAME head over S - 2.
    head = params["lm_head"]["kernel"]

    def by_hand(x, ahead):
        logp = jax.nn.log_softmax(np.asarray(x) @ np.asarray(head), axis=-1)
        return -sum(float(logp[i, ids[i + ahead]])
                    for i in range(seq - ahead))

    def sums(rcfg):
        return jax.jit(lambda ids: reference.sequence_nll_sums(
            params, ids, SAME, rcfg))(jnp.asarray(ids))

    main, mtp = sums(rcfg)
    np.testing.assert_allclose(main, by_hand(g, 1), rtol=1e-5)
    np.testing.assert_allclose(mtp, by_hand(m, 2), rtol=1e-5)
    # One ahead would be another number.
    assert abs(by_hand(m, 1) * (seq - 2) / (seq - 1) - float(mtp)) > 1e-3
    # A configuration without the module has no second loss.
    none = sums({**rcfg, "num_nextn_predict_layers": 0})
    np.testing.assert_allclose(none[0], main, rtol=1e-6)
    assert float(none[1]) == 0.0
