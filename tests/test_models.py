"""Model zoo smoke tests on tiny shapes (CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    BERT_TINY,
    BertEncoder,
    InceptionV3,
    MnistMLP,
    ResNetTiny,
    VGGTiny,
    mlm_loss,
)
from model_helpers import jit_apply, jit_init


def test_resnet_tiny_forward_and_grad():
    model = ResNetTiny(dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    variables = jit_init(model, x, train=True)
    logits, state = jax.jit(lambda v, x: model.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables, x)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()

    def loss(p):
        out, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return (out ** 2).mean()

    g = jax.jit(jax.grad(loss))(variables["params"])
    leaves = jax.tree_util.tree_leaves(g)
    assert any(float(jnp.abs(l).max()) > 0 for l in leaves)


def test_bert_tiny_forward_loss():
    cfg = BERT_TINY
    model = BertEncoder(cfg)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 12)))
    variables = jit_init(model, ids, deterministic=True)
    logits = jit_apply(model, deterministic=True)(variables, ids)
    assert logits.shape == (2, 12, cfg.vocab_size)
    loss = mlm_loss(logits, ids, jnp.ones((2, 12)))
    # Random init: loss ≈ ln(vocab_size)
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 2 * np.log(cfg.vocab_size)


def test_bert_attention_mask():
    cfg = BERT_TINY
    model = BertEncoder(cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    variables = jit_init(model, ids, deterministic=True)
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]])
    masked = jit_apply(model, attention_mask=mask, deterministic=True)
    out_masked = masked(variables, ids)
    # Changing a masked-out position's token must not affect unmasked outputs.
    out2 = masked(variables, ids.at[0, 6].set(5))
    np.testing.assert_allclose(np.asarray(out_masked[0, :4]),
                               np.asarray(out2[0, :4]), atol=1e-5)


def test_vgg_tiny_forward():
    model = VGGTiny(dtype=jnp.float32)
    x = jnp.ones((2, 16, 16, 3))
    variables = jit_init(model, x, train=False)
    out = jit_apply(model, train=False)(variables, x)
    assert out.shape == (2, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_inception_v3_forward():
    # 75x75 is the smallest valid input; keeps the CPU test fast while
    # exercising every block type (A/B/C/D/E + stem).
    model = InceptionV3(num_classes=7, dtype=jnp.float32)
    x = jnp.ones((1, 75, 75, 3))
    # The forward pass that draws the weights is the one looked at: one
    # program of the whole network, not two.
    out, variables = jax.jit(lambda x: model.init_with_output(
        jax.random.PRNGKey(0), x, train=False))(x)
    assert out.shape == (1, 7) and "batch_stats" in variables
    assert np.isfinite(np.asarray(out)).all()


def test_inception_v3_aux_logits():
    model = InceptionV3(num_classes=5, aux_logits=True, dtype=jnp.float32)
    x = jnp.ones((1, 75, 75, 3))
    (logits, aux), variables = jax.jit(lambda x: model.init_with_output(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, train=True))(x)
    assert logits.shape == (1, 5) and aux.shape == (1, 5)
    assert "aux_head" in variables["params"]


def test_mnist_mlp():
    model = MnistMLP()
    x = jnp.ones((4, 28, 28, 1))
    variables = jit_init(model, x)
    out = jit_apply(model)(variables, x)
    assert out.shape == (4, 10)


# 73 s under the driver's command on an idle box, 150 on a loaded one (45 s
# alone): five compiled BERT steps
# on eight devices. The dp x tp step against its single-device twin stays
# in tier-1 in test_llama.py::test_tensor_parallel_specs_match_data_parallel
# and test_fsdp.py::test_fsdp_dp_tp_hybrid_trains; the driver runs
# ``dryrun_multichip`` itself.
@pytest.mark.slow
def test_graft_entry_dryrun():
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def test_bert_sequence_parallel_positions():
    """BERT under sequence parallelism: ring attention through the seam and
    GLOBAL positions into the learned position embedding — must match the
    single-device encoder."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import BERT_TINY, BertEncoder
    from horovod_tpu.parallel import make_mesh
    from horovod_tpu.parallel.sequence import ring_attention

    n, s = 8, 64
    cfg = BERT_TINY
    ids = jnp.asarray(
        np.random.RandomState(9).randint(0, cfg.vocab_size, (2, s)),
        jnp.int32)
    ref_model = BertEncoder(cfg)
    variables = jit_init(ref_model, ids, deterministic=True)
    ref = jit_apply(ref_model, deterministic=True)(variables, ids)

    sp_model = BertEncoder(cfg, attention_fn=lambda q, k, v, m:
                           ring_attention(q, k, v, axis_name="seq",
                                          key_mask=m))
    mesh = make_mesh({"seq": n})
    s_local = s // n

    def body(params, ids_shard):
        idx = jax.lax.axis_index("seq")
        positions = idx * s_local + jnp.arange(s_local)
        return sp_model.apply(params, ids_shard, deterministic=True,
                              positions=positions)

    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    out = f(variables, ids)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_bert_remat_matches_no_remat():
    import dataclasses

    import jax
    import numpy as np

    from horovod_tpu.models import BERT_TINY, BertEncoder, mlm_loss

    # float32: under jit the two programs fuse differently, and in
    # bfloat16 that alone moves the gradients by their rounding.
    cfg = dataclasses.replace(BERT_TINY, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16)),
        jnp.int32)
    mask = jnp.asarray(np.random.RandomState(1).rand(2, 16) < 0.3)
    base = BertEncoder(cfg)
    remat = BertEncoder(dataclasses.replace(cfg, remat=True))
    variables = jit_init(base, ids, deterministic=True)

    def loss_fn(model):
        def f(params):
            logits = model.apply({"params": params}, ids, deterministic=True)
            return mlm_loss(logits, ids, mask)
        return f

    l0, g0 = jax.jit(jax.value_and_grad(loss_fn(base)))(variables["params"])
    l1, g1 = jax.jit(jax.value_and_grad(loss_fn(remat)))(variables["params"])
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1)


def test_vit_tiny_forward_loss_and_grad():
    from horovod_tpu.models import (VIT_TINY, VisionTransformer,
                                    classification_loss)

    cfg = VIT_TINY
    model = VisionTransformer(cfg)
    imgs = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    labels = jnp.asarray([1, 7])
    variables = jit_init(model, imgs, deterministic=True)

    def loss_and_logits(v):
        logits = model.apply(v, imgs, deterministic=True)
        return classification_loss(logits, labels), logits

    (loss, logits), grads = jax.jit(jax.value_and_grad(
        loss_and_logits, has_aux=True))(variables)
    assert logits.shape == (2, cfg.num_classes)
    # Random init: loss ~ ln(num_classes); params must all receive grads.
    assert 0.5 * np.log(cfg.num_classes) < float(loss) \
        < 3 * np.log(cfg.num_classes)
    leaves = jax.tree.leaves(grads)
    assert leaves and all(np.all(np.isfinite(np.asarray(g))) for g in leaves)
    assert any(float(jnp.abs(g).max()) > 0 for g in leaves)


def test_vit_remat_matches_no_remat():
    # Compare GRADIENTS, not just forwards: remat only changes the backward
    # (recomputation), so a forward-only comparison would be vacuous (the
    # BERT twin test, test_bert_remat_matches_no_remat, for the same
    # reason).
    import dataclasses

    from horovod_tpu.models import (VIT_TINY, VisionTransformer,
                                    classification_loss)

    imgs = jnp.asarray(np.random.RandomState(1).rand(1, 32, 32, 3), jnp.float32)
    labels = jnp.asarray([3])
    # float32, for test_bert_remat_matches_no_remat's reason.
    cfg = dataclasses.replace(VIT_TINY, dtype=jnp.float32)
    base = VisionTransformer(cfg)
    rematted = VisionTransformer(dataclasses.replace(cfg, remat=True))
    variables = jit_init(base, imgs, deterministic=True)

    def loss_fn(model):
        return lambda v: classification_loss(
            model.apply(v, imgs, deterministic=True), labels)

    l0, g0 = jax.jit(jax.value_and_grad(loss_fn(base)))(variables)
    l1, g1 = jax.jit(jax.value_and_grad(loss_fn(rematted)))(variables)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1)
