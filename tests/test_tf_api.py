"""TF/Keras adapter, single-process semantics (reference test_tensorflow.py /
test_keras.py size-independent parts). Cross-rank behavior: "tensorflow"
scenario in tests/test_multiprocess_frameworks.py."""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

import horovod_tpu.keras as hvd_keras  # noqa: E402
import horovod_tpu.tensorflow as hvd  # noqa: E402


def test_ops_size1():
    hvd.init()
    x = tf.constant([1.0, 2.0, 3.0])
    np.testing.assert_array_equal(hvd.allreduce(x).numpy(), x.numpy())
    np.testing.assert_array_equal(hvd.allgather(x).numpy(), x.numpy())
    np.testing.assert_array_equal(
        hvd.broadcast(x, root_rank=0).numpy(), x.numpy())


def test_indexed_slices_size1():
    hvd.init()
    slices = tf.IndexedSlices(
        values=tf.constant([[1.0, 2.0]]), indices=tf.constant([3]),
        dense_shape=tf.constant([5, 2]))
    out = hvd.allreduce(slices, average=True)
    assert isinstance(out, tf.IndexedSlices)
    np.testing.assert_array_equal(out.values.numpy(), [[1.0, 2.0]])
    np.testing.assert_array_equal(out.indices.numpy(), [3])


def test_distributed_gradient_tape_size1():
    hvd.init()
    w = tf.Variable([2.0])
    with hvd.DistributedGradientTape() as tape:
        loss = w * w
    (grad,) = tape.gradient(loss, [w])
    np.testing.assert_allclose(grad.numpy(), [4.0])


def test_distributed_optimizer_apply():
    hvd.init()
    opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.5))
    v = tf.Variable(1.0)
    opt.apply_gradients([(tf.constant(1.0), v)])
    np.testing.assert_allclose(v.numpy(), 0.5)


def test_broadcast_variables_size1():
    hvd.init()
    v = tf.Variable([1.0, 2.0])
    hvd.broadcast_variables([v], root_rank=0)
    with pytest.raises(ValueError, match="root_rank"):
        hvd.broadcast_variables([v], root_rank=2)


def test_keras_alias_surface():
    import horovod_tpu.tensorflow.keras as hvd_tfk

    assert hvd_tfk.DistributedOptimizer is hvd_keras.DistributedOptimizer
    assert hasattr(hvd_keras.callbacks, "BroadcastGlobalVariablesCallback")
    assert hasattr(hvd_keras.callbacks, "MetricAverageCallback")
    assert hasattr(hvd_keras.callbacks, "LearningRateWarmupCallback")
    assert hasattr(hvd_keras.callbacks, "LearningRateScheduleCallback")


def test_lr_schedule_callback_size1():
    hvd.init()
    model = tf.keras.Sequential(
        [tf.keras.layers.Dense(1, input_shape=(2,))])
    model.compile(optimizer=tf.keras.optimizers.SGD(0.1), loss="mse")
    cb = hvd_keras.callbacks.LearningRateScheduleCallback(
        multiplier=lambda epoch: 0.5 ** epoch)
    cb.set_model(model)
    cb.on_epoch_begin(0)
    np.testing.assert_allclose(
        float(model.optimizer.learning_rate.numpy()), 0.1, rtol=1e-6)
    cb.on_epoch_begin(2)
    np.testing.assert_allclose(
        float(model.optimizer.learning_rate.numpy()), 0.025, rtol=1e-6)


def test_compression_tf():
    x = tf.constant([1.0, 2.0])
    c, ctx = hvd.Compression.fp16.compress(x)
    assert c.dtype == tf.float16
    assert hvd.Compression.fp16.decompress(c, ctx).dtype == tf.float32


def test_broadcast_global_variables_eager_raises():
    hvd.init()
    with pytest.raises(NotImplementedError, match="broadcast_variables"):
        hvd.broadcast_global_variables(0)


def test_tf1_broadcast_global_variables_hook():
    """TF1-compat shim (reference tensorflow/__init__.py:90-143): inside a
    v1 graph + session, the hook broadcasts the global-variables collection
    at session creation. At size 1 broadcast is identity, so the check is
    that the op builds, runs, and leaves values intact."""
    hvd.init()
    graph = tf.Graph()
    with graph.as_default():
        v = tf.compat.v1.get_variable(
            "hook_var", initializer=tf.constant([1.5, -2.0]))
        hook = hvd.BroadcastGlobalVariablesHook(root_rank=0)
        hook.begin()
        assert hook.bcast_op is not None
        assert hook.bcast_op.graph is graph
        init_op = tf.compat.v1.global_variables_initializer()
        with tf.compat.v1.Session(graph=graph) as sess:
            sess.run(init_op)
            hook.after_create_session(sess, None)
            np.testing.assert_allclose(sess.run(v), [1.5, -2.0])


def test_tf1_broadcast_global_variables_op_rebuilt_per_graph():
    hvd.init()
    hook = hvd.BroadcastGlobalVariablesHook(root_rank=0)
    with tf.Graph().as_default():
        tf.compat.v1.get_variable("g1_var", initializer=tf.constant(1.0))
        hook.begin()
        op1 = hook.bcast_op
    with tf.Graph().as_default():
        tf.compat.v1.get_variable("g2_var", initializer=tf.constant(2.0))
        hook.begin()
        assert hook.bcast_op is not op1


def test_keras_load_model_wraps_optimizer(tmp_path):
    # Reference keras/__init__.py load_model (via _keras/__init__.py:93-109):
    # a model saved with a PLAIN optimizer deserializes with the optimizer
    # wrapped in DistributedOptimizer, state intact.
    hvd.init()
    model = tf.keras.Sequential([tf.keras.layers.Dense(1, input_shape=(2,))])
    model.compile(optimizer=tf.keras.optimizers.Adam(0.01), loss="mse")
    x = np.random.rand(8, 2).astype(np.float32)
    y = np.random.rand(8, 1).astype(np.float32)
    model.fit(x, y, epochs=1, verbose=0)
    path = str(tmp_path / "plain.keras")
    model.save(path)

    loaded = hvd_keras.load_model(path)
    opt = loaded.optimizer
    assert type(opt).__name__ == "DistributedAdam"
    assert float(opt.learning_rate.numpy()) == pytest.approx(0.01)
    # Optimizer slot state came back and training continues through the
    # wrapped apply_gradients.
    assert int(opt.iterations.numpy()) > 0
    loaded.fit(x, y, epochs=1, verbose=0)


def test_keras_load_model_roundtrip_distributed(tmp_path):
    # A model saved while ALREADY compiled with the wrapped optimizer
    # ("DistributedSGD" in its config) loads too.
    hvd.init()
    model = tf.keras.Sequential([tf.keras.layers.Dense(1, input_shape=(2,))])
    model.compile(optimizer=hvd.DistributedOptimizer(
        tf.keras.optimizers.SGD(0.5)), loss="mse")
    x = np.ones((4, 2), np.float32)
    y = np.ones((4, 1), np.float32)
    model.fit(x, y, epochs=1, verbose=0)
    path = str(tmp_path / "dist.keras")
    model.save(path)

    import horovod_tpu.tensorflow.keras as hvd_tfk

    loaded = hvd_tfk.load_model(path)
    assert type(loaded.optimizer).__name__ == "DistributedSGD"
    loaded.fit(x, y, epochs=1, verbose=0)
