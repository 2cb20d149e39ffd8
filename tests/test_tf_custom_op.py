"""TF custom-op binding: build, load, graph capture, SavedModel, gradients.

Single-process tier of the reference's ``test/test_tensorflow.py`` custom-op
coverage: the ops here are real graph nodes (AsyncOpKernels enqueueing into
the native engine, ``horovod_tpu/tensorflow/src/tf_ops.cc``), so unlike the
``tf.py_function`` fallback they must survive graph serialization. Engine
runs at size 1 (ring skipped); cross-rank semantics live in
``tests/test_multiprocess_frameworks.py::test_tf_custom_op_two_ranks``.
"""

import ctypes
import os

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

from horovod_tpu.core import bindings  # noqa: E402
from horovod_tpu.tensorflow import tf_ops  # noqa: E402


@pytest.fixture(scope="module")
def engine():
    lib = bindings.load()
    assert lib is not None, "native core toolchain must exist in CI"
    secret = b"\x01" * 32
    key = (ctypes.c_uint8 * len(secret)).from_buffer_copy(secret)
    rc = lib.hvd_eng_init(0, 1, b"", key, len(secret), 1.0, 1 << 20, 64,
                          1, 60.0, -1.0, b"", 0, 0, 0, 0, 1)
    assert rc == 0, lib.hvd_eng_last_error().decode()
    yield lib
    lib.hvd_eng_shutdown()


def test_library_builds_and_loads():
    # This box ships g++ and the TF headers: the fast path must be REAL
    # here, not silently degraded (tf_ops.load logs-and-falls-back in the
    # field; CI asserts the build).
    assert tf_ops.available(), tf_ops._load_failed


def test_eager_allreduce_size1(engine):
    x = tf.constant([1.0, 2.5, -3.0], dtype=tf.float32)
    out = tf_ops.allreduce_sum(x, name="tfop.smoke.ar")
    np.testing.assert_allclose(out.numpy(), [1.0, 2.5, -3.0])


@pytest.mark.parametrize("dtype", [tf.float64, tf.int32, tf.int64,
                                   tf.bfloat16, tf.float16, tf.uint8])
def test_eager_dtypes_size1(engine, dtype):
    x = tf.cast(tf.constant([[1, 2], [3, 4]]), dtype)
    out = tf_ops.allreduce_sum(x, name=f"tfop.smoke.{dtype.name}")
    np.testing.assert_array_equal(
        tf.cast(out, tf.float64).numpy(), [[1, 2], [3, 4]])


def test_eager_allgather_broadcast_size1(engine):
    x = tf.constant([[1, 2, 3]], dtype=tf.int32)
    out = tf_ops.allgather(x, name="tfop.smoke.ag")
    np.testing.assert_array_equal(out.numpy(), [[1, 2, 3]])
    b = tf_ops.broadcast(tf.constant([7.0]), root_rank=0,
                         name="tfop.smoke.bc")
    np.testing.assert_array_equal(b.numpy(), [7.0])


def test_traced_graph_contains_custom_op(engine):
    # The point of the custom op vs py_function: a real node in the graph.
    @tf.function
    def step(t):
        return tf_ops.allreduce_sum(t, name="tfop.traced.ar")

    cf = step.get_concrete_function(
        tf.TensorSpec([4], tf.float32))
    op_types = {op.type for op in cf.graph.get_operations()}
    assert "HorovodTpuAllreduce" in op_types
    assert "EagerPyFunc" not in op_types
    out = step(tf.constant([1.0, 2.0, 3.0, 4.0]))
    np.testing.assert_allclose(out.numpy(), [1.0, 2.0, 3.0, 4.0])


def test_savedmodel_roundtrip(engine, tmp_path):
    # py_function graphs refuse to serialize; the custom op must round-trip
    # through SavedModel (the boundary called out in docs/migration.md).
    class M(tf.Module):
        @tf.function(input_signature=[tf.TensorSpec([3], tf.float32)])
        def __call__(self, t):
            return tf_ops.allreduce_sum(t, name="tfop.saved.ar")

    path = os.path.join(tmp_path, "m")
    tf.saved_model.save(M(), path)
    loaded = tf.saved_model.load(path)
    out = loaded(tf.constant([5.0, 6.0, 7.0]))
    np.testing.assert_allclose(out.numpy(), [5.0, 6.0, 7.0])


def test_gradient_through_custom_op(engine):
    # Registered gradient (reference tensorflow/mpi_ops.py:82-93): backward
    # of sum-allreduce is sum-allreduce; at size 1 that's identity.
    x = tf.Variable([2.0, 3.0])
    with tf.GradientTape() as tape:
        y = tf_ops.allreduce_sum(x, name="tfop.grad.ar")
        loss = tf.reduce_sum(y * y)
    grad = tape.gradient(loss, x)
    np.testing.assert_allclose(grad.numpy(), [4.0, 6.0])


def test_allgather_gradient_needs_ranks(engine):
    # The allgather/broadcast grads call hvd.size()/rank(), which require
    # hvd.init(); covered cross-rank in the multiprocess scenario. Here just
    # pin that the op itself differentiates at the allreduce level.
    @tf.function
    def f(t):
        return tf.reduce_sum(tf_ops.allreduce_sum(t, name="tfop.grad2.ar"))

    x = tf.constant([1.0])
    with tf.GradientTape() as tape:
        tape.watch(x)
        y = f(x)
    assert tape.gradient(y, x).numpy() == pytest.approx(1.0)


def test_enqueue_after_shutdown_raises_cleanly(engine):
    # Shuts the shared engine down, asserts the op fails with the engine's
    # shutdown contract (FailedPrecondition, not a stale error string),
    # then re-inits so later tests don't depend on execution order
    # (re-init after finish() is legal, engine.cc hvd_eng_init).
    engine.hvd_eng_shutdown()
    try:
        with pytest.raises(tf.errors.FailedPreconditionError,
                           match="shut down"):
            tf_ops.allreduce_sum(tf.constant([1.0]),
                                 name="tfop.after.shutdown")
    finally:
        secret = b"\x01" * 32
        key = (ctypes.c_uint8 * len(secret)).from_buffer_copy(secret)
        rc = engine.hvd_eng_init(0, 1, b"", key, len(secret), 1.0, 1 << 20,
                                 64, 1, 60.0, -1.0, b"", 0, 0, 0, 0, 1)
        assert rc == 0, engine.hvd_eng_last_error().decode()
