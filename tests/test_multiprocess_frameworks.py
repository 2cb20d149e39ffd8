"""Eager multi-process tier (``test_multiprocess.py``), the other
frameworks' bindings across real ranks on the default (native) engine:
the torch, TensorFlow and MXNet scenarios and the TensorFlow custom op.
A rank here imports its framework beside jax, which is most of its time."""

import pytest

from mp_harness import run_ring_ranks as run_ranks


@pytest.mark.parametrize("scenario", ["torch", "tensorflow", "mxnet"])
def test_two_ranks(scenario):
    if scenario == "tensorflow":
        # On a fresh checkout this is the first job to want the TF op
        # library: built inside the ranks it is minutes of g++ on a loaded
        # box while the other rank sits parked in the availability vote,
        # and the job ran into its limit (120 s then: ISSUE 39). The
        # parent builds, as test_tf_custom_op_two_ranks' does.
        from horovod_tpu.tensorflow import tf_ops

        tf_ops.build()
    run_ranks(scenario, size=2)


@pytest.mark.slow  # ~11 s edge variant; test_tf_custom_op_two_ranks
def test_tf_custom_op_mixed_availability_agrees_on_fallback():  # stays
    """One rank opts out of the custom-op path (the shape of a host whose
    op library can't build): the job-wide vote in ``_custom_ops`` must drop
    BOTH ranks to the py_function path — a mixed-path job would diverge
    anonymous collective names (trace-time vs per-execution autonaming)
    and stall negotiation."""
    from horovod_tpu.tensorflow import tf_ops

    # Pre-build in the parent: rank 0's availability probe inside the vote
    # would otherwise spend minutes compiling while rank 1 sits parked in
    # the agreement allreduce, racing the timeout on a cold cache.
    tf_ops.build()
    run_ranks("tensorflow", size=2,
              per_rank_env={1: {"HOROVOD_TENSORFLOW_CUSTOM_OP": "0"}})


def test_tf_custom_op_two_ranks():
    """TF custom-op data path (tensorflow/src/tf_ops.cc) across real ranks:
    graph-node collectives, gradients, validation errors. Building the op
    library against the TF headers takes minutes on one core, so the parent
    builds (or reuses the cached .so) before the ranks spawn."""
    from horovod_tpu.tensorflow import tf_ops

    tf_ops.build()
    run_ranks("tf_custom_op", size=2)
