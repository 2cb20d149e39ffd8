"""Eager multi-process tier, the planes the native engine does not run:
the pure-Python star data plane (``HOROVOD_CPU_OPS=star``) and the Python
controller over the ring (``HOROVOD_ENGINE=python``). The harness and
the default engine's own run of these scenarios: ``test_multiprocess.py``."""

import pytest

from mp_harness import run_ring_ranks as run_ranks


@pytest.mark.parametrize("scenario", ["allreduce", "allgather", "broadcast"])
def test_star_data_plane(scenario):
    # Pure-Python fallback path (HOROVOD_CPU_OPS=star) stays correct.
    run_ranks(scenario, size=2, extra_env={"HOROVOD_CPU_OPS": "star"})


@pytest.mark.parametrize("scenario", [
    "allreduce", "fusion", "cache", "error_mismatch", "duplicate_name",
    "inplace", "objects", "reducescatter_alltoall",
    # grouped behind @slow on this engine (~15 s: torch+tf imports in one
    # worker); python-engine fusion grouping stays covered by [fusion]
    # and the native run of the full grouped scenario stays in tier-1.
    pytest.param("grouped", marks=pytest.mark.slow),
    # TF on the Python controller = the tf.py_function fallback path (the
    # native-engine run of this scenario rides the custom op instead).
    "tensorflow",
    # torch/mxnet re-run here so the Handle.tensor_sizes plumbing (one
    # collective per autograd allgather; metric gather split) is covered on
    # BOTH data planes, not just the native engine's slot accessors.
    "torch", "mxnet",
])
def test_python_engine(scenario):
    # The Python controller (TCP star control plane) remains selectable via
    # HOROVOD_ENGINE=python; the default above exercises the native C++
    # engine (engine.cc) whenever ring addresses are exported.
    if scenario == "tensorflow":    # test_two_ranks[tensorflow] has the reason
        from horovod_tpu.tensorflow import tf_ops

        tf_ops.build()
    run_ranks(scenario, size=2, extra_env={"HOROVOD_ENGINE": "python"})
