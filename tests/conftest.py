"""Test harness: hermetic 8-virtual-device CPU mesh.

The reference tests "distributed" behavior with 2 MPI ranks on one container
(SURVEY.md §4). Our equivalent: a single process with 8 XLA host devices
(``--xla_force_host_platform_device_count=8``) exercising the SPMD tier, plus
subprocess-spawned multi-rank tests for the eager controller tier.

The platform and the device count are forced in-process, before
``import jax``, so the suite is hermetic whatever the caller exported —
on a machine with a chip too.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_state():
    """Each test gets a fresh hvd lifecycle and mesh registry."""
    yield
    import horovod_tpu as hvd
    from horovod_tpu.parallel import reset_mesh

    hvd.shutdown()
    reset_mesh()


@pytest.fixture(scope="module")
def reference_results():
    """What the XLA reference gave for a kernel case of this file, keyed
    by the case and not by the kernel path that asked
    (``attention_helpers.assert_matches_reference``). It outlives
    ``_fresh_state``'s shutdown: it holds arrays only, nothing of ``hvd``
    or the mesh registry."""
    return {}
