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
import sys

# This installation keeps no bytecode (``PYTHONDONTWRITEBYTECODE`` is set
# and site-packages holds no ``.pyc``), so every process compiled jax's 596
# files anew: ``import horovod_tpu`` 4.1 s for 1.0, TensorFlow 22 for 7.
# The suite keeps ONE cache under the checkout, for its workers and,
# through the environment, for every process it starts.
sys.dont_write_bytecode = False
sys.pycache_prefix = os.environ["PYTHONPYCACHEPREFIX"] = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".pycache")
os.environ.pop("PYTHONDONTWRITEBYTECODE", None)

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


# Rule 1 of the tier-1 clock (ROADMAP.md D9): ONE COMPILED PROGRAM A CASE.
# A model, a reference or a flax ``init`` called eagerly is one XLA program
# an operation. A test outside ``tests/benchmark/`` that compiles more than
# this many, set-up included, fails: put the call under ``jax.jit``
# (``model_helpers``, ``attention_helpers.out_and_grads``). A module whose
# subject IS eager dispatch says ``EAGER_BY_DESIGN = "<why>"`` at its top
# level; ``tests/test_lint.py`` lists those modules.
MAX_XLA_PROGRAMS_A_TEST = 135

_programs = _programs_at_last_test = 0


def _count_program(event, duration, **kw):
    global _programs
    if event == "/jax/core/compile/backend_compile_duration":
        _programs += 1


jax.monitoring.register_event_duration_secs_listener(_count_program)


@pytest.fixture(autouse=True)
def _fresh_state(request):
    """Each test gets a fresh hvd lifecycle and mesh registry, and at most
    ``MAX_XLA_PROGRAMS_A_TEST`` compiles since the test before it ended
    (a module's fixtures count with the first test that asks)."""
    global _programs_at_last_test
    yield
    import horovod_tpu as hvd
    from horovod_tpu.parallel import reset_mesh

    hvd.shutdown()
    reset_mesh()
    compiled = _programs - _programs_at_last_test
    _programs_at_last_test = _programs
    request.node.user_properties.append(("xla_programs", compiled))
    capped = (request.node.path.parent.name != "benchmark"
              and not hasattr(request.module, "EAGER_BY_DESIGN"))
    if capped and compiled > MAX_XLA_PROGRAMS_A_TEST:
        pytest.fail(
            f"{compiled} XLA programs compiled by one test, more than "
            f"MAX_XLA_PROGRAMS_A_TEST = {MAX_XLA_PROGRAMS_A_TEST} "
            f"(tests/conftest.py, rule 1: one compiled program a case; "
            f"an eager model call compiles one program an operation)",
            pytrace=False)


@pytest.fixture(scope="module")
def reference_results():
    """What the XLA reference gave for a kernel case of this file, keyed
    by the case and not by the kernel path that asked
    (``attention_helpers.assert_matches_reference``). It outlives
    ``_fresh_state``'s shutdown: it holds arrays only, nothing of ``hvd``
    or the mesh registry."""
    return {}
