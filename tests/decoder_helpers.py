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
import pytest

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


def share(params, held):
    """``params`` of the model that holds every routed expert, cut to
    ``held``: the leading axis of every ``w_gate`` / ``w_up`` / ``w_down``
    that has one (a dense MLP's and a shared expert's are matrices);
    what every chip holds alike is left whole."""
    held = jnp.array(held, jnp.int32)

    def cut(path, x):
        names = {getattr(k, "key", None) for k in path}
        routed = x.ndim == 3 and names & {"w_gate", "w_up", "w_down"}
        return x[held] if routed else x

    return jax.tree_util.tree_map_with_path(cut, params)
