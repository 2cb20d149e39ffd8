"""What the attention test files share: the small shapes, seeded inputs,
the two kernel paths as a parameter, and the gradient comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

B, S, H, D = 2, 64, 2, 16


def _qkv(seed=0, s=S):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, s, H, D).astype(np.float32)) * 0.3
    return mk(), mk(), mk()


# --------------------------------------------------------------------------
# The two paths through the flash kernels (PR 25). ``one_tile``: after
# _fit_block the query and key axes are one block each, so the forward is a
# plain softmax of the tile and each backward kernel one pass over it. ``streamed``:
# explicit small blocks force the online-softmax kernels, which is how a
# test of this size reaches them (the defaults are one tile here).
PATHS = {"one_tile": {}, "streamed": {"block_q": 16, "block_k": 16}}
both_paths = pytest.mark.parametrize("path", sorted(PATHS))


def _rand(shape, seed, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3, dtype)


def _sq_loss(fn):
    return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()


def _assert_grads_close(fn, ref, q, k, v, tol):
    gf = jax.grad(_sq_loss(fn), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(_sq_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() / (np.abs(b).max() + 1e-6) < tol


def kernel_grids(fn, *args):
    """``{pallas_call name: its grid}`` of ``fn`` traced on ``args``
    (arrays or ``jax.ShapeDtypeStruct``s); a name met twice fails."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                assert name not in found, name
                found[name] = tuple(eqn.params["grid_mapping"].grid)
            for inner in jax.core.jaxprs_in_params(eqn.params):
                walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# Both paths call their kernels by these names (the benchmark's per-kernel
# metrics read them).
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
