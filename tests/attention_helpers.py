"""What the attention test files share: the small shapes, seeded inputs,
the two kernel paths as a parameter, and the comparison of a kernel with
the reference, output and gradients, from one compiled program a side."""

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


def out_and_grads(fn, q, k, v, cot=None):
    """``(out, (dq, dk, dv))`` of ``fn(q, k, v)`` from ONE compiled
    program: the gradients are of ``sum(out ** 2)`` taken in float32 or,
    with ``cot``, of ``sum(out * cot)``, which is ``fn``'s vjp at ``cot``.
    Called eagerly, a flash call in the interpreter dispatches every
    operation of every kernel as a program of its own and a test that
    wants the output beside the gradients runs the forward twice; a
    kernel case is three to five times shorter this way (CHANGES.md,
    PR 39)."""
    def loss(q, k, v):
        out = fn(q, k, v)
        o = out.astype(jnp.float32)
        return (o * o if cot is None else o * cot).sum(), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return out, grads


def assert_matches_reference(flash, ref, q, k, v, *, cot=None, shared=None):
    """``flash`` against ``ref`` on ``(q, k, v)``, the output and all three
    gradients, at the tolerances of the operands' dtype; returns flash's
    ``(out, grads)``. ``shared``: ``(results, key)``, a dict that outlives
    the test (the ``reference_results`` fixture) and what names the case
    whatever kernel path runs it: the reference's result is computed by
    the first path that asks and read by the others."""
    results, key = shared or ({}, None)
    if key not in results:
        results[key] = out_and_grads(ref, q, k, v, cot)
    want, want_grads = results[key]
    out, grads = out_and_grads(flash, q, k, v, cot)
    f32 = q.dtype == jnp.float32
    # The output is as wide as v, which may be another width than q and k.
    assert out.dtype == q.dtype and out.shape == q.shape[:-1] + v.shape[-1:]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if f32 else 2e-2, rtol=1e-4 if f32 else 2e-2)
    for a, b in zip(grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert (np.abs(a - b).max() / (np.abs(b).max() + 1e-6)
                < (2e-3 if f32 else 5e-2))
    return out, grads


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

# The streamed calls of the benchmark's cells:
# name: (batch, sequence, query heads, K/V heads, q/k width, v width, window)
CELLS = {
    "smallthinker-global": (2, 8192, 28, 4, 128, 128, None),
    "smallthinker-window-4096": (2, 8192, 28, 4, 128, 128, 4096),
    "olmo-hybrid": (1, 8192, 15, 15, 128, 128, None),
    "laguna-full": (2, 8192, 48, 8, 128, 128, None),
    "laguna-window-512": (2, 8192, 64, 8, 128, 128, 512),
    "lfm2-head-64": (4, 8192, 32, 8, 64, 64, None),
    "joyai-192-over-128": (2, 8192, 32, 32, 192, 128, None),
}
