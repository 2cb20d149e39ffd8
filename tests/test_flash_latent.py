"""The flash kernels with q and k of one head width and v, the output and
``do`` of another (multi-head latent attention: 192 over 128), in the
interpreter against ``reference_attention``: the output and the three
gradients, on both kernel paths (the one-tile family takes the two widths
too: a head is the band of rows its own block gives it), causal and not,
grouped and not, under a window, in bfloat16; the scale is q's width's;
q and k of two widths are refused. One jitted program a side
(``attention_helpers``)."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from attention_helpers import (KERNELS, PATHS, _rand, assert_matches_reference,
                               both_paths, kernel_grids, out_and_grads)
from horovod_tpu.ops.attention import (_one_tile_path, flash_attention,
                                       reference_attention)

B, S = 2, 64
# (q/k width, v width): the published pair, and its 3:2 at a tiny size.
WIDTHS = {"192-over-128": (192, 128), "48-over-32": (48, 32)}


def _operands(widths, heads, kv_heads, dtype=jnp.float32, seq=S):
    qk, vo = WIDTHS[widths]
    return (_rand((B, seq, heads, qk), 1, dtype),
            _rand((B, seq, kv_heads, qk), 2, dtype),
            _rand((B, seq, kv_heads, vo), 3, dtype))


@pytest.fixture(scope="module")
def reference_results():
    return {}


@both_paths
@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("causal,heads,kv_heads", [
    (True, 4, 2), (False, 2, 2)], ids=["causal-grouped", "full-ungrouped"])
def test_two_widths_match_the_reference(path, widths, causal, heads,
                                        kv_heads, reference_results):
    q, k, v = _operands(widths, heads, kv_heads)
    cot = _rand((B, S, heads, WIDTHS[widths][1]), 4)
    out, (dq, dk, dv) = assert_matches_reference(
        functools.partial(flash_attention, causal=causal, interpret=True,
                          **PATHS[path]),
        functools.partial(reference_attention, causal=causal),
        q, k, v, cot=cot, shared=(reference_results,
                                  (widths, causal, heads)))
    assert (dq.shape, dk.shape, dv.shape) == (q.shape, k.shape, v.shape)
    # The path the case names is the path it took.
    blocks = PATHS[path] or {"block_q": S, "block_k": S}
    assert bool(_one_tile_path(q, k, blocks["block_q"], blocks["block_k"],
                               v)) is (path == "one_tile")


@pytest.mark.parametrize("case", ["window", "bfloat16", "longer-keys"])
def test_two_widths_on_the_streamed_path(case):
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    q, k, v = _operands("192-over-128", 4, 2, dtype)
    kwargs = dict(causal=True)
    if case == "window":
        kwargs["window"] = 24
    if case == "longer-keys":
        # The decode convention: the queries are the last rows.
        q = q[:, S // 2:]
    assert_matches_reference(
        functools.partial(flash_attention, interpret=True,
                          **PATHS["streamed"], **kwargs),
        functools.partial(reference_attention, **kwargs), q, k, v)


def test_the_scale_is_q_width_and_the_kernels_keep_their_names():
    q, k, v = _operands("48-over-32", 2, 2)
    flash = functools.partial(flash_attention, causal=True, interpret=True,
                              **PATHS["streamed"])
    out, _ = out_and_grads(flash, q, k, v)
    want, _ = out_and_grads(functools.partial(
        reference_attention, causal=True, sm_scale=48 ** -0.5), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-4)
    wrong, _ = out_and_grads(functools.partial(
        reference_attention, causal=True, sm_scale=32 ** -0.5), q, k, v)
    assert float(jnp.max(jnp.abs(wrong - want))) > 1e-3
    import jax

    grids = kernel_grids(jax.grad(
        lambda q, k, v: flash(q, k, v).sum(), (0, 1, 2)), q, k, v)
    assert set(grids) == set(KERNELS)


@pytest.mark.parametrize("fn", [
    functools.partial(flash_attention, interpret=True), reference_attention],
    ids=["flash", "reference"])
def test_q_and_k_of_two_widths_are_refused(fn):
    q, k, v = _operands("48-over-32", 2, 2)
    with pytest.raises(ValueError, match="contracted over one head width"):
        fn(q, v, v)
