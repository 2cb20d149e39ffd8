"""The fused gated short convolution (``ops/short_conv.py``) in the
Pallas interpreter: ``gated_short_conv_packed`` against the plain form
``c * causal_conv(b * u, taps)`` and the benchmark reference's shifted
sums, output and all four gradients; what crosses a block's edge and what
does not cross a batch entry's; which shapes take the kernels; and both
kernels compiled by Mosaic for a described v5e at LFM2's shapes. One
compiled program a case. Blocks are ``block_rows``' own: 512 rows at
widths 128 and 256, so a sequence of 1,024 is two blocks."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.lfm2 import LFM2_TINY, ShortConvMixer
from horovod_tpu.ops.linear_attention import causal_conv
from horovod_tpu.ops.short_conv import block_rows, gated_short_conv_packed
from decoder_helpers import reference_fixture
from test_flash_layouts_streamed import one_chip  # noqa: F401

BATCH, SEQ, ROWS = 2, 1024, 512

reference = reference_fixture("lfm2-24b-a2b")


def _inputs(dim, taps=3, dtype=jnp.float32, seq=SEQ):
    keys = jax.random.split(jax.random.PRNGKey(dim + taps), 3)
    return (jax.random.normal(keys[0], (BATCH, seq, 3 * dim), dtype),
            jax.random.uniform(keys[1], (taps, dim), jnp.float32, -0.6, 0.6),
            jax.random.normal(keys[2], (BATCH, seq, dim), dtype))


def plain(packed, taps):
    b_gate, c_gate, u = jnp.split(packed, 3, axis=-1)
    return c_gate * causal_conv(b_gate * u, taps)


def _both(packed, taps, cot):
    """``(y, d_packed, d_taps)`` of the kernels and of the plain form from
    one compiled program."""
    def side(fn):
        y, vjp = jax.vjp(fn, packed, taps)
        return (y,) + vjp(cot)

    return jax.jit(lambda: (side(gated_short_conv_packed), side(plain)))()


def test_the_block_is_read_from_the_shapes():
    assert block_rows(8192, 2048, 3) == 256
    assert block_rows(SEQ, 128, 3) == block_rows(SEQ, 256, 4) == ROWS
    assert block_rows(8192, 8192, 3) == 64
    # A width off the lane tile, a sequence the block does not divide, a
    # single tap and more taps than the carried rows hold.
    for seq, dim, taps in ((8192, 64, 3), (8192, 2048 + 64, 3),
                           (1000, 128, 3), (8192 + 128, 2048, 3),
                           (SEQ, 128, 1), (SEQ, 128, 10)):
        assert block_rows(seq, dim, taps) is None
    with pytest.raises(ValueError, match="take no kernel"):
        gated_short_conv_packed(jnp.zeros((1, 96, 3 * 64)),
                                jnp.zeros((3, 64)))


@pytest.mark.parametrize("dim,taps", [(128, 3), (256, 3), (128, 4)])
def test_output_and_four_gradients_match_the_plain_form(dim, taps):
    packed, w, cot = _inputs(dim, taps)
    assert SEQ // block_rows(SEQ, dim, taps) == 2
    got, want = _both(packed, w, cot)
    for g, r in zip(got, want):
        assert g.shape == r.shape and g.dtype == r.dtype
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=1e-5)
    # The gates' gradients one by one, then the taps'.
    for g, r in zip(jnp.split(got[1], 3, -1) + [got[2]],
                    jnp.split(want[1], 3, -1) + [want[2]]):
        assert float(jnp.max(jnp.abs(g - r))) \
            <= 2e-5 * float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(r))) > 1.0


@pytest.mark.parametrize("dim", [128, 256])
def test_the_forward_is_the_references_three_shifted_sums(dim, reference):
    packed, w, _ = _inputs(dim)

    @jax.jit
    def both():
        b_gate, c_gate, u = jnp.split(packed, 3, axis=-1)
        want = jnp.stack([c * reference._short_conv(lambda a: a, x, w)
                          for c, x in zip(c_gate, b_gate * u)])
        return gated_short_conv_packed(packed, w), want

    got, want = both()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_bfloat16_gates_are_rounded_once_and_no_less_exact_than_the_plain():
    packed, w, cot = _inputs(128, dtype=jnp.bfloat16)

    @jax.jit
    def three():
        def side(fn, *inputs):
            y, vjp = jax.vjp(fn, *inputs)
            return (y,) + vjp(cot.astype(y.dtype))
        return (side(gated_short_conv_packed, packed, w),
                side(plain, packed, w),
                side(plain, packed.astype(jnp.float32), w))

    got, want, exact = three()
    for g, r, e in zip(got, want, exact):
        assert g.dtype == r.dtype
        scale = float(jnp.max(jnp.abs(e)))
        ours = float(jnp.max(jnp.abs(g.astype(jnp.float32) - e))) / scale
        theirs = float(jnp.max(jnp.abs(r.astype(jnp.float32) - e))) / scale
        # One rounding of the result: half a bfloat16 step of the largest.
        assert ours <= 2.0 ** -8 and ours <= theirs * 1.001


def test_an_impulse_crosses_a_blocks_edge_by_two_rows_and_so_does_its_gradient():
    dim = 128
    packed, w, _ = _inputs(dim)
    edge = ROWS - 1                     # the last row of the first block
    bumped = packed.at[0, edge, 2 * dim:].add(1.0)      # u alone
    # What rows 512 and 513 (the next block's first two) send back.
    cot = jnp.zeros((BATCH, SEQ, dim)).at[0, ROWS:ROWS + 2].set(1.0)

    @jax.jit
    def run():
        y, vjp = jax.vjp(gated_short_conv_packed, packed, w)
        (want_grad, _) = jax.vjp(plain, packed, w)[1](cot)
        return y, gated_short_conv_packed(bumped, w), vjp(cot)[0], want_grad

    y, moved, grad, want_grad = run()
    rows = np.flatnonzero(np.any(np.asarray(moved != y), axis=(0, 2)))
    assert rows.tolist() == [edge, edge + 1, edge + 2]
    assert not np.any(np.asarray(moved[1] != y[1]))
    # B's and u's gradient reach two rows behind the edge and stop; C's
    # stays on the rows the cotangent is on.
    d_b, d_c, d_u = jnp.split(grad, 3, -1)
    for g in (d_b, d_u):
        rows = np.flatnonzero(np.any(np.asarray(g[0] != 0), axis=-1))
        assert rows.tolist() == [edge - 1, edge, edge + 1, edge + 2]
    rows = np.flatnonzero(np.any(np.asarray(d_c[0] != 0), axis=-1))
    assert rows.tolist() == [edge + 1, edge + 2]
    assert not np.any(np.asarray(grad[1]))
    assert float(jnp.max(jnp.abs(grad - want_grad))) \
        <= 2e-5 * float(jnp.max(jnp.abs(want_grad)))


def test_a_batch_entrys_first_tokens_read_zeros_not_the_entry_before():
    dim = 128
    packed, w, cot = _inputs(dim)
    # The first entry ends, and the second starts, on rows a carry would
    # show.
    loud = packed.at[0, -2:].set(100.0).at[1, :2].set(100.0)

    @jax.jit
    def run():
        def side(p, c):
            y, vjp = jax.vjp(gated_short_conv_packed, p, w)
            return (y,) + vjp(c)
        return side(loud, cot), side(loud[1:], cot[1:]), side(loud[:1],
                                                              cot[:1])

    (y, grad, d_taps), second, first = run()
    np.testing.assert_array_equal(y[1], second[0][0])
    np.testing.assert_array_equal(grad[1], second[1][0])
    np.testing.assert_array_equal(y[0], first[0][0])
    np.testing.assert_array_equal(grad[0], first[1][0])
    np.testing.assert_allclose(d_taps, first[2] + second[2], rtol=1e-5)
    b_gate, c_gate, u = (a[1, 0] for a in jnp.split(loud, 3, -1))
    np.testing.assert_allclose(y[1, 0], c_gate * w[-1] * b_gate * u,
                               rtol=1e-6)


def _mixer(dim, seq):
    cfg = dataclasses.replace(LFM2_TINY, dim=dim, dtype=jnp.float32)
    mixer = ShortConvMixer(cfg)
    x = jax.ShapeDtypeStruct((1, seq, dim), jnp.float32)
    params = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)

    def loss(params, x):
        return jnp.sum(mixer.apply(params, x) ** 2)

    return jax.value_and_grad(loss), (params, x)


def _primitives(step, shapes):
    """Names of the primitives of ``step``'s jaxpr, nested ones too, a
    kernel's body left out."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.append(eqn.primitive.name)
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    jaxpr = jax.make_jaxpr(step)(*shapes)
    walk(jaxpr.jaxpr)
    return names, jaxpr.jaxpr


@pytest.mark.parametrize("dim,seq", [(64, 1024), (128, 1000)],
                         ids=["width-64", "ragged-sequence"])
def test_shapes_that_do_not_tile_take_the_plain_form(dim, seq):
    """No kernel in the program, no custom call in its text, and the
    plain form's pad in it."""
    step, shapes = _mixer(dim, seq)
    assert block_rows(seq, dim, 3) is None
    assert "pallas_call" not in _primitives(step, shapes)[0]
    text = jax.jit(step).lower(*shapes).as_text()
    assert "custom_call" not in text and "stablehlo.pad" in text


def test_the_projections_result_is_read_and_its_gradient_written_in_place():
    """Where the shapes tile: one forward and one backward kernel, no
    split or slice of the in-projection's result ahead of the forward
    one and no concatenation or pad behind the backward one: the
    kernels' operand is the product itself."""
    names, jaxpr = _primitives(*_mixer(128, 1024))
    assert names.count("pallas_call") == 2
    assert not {"split", "slice", "dynamic_slice", "gather", "concatenate",
                "pad", "dynamic_update_slice"} & set(names), names
    producers = {id(v): eqn.primitive.name for eqn in jaxpr.eqns
                 for v in eqn.outvars}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            assert producers.get(id(eqn.invars[0])) == "dot_general"


def test_both_kernels_compile_for_v5e_at_the_cells_shapes(
        one_chip, monkeypatch):  # noqa: F811
    """Mosaic, not the interpreter, at four sequences of 8,192 by 2,048
    in bfloat16: the rolls along sublanes, the 16-row block behind and 64
    MiB of VMEM asked for are what the interpreter cannot refuse. The two
    calls are the whole program: nothing is copied around them. Nothing
    runs."""
    import horovod_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    packed = shape((4, 8192, 3 * 2048), jnp.bfloat16)
    cot = shape((4, 8192, 2048), jnp.bfloat16)

    def both(packed, taps, cot):
        y, vjp = jax.vjp(gated_short_conv_packed, packed, taps)
        return (y,) + vjp(cot)

    compiled = jax.jit(both).lower(
        packed, shape((3, 2048), jnp.float32), cot).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2
    for name in ("hvd_shortconv_fwd", "hvd_shortconv_bwd"):
        assert sum(name in line for line in calls) == 1
    assert " fusion(" not in text and " copy(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0
