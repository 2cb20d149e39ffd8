"""Where the flash kernels find a head in the caller's arrays (PR 29,
PR 42).

Both families take (B, heads * d, S): a head is a band of d rows which
the block index map picks, with its sequence on the lanes. A one-tile
grid step's block is a few heads and a head a static slice of it; a
streamed step's block is one head's (1, d, block). Every head layout a
step can be handed (a proper part of the head axis, the whole axis, one
head, a query group, narrow and wide heads, q and k of another width
than v) is checked on both against the XLA reference, forward and all
three gradients (``lse`` out and ``dlse`` in: ``test_flash_paths.py``;
the streamed kernels at the head layouts of the benchmark's cells:
``test_flash_layouts_streamed.py``); and the program either path hands
XLA holds no 4-D transpose."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_helpers import (CELLS, PATHS, _rand,
                               assert_matches_reference, both_paths)
from horovod_tpu.ops.attention import (_one_tile_path, flash_attention,
                                       reference_attention)

B, S = 2, 32

# name: (query heads, K/V heads, head width)
HEADS = {
    "h12_d64": (12, 12, 64),        # BERT: a step's band is 4 heads of 12
    "h2_d64": (2, 2, 64),           # the whole axis in one step
    "h1_d64": (1, 1, 64),           # one head
    "gqa8_2_d64": (8, 2, 64),       # a query group of 4 over each K/V head
    "gqa28_4_d128": (28, 4, 128),   # SmallThinker: a group of 7 (streams)
    "h2_d32": (2, 2, 32),
    "h2_d256": (2, 2, 256),
}
heads = pytest.mark.parametrize("heads", sorted(HEADS))


def _qkv(name, dtype, sq=S, sk=S, seed=0):
    h, hkv, d = HEADS[name]
    return (_rand((B, sq, h, d), seed, dtype),
            _rand((B, sk, hkv, d), seed + 1, dtype),
            _rand((B, sk, hkv, d), seed + 2, dtype))


def _key_mask(sk, seed):
    mask = np.random.RandomState(seed).rand(B, sk) > 0.3
    mask[:, 0] = True      # no fully-masked row, causal or not
    return jnp.asarray(mask)


@both_paths
@heads
@pytest.mark.parametrize("how,dtype", [("key_mask", "bfloat16"),
                                       ("causal", "float32")])
def test_flash_head_layouts_forward_and_grad(path, heads, how, dtype,
                                             reference_results):
    q, k, v = _qkv(heads, jnp.dtype(dtype))
    kw = (dict(key_mask=_key_mask(S, 5)) if how == "key_mask"
          else dict(causal=True))
    assert_matches_reference(
        lambda q, k, v: flash_attention(q, k, v, **kw, **PATHS[path]),
        lambda q, k, v: reference_attention(q, k, v, **kw), q, k, v,
        shared=(reference_results, (heads, how, dtype)))


@both_paths
@pytest.mark.parametrize("heads,how", [
    (heads, how)
    for heads in ("h12_d64", "gqa8_2_d64", "gqa28_4_d128")
    for how in ("plain", "causal_sq_ne_sk", "window")
] + [("h12_d64", "pad_197")])
def test_flash_head_layouts_shapes_of_the_band(path, heads, how,
                                               reference_results):
    sq, sk, kw = {
        "plain": (S, S, {}),
        "causal_sq_ne_sk": (16, S, dict(causal=True)),       # decode rows
        "window": (S, S, dict(causal=True, window=12)),
        "pad_197": (197, 197, dict(key_mask=_key_mask(197, 7))),
    }[how]
    blocks = PATHS[path]
    if how == "pad_197" and blocks:     # 197 pads to 256: four blocks
        blocks = dict(block_q=64, block_k=64)
    q, k, v = _qkv(heads, jnp.float32, sq, sk, seed=10)
    assert_matches_reference(
        lambda q, k, v: flash_attention(q, k, v, **kw, **blocks),
        lambda q, k, v: reference_attention(q, k, v, **kw), q, k, v,
        shared=(reference_results, (heads, how)))


def _transposed_ranks(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "transpose":
            found.append(len(eqn.invars[0].aval.shape))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _transposed_ranks(inner, found)
    return found


@pytest.mark.parametrize("case,b,s,h,hkv,d,masked", [
    ("bert-base-s512-dp1", 64, 512, 12, 12, 64, True),
    ("vit_padded_197", 8, 256, 12, 12, 64, True),
    ("decoder_gqa_d128", 4, 512, 8, 2, 128, False),
] + [pytest.param(name, b, s, h, hkv, (d, dv), window, id=name)
     for name, (b, s, h, hkv, d, dv, window) in sorted(CELLS.items())])
def test_the_program_transposes_no_4d_operand(case, b, s, h, hkv, d, masked):
    # What the fold was: (B, S, H, D) -> (B, H, S, D), a copy XLA had to
    # make around every call (eight a BERT layer; 15 arrays of 134-201 MB
    # a block of JoyAI's cell while the streamed kernels took it). Both
    # paths reshape to (B, S, H * D), a bitcast, and swap the last two
    # axes, which XLA folds into the layout it already keeps the array in
    # (tests/benchmark/test_aot_flash_layout.py holds that).
    streamed = case in CELLS
    d, dv, window = (d + (masked,)) if streamed else (d, d, None)
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, hkv, dv), jnp.bfloat16)
    m = jax.ShapeDtypeStruct((b, s), jnp.bool_)
    one_tile = _one_tile_path(q, k, min(s, 512), min(s, 1024), v)
    assert bool(one_tile) is not streamed

    def attend(q, k, v, m):
        if streamed:
            return flash_attention(q, k, v, causal=True, window=window,
                                   interpret=True)
        return flash_attention(q, k, v, key_mask=m if masked else None,
                               causal=not masked, interpret=True)

    grad = jax.grad(lambda *a: attend(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))
    for fn in (attend, grad):
        ranks = _transposed_ranks(jax.make_jaxpr(fn)(q, k, v, m).jaxpr, [])
        assert ranks and max(ranks) == 3, (case, ranks)
