"""Operand rounding for the plain references: ``f32`` is the reference
itself, the lower precisions are its controls."""

import jax
import jax.numpy as jnp


def rounder(precision):
    """Operand rounding of every convolution and matmul, forward and
    backward. ``f32`` is the reference. The lower ones are the control
    that must read not correct: the reference put in the program's place,
    one precision below the configuration's bf16. ``int8`` is what this
    chip's MXU runs at twice the bf16 rate, so the step that would tempt:
    every operand and every cotangent rounded to 255 levels of its
    largest magnitude. ``fp8`` (not native here) likewise scales each
    operand into e4m3's range on the way forward and each cotangent into
    e5m2's on the way back; neither is a bare cast."""
    if precision == "f32":
        return lambda a: a
    if precision == "bf16":
        return lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

    def scaled(a, dtype):
        scale = jnp.max(jnp.abs(a)) / float(jnp.finfo(dtype).max)
        scale = jnp.where(scale > 0, scale, 1.0)
        return (a / scale).astype(dtype).astype(jnp.float32) * scale

    def int8(a):
        scale = jnp.max(jnp.abs(a)) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return jnp.round(a / scale) * scale

    forward, backward = {
        "fp8": (lambda a: scaled(a, jnp.float8_e4m3fn),
                lambda g: scaled(g, jnp.float8_e5m2)),
        "int8": (int8, int8),
    }[precision]

    @jax.custom_vjp
    def q(a):
        return forward(a)

    q.defvjp(lambda a: (forward(a), None), lambda _, g: (backward(g),))
    return q
