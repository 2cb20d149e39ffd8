"""One compiled program a call for the model tests. Called eagerly, a flax
``init`` or ``apply`` dispatches every operation as a program of its own,
and XLA compiles each; a test of a tiny model then spends its time in
hundreds of compiles. These hand XLA the whole call instead."""

import jax


def jit_init(model, *args, rngs=None, **kw):
    """``model.init(rngs, *args, **kw)`` as one program; ``rngs`` defaults
    to ``PRNGKey(0)``, ``kw`` (``train=``, ``deterministic=``) is static."""
    rngs = jax.random.PRNGKey(0) if rngs is None else rngs
    return jax.jit(lambda rngs, *args: model.init(rngs, *args, **kw))(
        rngs, *args)


def jit_apply(model, **kw):
    """``lambda variables, *args: model.apply(variables, *args, **kw)``,
    jitted; ``kw`` is static, closed-over arrays are constants."""
    return jax.jit(lambda variables, *args: model.apply(
        variables, *args, **kw))
