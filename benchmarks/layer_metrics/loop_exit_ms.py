"""Milliseconds a step of device time in what makes a looped decoder's
passes one loss: operations traced under the program's scope
``hvd.loop.exit`` (``models/ouro.py``: the stacking of every pass's
normed states, the gate's product on them, the exit distribution, its
entropy and the weights handed to the head's sweep), forward and backward
together. Pointwise passes over ``passes x tokens`` floats and one
product ``hidden -> 1``: small beside the passes unless something
materialises the stacked states more often than it must. ``None`` from a
program that plants no such scope."""

from harness import scope_time

SCOPE = "hvd.loop.exit"


def read(run):
    keep = scope_time.names_under(run["compiled_text"], (SCOPE,))
    return scope_time.union_ms_a_step(run, keep) if keep else None
