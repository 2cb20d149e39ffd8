"""Milliseconds a step of device time in the forward pass: operations
traced under jax's ``jvp(`` and not under ``transpose(`` or a scope of
the program's (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.phase_ms_a_step(run, ("forward",))
