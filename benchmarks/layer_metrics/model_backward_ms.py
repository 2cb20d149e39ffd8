"""Milliseconds a step of device time in the backward pass: operations
traced under jax's ``transpose(`` (recomputation included) and not under
a scope of the program's (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.phase_ms_a_step(run, ("backward",))
