"""Milliseconds a step of device time that forward, backward, update and
exchange do not explain: operations under no phase's scope, and fusions
whose inner instructions fall in more than one phase."""

from harness import scopes


def read(run):
    return scopes.phase_ms_a_step(run, (scopes.NONE, scopes.MIXED))
