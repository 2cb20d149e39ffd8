"""Milliseconds a step of device time in the shared expert: operations
traced under the program's scope ``hvd.moe.shared`` (``models/laguna.py``:
the dense SiLU-gated MLP every token passes beside the routed experts),
forward, recomputed and backward together. ``None`` from a program that
plants no such scope."""

from harness import scope_time

SCOPE = "hvd.moe.shared"


def read(run):
    keep = scope_time.names_under(run["compiled_text"], (SCOPE,))
    return scope_time.union_ms_a_step(run, keep) if keep else None
