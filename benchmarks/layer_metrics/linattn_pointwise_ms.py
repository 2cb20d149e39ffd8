"""Milliseconds a step of device time in the pointwise parts of the
linear-attention layers: operations traced under the program's scopes
``hvd.linattn.conv`` (the three causal convolutions with their SiLU and
the q/k L2 norms) and ``hvd.linattn.gate`` (the gated per-head norm),
forward, recomputed and backward together: bandwidth-bound passes over
arrays of tokens x 1440 to 2880. A fusion that also holds an operation of
the scan counts there, not here. ``None`` from a program that plants no
such scope."""

from harness import scope_time
from layer_metrics import linattn_scan_ms

SCOPES = ("hvd.linattn.conv", "hvd.linattn.gate")


def read(run):
    keep = scope_time.names_under(run["compiled_text"], SCOPES)
    if not keep:
        return None
    return scope_time.union_ms_a_step(
        run, keep - linattn_scan_ms.scan_operations(run))
