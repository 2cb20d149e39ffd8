"""Milliseconds a step of device time in the gated delta rule's chunked
scan: operations traced under the program's scope ``hvd.linattn.scan``
(everything of ``ops.linear_attention.gated_delta_rule``: the chunk-local
products and the triangular system, the scan over chunks, the outputs),
forward, recomputed and backward together. The scan over chunks is a
``while`` of the compiled step, which carries the scope in its own
``op_name`` and so counts whole, the time between its body's operations
included. ``None`` from a program that plants no such scope."""

from harness import scope_time

SCOPE = "hvd.linattn.scan"


def scan_operations(run):
    return scope_time.names_under(run["compiled_text"], (SCOPE,))


def read(run):
    keep = scan_operations(run)
    return scope_time.union_ms_a_step(run, keep) if keep else None
