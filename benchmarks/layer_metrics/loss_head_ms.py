"""Milliseconds a step of device time in the chunked loss head:
operations traced under the program's scope ``hvd.loss.head`` (everything
of ``models.chunked_causal_lm_loss``: the sweep over the sequence's chunks
that applies the head and computes the loss and both of its gradients,
and what stands around it). The sweep is a ``while`` of the compiled
step, which carries the scope in its own ``op_name`` and so counts whole,
the time between its body's operations included. ``None`` from a program
that plants no such scope."""

from harness import scope_time

SCOPE = "hvd.loss.head"


def read(run):
    keep = scope_time.names_under(run["compiled_text"], (SCOPE,))
    return scope_time.union_ms_a_step(run, keep) if keep else None
