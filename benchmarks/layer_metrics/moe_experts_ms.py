"""Milliseconds a step of device time in the expert layer's own products:
operations traced under the program's scope ``hvd.moe.experts``, forward,
recomputed and backward together, and the grouped products themselves.
XLA expands ``jax.lax.ragged_dot`` into Mosaic calls that it names
``ragged-dot-none`` (with a ``ragged-dot-metadata`` call ahead that turns
the group sizes into a tile schedule) and that keep no scope; the expert
layer's are the only grouped products of the step, so they are counted
here by that name."""

from harness import scope_time, scopes

SCOPE = "hvd.moe.experts"
GROUPED_PRODUCTS = ("ragged-dot-none", "ragged-dot-metadata")


def expert_operations(run):
    """Names of the traced operations that are the experts' work, or
    ``None`` where the program plants no such scope."""
    text = run["compiled_text"]
    keep = scope_time.names_under(text, (SCOPE,))
    if not keep:
        return None
    trace = run.get("trace")
    if trace is not None:
        for kernel in GROUPED_PRODUCTS:
            keep |= scopes.kernel_names(trace, text, kernel)
    return keep


def read(run):
    keep = expert_operations(run)
    return None if keep is None else scope_time.union_ms_a_step(run, keep)
