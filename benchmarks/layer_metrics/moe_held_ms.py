"""Milliseconds a step of device time in the routed experts' layer, all
of it: operations traced under the program's scopes ``hvd.moe.route``,
``hvd.moe.dispatch``, ``hvd.moe.experts`` and ``hvd.moe.combine``
(``parallel/moe.py`` ``moe_apply_held``) and the grouped products, which
keep no scope and are counted by name (``moe_experts_ms``), forward,
recomputed and backward together. One number where the held experts are
a sixteenth of the router's width: most of the sorted rows belong to no
group, and what the layer costs is moving them. ``None`` from a program
that plants no such scope."""

from harness import scope_time
from layer_metrics import moe_dispatch_ms, moe_experts_ms


def read(run):
    experts = moe_experts_ms.expert_operations(run)
    if experts is None:
        return None
    return scope_time.union_ms_a_step(run, experts | scope_time.names_under(
        run["compiled_text"], moe_dispatch_ms.SCOPES))
