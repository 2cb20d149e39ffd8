"""Milliseconds a step of device time around the experts: operations
traced under the program's scopes ``hvd.moe.route``, ``hvd.moe.dispatch``
and ``hvd.moe.combine`` (choosing experts and weights, sorting and
gathering the rows that land here, the weighted sum back into token
order), forward, recomputed and backward together. A fusion that also
holds an operation of the experts' own counts there, not here."""

from harness import scope_time
from layer_metrics import moe_experts_ms

SCOPES = ("hvd.moe.route", "hvd.moe.dispatch", "hvd.moe.combine")


def read(run):
    keep = scope_time.names_under(run["compiled_text"], SCOPES)
    if not keep:
        return None
    return scope_time.union_ms_a_step(
        run, keep - (moe_experts_ms.expert_operations(run) or set()))
