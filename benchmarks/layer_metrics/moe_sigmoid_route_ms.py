"""Milliseconds a step of device time in choosing experts and weights by
the sigmoid rule: operations traced under the program's scope
``hvd.moe.route`` alone (``parallel/moe.py`` ``moe_apply_held`` calls the
model's rule there; ``sigmoid_top_k``: the sigmoid over the router's 64
outputs, the bias added for the choice, the 4 largest, the chosen
experts' own scores and their normalisation), forward, recomputed and
backward together. A fusion that also holds an operation of the experts'
own counts there, not here. ``None`` from a program that plants no such
scope."""

from harness import scope_time
from layer_metrics import moe_experts_ms

SCOPE = "hvd.moe.route"


def read(run):
    keep = scope_time.names_under(run["compiled_text"], (SCOPE,))
    if not keep:
        return None
    return scope_time.union_ms_a_step(
        run, keep - (moe_experts_ms.expert_operations(run) or set()))
