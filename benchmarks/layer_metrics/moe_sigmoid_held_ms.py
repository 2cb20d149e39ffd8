"""Milliseconds a step of device time in the routed experts' layer under
the sigmoid rule, all of it: what ``moe_held_ms`` reads (the four
``hvd.moe.*`` scopes and the grouped products together, forward,
recomputed and backward), under a name of this cell's own: 8 of 64
experts held, 4 chosen, so an eighth of the 131,072 sorted rows belong to
a group. ``None`` from a program that plants no such scope."""

from layer_metrics import moe_held_ms


def read(run):
    return moe_held_ms.read(run)
