"""Milliseconds a step of device time in the routed experts' layer under
the sigmoid rule at a router 256 wide, all of it: what ``moe_held_ms``
reads (the four ``hvd.moe.*`` scopes and the grouped products together,
forward, recomputed and backward), under a name of this cell's own: 8 of
256 experts held, 8 chosen, five sparse blocks (the
multi-token-prediction module's among them), so a thirty-second of the
131,072 sorted rows a block belong to a group. ``None`` from a program
that plants no such scope."""

from layer_metrics import moe_held_ms


def read(run):
    return moe_held_ms.read(run)
