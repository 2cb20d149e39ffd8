"""Milliseconds a step of device time in the head of a looped decoder:
operations traced under the program's scope ``hvd.loss.head`` (everything
of ``models.weighted_chunked_causal_lm_loss``: the one sweep over the
sequence's chunks that takes every pass's exit, stacked on the batch
axis, through the whole head, and computes the loss and its three
gradients): what ``loss_head_ms`` reads, under a name of this cell's own.
The sweep is a ``while`` of the compiled step and counts whole. ``None``
from a program that plants no such scope."""

from layer_metrics import loss_head_ms


def read(run):
    return loss_head_ms.read(run)
