"""Milliseconds a step of device time in the pointwise part of the gated
short-convolution mixers: operations traced under the program's scope
``hvd.shortconv.pointwise`` (``models/lfm2.py``: ``C * conv3(B * u)``,
the two gates around the three-tap causal depthwise convolution of
``ops/linear_attention.causal_conv``), forward, recomputed and backward
together: bandwidth-bound passes over arrays of tokens x 2048.

XLA may fuse such a pass into a projection's matrix product (a gate into
the out-projection's prologue, the gradients' concatenation into the
in-projection's weight gradient): a fusion that holds a ``convolution``
or a ``dot`` counts with that product and not here
(``attn_pointwise_ms.product_holders``), so this is the time of the
passes that run on their own. ``None`` from a program that plants no such
scope."""

from harness import scope_time
from layer_metrics import attn_pointwise_ms

SCOPE = "hvd.shortconv.pointwise"


def read(run):
    text = run["compiled_text"]
    keep = scope_time.names_under(text, (SCOPE,))
    if not keep:
        return None
    return scope_time.union_ms_a_step(
        run, keep - attn_pointwise_ms.product_holders(text))
