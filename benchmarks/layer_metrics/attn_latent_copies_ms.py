"""Milliseconds a step of device time under the program's scope
``hvd.attn.latent`` (``models/joyai.py`` ``LatentAttention``: the
attention call on the expanded q, k and v) OUTSIDE the three flash
kernels ``attn_latent_flash_ms`` reads: what XLA puts around the Mosaic
calls to hand them their operands and take their results, copies and
transpositions that compute nothing (while the streamed kernels took
heads folded into batch these were the fold, a transposed copy of every
operand and result: 15 arrays of 134-201 MB a block), and the pass that
makes ``delta`` from ``do`` and ``o``, forward, recomputed forward and
backward together. ``hvd.attn.latent.proj`` is another scope, not this
one's: a scope counts as a whole word. ``None`` from a program that
plants no such scope."""

from harness import scope_time
from layer_metrics import attn_latent_flash_ms, attn_window_ms

SCOPE = attn_latent_flash_ms.SCOPE


def read(run):
    kernels = attn_window_ms.kernels_under(run, SCOPE)
    if kernels is None:
        return None
    around = scope_time.names_under(run["compiled_text"], (SCOPE,)) - kernels
    return scope_time.union_ms_a_step(run, around)
