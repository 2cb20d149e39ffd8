"""Milliseconds a step of device time in everything of multi-head latent
attention but the attention call and ``wo``: operations traced under the
program's scope ``hvd.attn.latent.proj`` (``models/joyai.py``
``LatentAttention``: the two down-projections 2048 x 1536 and 2048 x 576,
their norms, the two up-projections 1536 x 6144 and 512 x 8192, the
rotation of the 64 rotary columns and the copy of the one rotary key a
token into every head's k), every attending block's together, forward,
recomputed and backward, the projections' weight gradients included.
``hvd.attn.latent`` is another scope, not this one's prefix: a scope
counts as a whole word. ``None`` from a program that plants no such
scope."""

from harness import scope_time

SCOPE = "hvd.attn.latent.proj"


def read(run):
    keep = scope_time.names_under(run["compiled_text"], (SCOPE,))
    return scope_time.union_ms_a_step(run, keep) if keep else None
