"""Milliseconds a step of device time in the multi-token-prediction
module, all of it: operations traced under the program's scope
``hvd.mtp`` (``models/joyai.py``: the lookup of each position's next
token, the two norms and the projection 4096 x 2048, the module's block
with its latent attention and its expert layer, whose own scopes lie
inside this one, its norm, and in ``joyai_lm_loss`` the second pass
through the main model's head), forward, recomputed and backward
together. One block in six and one head pass in two. ``None`` from a
program that plants no such scope."""

from harness import scope_time

SCOPE = "hvd.mtp"


def read(run):
    keep = scope_time.names_under(run["compiled_text"], (SCOPE,))
    return scope_time.union_ms_a_step(run, keep) if keep else None
