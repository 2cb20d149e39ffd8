"""Milliseconds a step of device time in the passes of a looped decoder:
operations traced under the program's scope ``hvd.loop.pass``
(``models/decoder.py`` ``looped_decoder_layers`` plants it around each
pass's layers and the final norm that ends it, under the same name in
every pass), forward, recomputed and backward together, the flash
kernels, the projections and the MLPs inside. The passes are unrolled, so
every operation of every pass is an operation of the compiled step and
counts on its own; what is left of the step is the lookup, the exits, the
head and the optimizer. ``None`` from a program that plants no such
scope."""

from harness import scope_time

SCOPE = "hvd.loop.pass"


def read(run):
    keep = scope_time.names_under(run["compiled_text"], (SCOPE,))
    return scope_time.union_ms_a_step(run, keep) if keep else None
