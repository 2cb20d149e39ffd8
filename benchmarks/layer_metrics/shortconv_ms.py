"""Milliseconds a step of device time in the gated short-convolution
mixers, all of them and all of each: operations traced under the
program's scope ``hvd.shortconv`` (``models/lfm2.py`` ``ShortConvMixer``
plants it around the whole mixer: the in-projection 2048 x 6144, the two
gates and the causal convolution, the out-projection), forward,
recomputed and backward together, the projections' weight gradients
included. ``None`` from a program that plants no such scope."""

from harness import scope_time

SCOPE = "hvd.shortconv"


def read(run):
    keep = scope_time.names_under(run["compiled_text"], (SCOPE,))
    return scope_time.union_ms_a_step(run, keep) if keep else None
