"""Megabytes (1e6 bytes) the compiled step's collectives carry: the sum
of their result shapes."""

from harness import hlo_text


def read(run):
    return sum(c.payload_bytes
               for c in hlo_text.collectives(run["compiled_text"])) / 1e6
