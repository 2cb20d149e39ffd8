"""Collectives in the compiled step, ``-start``/``-done`` pairs once."""

from harness import hlo_text


def read(run):
    return len(hlo_text.collectives(run["compiled_text"]))
