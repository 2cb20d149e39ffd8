"""Milliseconds a step of device time in the pointwise passes around
attention: operations traced under the program's scope
``hvd.attn.pointwise`` (``models/laguna.py``: the rotary embedding of q
and k, partial and scaled on a full layer, and the sigmoid gate a head on
the context), forward, recomputed and backward together: bandwidth-bound
passes over arrays of tokens x 1024 to 8192.

XLA fuses some of these passes into a projection's matrix product (the
rotation's transpose into the prologue of ``wq``'s weight gradient, the
gate's sigmoid into ``wg``'s product): a fusion that holds a
``convolution`` or a ``dot`` counts with that product and not here, so
this is the time of the passes that run on their own. ``None`` from a
program that plants no such scope."""

import re

from harness import scope_time, scopes

SCOPE = "hvd.attn.pointwise"
PRODUCT_RE = re.compile(r"=\s*\S+\s+(?:convolution|dot)\(")


def product_holders(text):
    """Names of the instructions of the compiled ``text`` that are a
    matrix product or call a computation that holds one (nested calls
    followed)."""
    holders = {m.group(1) for m in map(scopes.INSTRUCTION_RE.match,
                                       filter(PRODUCT_RE.search,
                                              text.splitlines())) if m}
    _, calls, members = scopes._parse(text)
    grown = True
    while grown:
        grown = {name for name, target in calls.items()
                 if name not in holders
                 and holders.intersection(members.get(target, ()))}
        holders |= grown
    return holders


def read(run):
    text = run["compiled_text"]
    keep = scope_time.names_under(text, (SCOPE,))
    if not keep:
        return None
    return scope_time.union_ms_a_step(run, keep - product_holders(text))
