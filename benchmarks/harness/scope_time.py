"""Device time under scopes of the program's own, whatever the phase.

``scopes.py`` sorts a traced operation into one phase of a step; a layer
that plants scopes of its own (an expert layer's route, dispatch, experts
and combine) asks another question: how long did the operations run that
were traced under one of these names, forward, backward and recomputed
alike. An operation counts where any ``op_name`` it stands for (its own
or, for a fusion, an inner instruction's) holds the scope as a whole
word.
"""

import re

from . import scopes, trace_reduce


def _word(scope):
    return re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")


def names_under(text, scope_names):
    """Instruction names of the compiled ``text`` traced under one of
    ``scope_names``."""
    words = [_word(s) for s in scope_names]
    return {name for name, ops in scopes.op_names(text).items()
            if any(w.search(op) for w in words for op in ops)}


def union_ms_a_step(run, keep):
    """Milliseconds a step in which an operation named in ``keep`` ran,
    per device the union of their intervals, averaged over the devices;
    ``None`` without a device trace."""
    trace = run.get("trace")
    if trace is None or not trace.devices:
        return None
    ns = sum(trace_reduce.total(trace_reduce.spans(
        events, lambda n: n in keep)) for events in trace.devices.values())
    return ns / len(trace.devices) / run["steps"] / 1e6
