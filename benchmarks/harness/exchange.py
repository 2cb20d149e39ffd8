"""The gradient exchange of a step: what the program said it asked to be
reduced, and what the device trace shows of it.

Two sources, neither read from outside the program's own names:

* ``horovod_tpu.profiler.exchanges()``, the record the program keeps
  while it traces a step (leaves, bytes as held and on the wire, axis
  and its size), read in the run's own process as ``program_log`` reads
  the span log. A program that keeps no such record (an older commit)
  gives ``None``. A test hands its records in under
  ``run["program_exchanges"]``, as dicts of the record's fields.
* The traced operations whose phase is ``exchange`` (``scopes.phases``:
  traced under ``hvd.exchange`` / ``hvd.allreduce.<prefix>.<i>``, so the
  collectives with compression's casts and the averaging that XLA left
  beside them), against the operations of the backward pass.

Checked on a hand-worked text and trace and on heads recorded on the
chip in ``tests/benchmark/test_exchange.py``.
"""

import collections
import functools

from . import program_log, scopes, trace_reduce

PHASE = "exchange"


# ---------------------------------------------------- the program's record

def records(run):
    """The program's exchange records as dicts, oldest first, or
    ``None``."""
    if "program_exchanges" in run:
        return run["program_exchanges"]
    try:
        from horovod_tpu.common import profiler
    except ImportError:
        return None
    read = getattr(profiler, "exchanges", None)
    return None if read is None else [r._asdict() for r in read()]


def record_of_the_step(run):
    """The newest record stamped before the window began: the timed
    step's, since set-up traces it last. ``None`` where there is none."""
    limit = program_log.window_start_ns(run)
    found = [r for r in records(run) or () if r["at_ns"] <= limit]
    return found[-1] if found else None


# ------------------------------------------------------- the device trace

@functools.lru_cache(maxsize=2)
def operations(text):
    """``(exchange, backward)``: the instruction names of the compiled
    ``text`` whose phase is the exchange, and those that are work of the
    backward pass: phase ``backward``, or ``mixed`` with a backward
    instruction inside (a weight gradient with the optimizer's update in
    its epilogue; not the exchange's averaging fused with the update)."""
    phases, names = scopes.phases(text), scopes.op_names(text)
    exchange = {n for n, p in phases.items() if p == PHASE}
    backward = {n for n, p in phases.items() if p == "backward" or (
        p == scopes.MIXED
        and any(scopes.phase(o) == "backward" for o in names[n]))}
    return exchange, backward


def _traced(run):
    """``(event lists, exchange, backward)``: every device's events and
    :func:`operations` of the run's text, or ``None`` where there is no
    device trace or the program plants no exchange scope."""
    trace = run.get("trace")
    if trace is None or not trace.devices:
        return None
    text = run["compiled_text"]
    if PHASE not in scopes.phases_planted(text):
        return None
    return (list(trace.devices.values()),) + operations(text)


def worst_device_ms_a_step(run, ns_of):
    """``ns_of(events, is_exchange)`` on every device, the largest, as
    milliseconds a step; ``None`` where :func:`_traced` finds nothing
    (0.0 where the scope is planted and XLA left no operation of the
    exchange alone)."""
    found = _traced(run)
    if found is None:
        return None
    devices, exchange, _ = found
    worst = max(ns_of(events, exchange.__contains__) for events in devices)
    return worst / run["steps"] / 1e6


def busy_ns(events, keep):
    """Nanoseconds in which an operation ``keep`` selects ran: the union
    of their intervals, as busy time is counted."""
    return trace_reduce.total(trace_reduce.spans(events, keep))


def split_steps(events, steps):
    """A device's events, one list a step. The device's own list tells
    the steps apart: an instruction of the entry computation runs once a
    step (one inside a loop's body more often), so the first event whose
    name occurs ``steps`` times opens every step. Where no name does (a
    trace cut short), the rarest count stands in for ``steps``. Events
    before the first opening are left out."""
    counts = collections.Counter(name for name, _, _ in events)
    if steps not in counts.values():
        steps = min(counts.values())
    opener = next(name for name, _, _ in events if counts[name] == steps)
    out = []
    for event in events:
        if event[0] == opener:
            out.append([])
        if out:
            out[-1].append(event)
    return out


def backward_left(step_events, exchange, backward):
    """Of one step's backward time on one device (the union of those
    operations' intervals), the share that lies after the step's first
    exchange operation started; ``None`` where the step holds no exchange
    operation or no backward one."""
    starts = [s for name, s, _ in step_events if name in exchange]
    spans = trace_reduce.spans(step_events, backward.__contains__)
    if not starts or not spans:
        return None
    first = min(starts)
    left = sum(max(0, end - max(start, first)) for start, end in spans)
    return left / trace_reduce.total(spans)


def backward_left_pct(run):
    """:func:`backward_left` as a percentage, the mean over the steps of
    every device; ``None`` where :func:`_traced` finds nothing or no
    step holds both kinds of operation."""
    found = _traced(run)
    if found is None:
        return None
    devices, exchange, backward = found
    shares = [backward_left(step, exchange, backward)
              for events in devices
              for step in split_steps(events, run["steps"])]
    shares = [s for s in shares if s is not None]
    return 100.0 * sum(shares) / len(shares) if shares else None
