"""Milliseconds a step, per device, in which a collective runs and no
other operation does, on the worst device. Nothing to read where the
trace holds no collective."""

from harness import trace_reduce


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.devices:
        return None
    collectives = trace_reduce.collective_names(trace)
    if not collectives:
        return None
    worst = max(trace_reduce.exposed_ns(events, collectives.__contains__)
                for events in trace.devices.values())
    return worst / run["steps"] / 1e6
