"""Milliseconds a step in which some operation runs on the device: the
union of the operations' intervals over the traced window, averaged over
the devices, divided by the steps."""

from harness import trace_reduce


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.devices:
        return None
    return trace_reduce.mean_busy_ns(trace) / run["steps"] / 1e6
