"""The longest interval with no operation on a device, in milliseconds,
between its first and its last operation of the traced window."""

from harness import trace_reduce


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.devices:
        return None
    return max((end - start for events in trace.devices.values()
                for start, end in trace_reduce.gaps(events)),
               default=0) / 1e6
