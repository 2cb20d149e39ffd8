"""Share of the traced window in which no operation runs, in percent, on
the device that idles most. The window is the host's: from the drained
device before the first dispatch to the arrival of the last loss."""

from harness import trace_reduce


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.devices:
        return None
    window_ns = (run["window"]["end"] - run["window"]["start"]) * 1e9
    least = min(trace_reduce.busy_ns(e) for e in trace.devices.values())
    return 100.0 * (1.0 - least / window_ns)
