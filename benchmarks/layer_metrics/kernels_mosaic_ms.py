"""Milliseconds a step of device time in Mosaic custom calls (the Pallas
kernels), all of them together, on the average device. Nothing to read
where the compiled step holds no such call."""

from harness import hlo_text


def mosaic_ns(run):
    trace = run.get("trace")
    if (trace is None or not trace.devices
            or not hlo_text.mosaic_calls(run["compiled_text"])):
        return None
    sums = [sum(d for name, _, d in events if name in trace.kernels)
            for events in trace.devices.values()]
    return sum(sums) / len(sums)


def read(run):
    ns = mosaic_ns(run)
    return None if ns is None else ns / run["steps"] / 1e6
