"""Model FLOP/s utilization over device-busy time, in percent: the
FLOPs a step needs by the builder's own count from shapes (forward and
backward, recomputation not counted), over busy seconds a step times the
chips' bf16 peak."""

from harness import device, trace_reduce


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.devices:
        return None
    busy_s = trace_reduce.mean_busy_ns(trace) / run["steps"] / 1e9
    peak = device.peaks(run["stamp"]["kind"])["bf16_flops_per_s"]
    return 100.0 * run["bench"].flops_per_step / (
        busy_s * peak * run["chips"])
