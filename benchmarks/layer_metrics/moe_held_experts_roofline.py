"""Share of their roofline the held experts' grouped products reach, in
percent, in a configuration that names its widths as ``laguna-xs.2``
does: the least time the chip could take for the rows that landed on the
held experts in the last checked step (``moe_load``, every sparse layer's
together), by ``moe_experts_roofline.experts_work`` at the
configuration's ``hidden_size`` and ``moe_intermediate_size``, over the
time the trace gives the expert layer's own operations
(``moe_experts_ms``). The least time is the larger of FLOPs over the bf16
peak and bytes over the HBM peak; the printed line says which bounds."""

from harness import device
from layer_metrics import moe_experts_ms, moe_experts_roofline


def read(run):
    ms = moe_experts_ms.read(run)
    load = run.get("moe_load")
    config = run["cell"].config
    if not ms or not load or "moe_intermediate_size" not in config:
        return None
    flops, nbytes = moe_experts_roofline.experts_work(
        sum(map(sum, load)), sum(map(len, load)), config["hidden_size"],
        config["moe_intermediate_size"])
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[moe_held_experts_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: least "
          f"{max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
