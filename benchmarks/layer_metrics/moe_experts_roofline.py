"""Share of their roofline the experts' grouped products reach, in
percent: the least time the chip could take for the rows that landed on
the held experts, over the time the trace gives the expert layer's own
operations.

Work, from the rows the program counted in its last checked step
(``moe_load``, every layer's held experts together): a row meets three
matrices of hidden x expert width, in three passes (forward, the
gradient of the rows, the gradient of the weights), 2 FLOPs a
multiply-add; recomputation is not counted. Bytes: each pass reads and
writes a row's three products once in bf16 (hidden + width in, width or
hidden out) and reads each held expert's three matrices once. The least
time is the larger of FLOPs over the bf16 peak and bytes over the HBM
peak; the printed line says which bounds."""

from harness import device
from layer_metrics import moe_experts_ms

PASSES, MATRICES = 3, 3


def experts_work(rows, experts, hidden, width):
    """``(flops, bytes)`` of one step's grouped products over ``rows``
    assignment rows and ``experts`` held experts (all layers together)."""
    flops = PASSES * MATRICES * 2 * hidden * width * rows
    per_pass = MATRICES * (rows * (hidden + width) + experts * hidden * width)
    return flops, PASSES * per_pass * 2


def read(run):
    ms = moe_experts_ms.read(run)
    load = run.get("moe_load")
    if not ms or not load:
        return None
    config = run["cell"].config
    flops, nbytes = experts_work(
        sum(map(sum, load)), sum(map(len, load)), config["hidden_size"],
        config["moe_ffn_hidden_size"])
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[moe_experts_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: least "
          f"{max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
