"""Share of their roofline the three flash-attention kernels reach, in
percent: the least time the chip could take for their work, over the
time the trace gives them.

Work a layer, from shapes alone (B sequences, H heads, S positions, head
width D, no causal mask): forward 4*B*H*S^2*D FLOPs (scores and
context), backward-dq 6* (scores again, dP, dQ), backward-dkdv 8*
(scores again, dP, dV, dK). Bytes: each call's operands and results
once, in bf16, with the f32 row statistics (log-sum-exp and delta). The
least time is the larger of FLOPs over the bf16 peak and bytes over the
HBM peak; the printed line says which bounds. A head width of 64 fills
half of the MXU's 128 columns; the peak is not lowered for it.
"""

from harness import device
from layer_metrics import kernels_mosaic_ms


def flash_work(batch, heads, seq, width, layers):
    """``(flops, bytes)`` of one step's forward, dq and dkdv calls."""
    tile = batch * heads * seq * width * 2          # one bf16 q/k/v/o/do
    stat = batch * heads * seq * 4                  # one f32 row statistic
    flops = (4 + 6 + 8) * batch * heads * seq * seq * width
    forward = 4 * tile + stat                       # q k v -> o, lse
    backward_dq = 5 * tile + 2 * stat               # q k v do lse delta -> dq
    backward_dkdv = 6 * tile + 2 * stat             # ... -> dk, dv
    return (layers * flops,
            layers * (forward + backward_dq + backward_dkdv))


def read(run):
    ns = kernels_mosaic_ms.mosaic_ns(run)
    if not ns:
        return None
    model = run["cell"].config["model"]
    traffic = run["cell"].traffic
    heads = model["num_attention_heads"]
    flops, nbytes = flash_work(
        traffic["per_chip_batch"], heads, traffic["sequence_length"],
        model["hidden_size"] // heads, model["num_hidden_layers"])
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[flash_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: least "
          f"{max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ns / run["steps"] / 1e9)
