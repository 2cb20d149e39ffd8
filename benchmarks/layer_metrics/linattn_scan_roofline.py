"""Share of its roofline the gated delta rule's chunked scan reaches, in
percent: the least time the chip could take for the scan's work of one
step, over the time the trace gives the operations under its scope.

Work, from shapes, by the configuration's builder
(``scan_work_per_step``): every linear layer, every pass the step makes
(forward, the recomputed forward, the backward pass at twice the
forward): a chunk's ``q k^T`` and ``k k^T``, its triangular solve, the two
chunk x chunk by chunk x d_v products and the three d_k x d_v products a
token against the state, 2 FLOPs a multiply-add; bytes: q, k, v, o in
bf16 and g, beta in float32 once a pass and one float32 state a chunk.
The least time is the larger of FLOPs over the bf16 peak and bytes over
the HBM peak; the printed line says which bounds."""

from harness import device, manifest
from layer_metrics import linattn_scan_ms


def read(run):
    ms = linattn_scan_ms.read(run)
    if not ms:
        return None
    config, traffic = run["cell"].config, run["cell"].traffic
    builder = manifest.load_module("builders", config["builder"])
    flops, nbytes = builder.scan_work_per_step(
        config, traffic["per_chip_batch"], traffic["sequence_length"])
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[linattn_scan_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: least "
          f"{max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
