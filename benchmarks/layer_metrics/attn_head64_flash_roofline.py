"""Share of their roofline the flash-attention kernels reach at head
width 64 on the streamed path, in percent: the least time the chip could
take for the causal pairs' work, over the time the trace gives the
kernels under ``hvd.attn.full`` (``attn_head64_flash_ms``).

Work, from shapes, by the configuration's builder
(``head64_flash_work``): every pair ``j <= i`` at 32 query heads over 8
of width 64, FLOPs and bytes as
``attn_flash_roofline.flash_band_work`` counts a layer's; a forward call
the compiled step makes twice (the block recomputed in the backward pass)
is counted twice: the share is the kernels', not the model's. The MXU
contracts over 64 where it could over 128, and a tile's lanes are half
empty: both show here as a low share. The least time is the larger of
FLOPs over the bf16 peak and bytes over the HBM peak; the printed line
says which bounds."""

from harness import device, manifest
from layer_metrics import attn_flash_ms, attn_full_ms, attn_window_ms


def read(run):
    ms = attn_full_ms.read(run)
    if not ms:
        return None
    config, traffic = run["cell"].config, run["cell"].traffic
    builder = manifest.load_module("builders", config["builder"])
    layers = sum(kind == builder.FULL for kind, _ in builder.layers(config))
    forward_calls = len(attn_window_ms.kernels_under(
        run, attn_full_ms.SCOPE, attn_flash_ms.KERNELS[:1])) / layers
    flops, nbytes = builder.head64_flash_work(
        config, traffic["per_chip_batch"], traffic["sequence_length"],
        forward_calls)
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[attn_head64_flash_roofline] {forward_calls:g} forward calls a "
          f"layer; bound by {'flops' if by_flops >= by_bytes else 'bytes'}: "
          f"least {max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
