"""Share of their roofline the flash-attention kernels reach on the
layers that see a window of keys, in percent: the least time the chip
could take for the band's work, over the time the trace gives those
layers' kernels (``attn_window_ms``).

Work, from shapes, by the configuration's builder
(``window_flash_work``): for every sliding layer the query-key pairs
inside the band ``i - window < j <= i`` at that layer's query heads over
the key/value heads, FLOPs and bytes as
``attn_flash_roofline.flash_band_work`` counts a layer's; a forward call
the compiled step makes twice (the block recomputed in the backward pass)
is counted twice: the share is the kernels', not the model's. The tiles a
kernel's static grid fetches outside the band are no part of the work: at
a window below a key block that is most of what the kernel moves, and it
shows here as a low share. The least time is the larger of FLOPs over the
bf16 peak and bytes over the HBM peak; the printed line says which."""

from harness import device, manifest
from layer_metrics import attn_flash_ms, attn_window_ms


def read(run):
    ms = attn_window_ms.read(run)
    if not ms:
        return None
    config, traffic = run["cell"].config, run["cell"].traffic
    builder = manifest.load_module("builders", config["builder"])
    sliding = sum(kind == builder.SLIDING
                  for kind, _, _ in builder.layers(config))
    forward_calls = len(attn_window_ms.kernels_under(
        run, attn_window_ms.SCOPE, attn_flash_ms.KERNELS[:1])) / sliding
    flops, nbytes = builder.window_flash_work(
        config, traffic["per_chip_batch"], traffic["sequence_length"],
        forward_calls)
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[attn_window_roofline] {forward_calls:g} forward calls a layer; "
          f"bound by {'flops' if by_flops >= by_bytes else 'bytes'}: least "
          f"{max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
