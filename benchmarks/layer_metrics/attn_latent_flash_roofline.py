"""Share of their roofline the flash-attention kernels reach at q and k
192 wide over v 128, in percent: the least time the chip could take for
the causal pairs' work, over the time the trace gives the kernels under
``hvd.attn.latent`` (``attn_latent_flash_ms``).

Work, from shapes, by the configuration's builder
(``latent_flash_work``): every pair ``j <= i`` of 32 heads in every
attending block; a pair costs a forward call 2 x 192 + 2 x 128 FLOPs,
dq 1024, dk/dv 1280; a forward call the compiled step makes twice (the
block recomputed in the backward pass) is counted twice: the share is the
kernels', not the model's. Bytes with q, k, dq and dk 192 wide and v, o,
do and dv 128, k at all 32 heads (the one rotary key a token is copied
into each before the call). 192 is a tile and a half of lanes: the MXU
contracts the scores over 256 and the q and k tiles lie in HBM 256 wide
by XLA's (8, 128) tiling, and both show here as a lower share. The least
time is the larger of FLOPs over the bf16 peak and bytes over the HBM
peak; the printed line says which bounds."""

from harness import device, manifest
from layer_metrics import attn_flash_ms, attn_latent_flash_ms, attn_window_ms


def read(run):
    ms = attn_latent_flash_ms.read(run)
    if not ms:
        return None
    config, traffic = run["cell"].config, run["cell"].traffic
    builder = manifest.load_module("builders", config["builder"])
    forward_calls = len(attn_window_ms.kernels_under(
        run, attn_latent_flash_ms.SCOPE, attn_flash_ms.KERNELS[:1])) \
        / builder.attention_blocks(config)
    flops, nbytes = builder.latent_flash_work(
        config, traffic["per_chip_batch"], traffic["sequence_length"],
        forward_calls)
    peaks = device.peaks(run["stamp"]["kind"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[attn_latent_flash_roofline] {forward_calls:g} forward calls a "
          f"block; bound by {'flops' if by_flops >= by_bytes else 'bytes'}: "
          f"least {max(by_flops, by_bytes) * 1e3:.4f} ms a step", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
