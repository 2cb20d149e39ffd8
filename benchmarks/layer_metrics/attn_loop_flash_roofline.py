"""Share of their roofline the flash-attention kernels reach in a looped
decoder, in percent: the least time the chip could take for the causal
pairs' work, over the time the trace gives the kernels under
``hvd.attn.full`` (``attn_loop_flash_ms``).

Work, from shapes, by the configuration's builder (``loop_flash_work``):
every pair ``j <= i`` at 16 query heads over 16 of width 128 for every
application of a block (layers x passes), FLOPs and bytes as
``attn_flash_roofline.flash_band_work`` counts a layer's; a forward call
the compiled step makes twice (the application recomputed in the backward
pass) is counted twice: the share is the kernels', not the model's. The
least time is the larger of FLOPs over the bf16 peak and bytes over the
HBM peak; the printed line says which bounds."""

from harness import manifest
from layer_metrics import (attn_flash_ms, attn_full_ms, attn_window_ms,
                           loop_head_roofline)


def read(run):
    ms = attn_full_ms.read(run)
    if not ms:
        return None
    config, traffic = run["cell"].config, run["cell"].traffic
    builder = manifest.load_module("builders", config["builder"])
    forward_calls = len(attn_window_ms.kernels_under(
        run, attn_full_ms.SCOPE, attn_flash_ms.KERNELS[:1])) \
        / builder.block_applications(config)
    flops, nbytes = builder.loop_flash_work(
        config, traffic["per_chip_batch"], traffic["sequence_length"],
        forward_calls)
    return loop_head_roofline.share_of_least(
        run, "attn_loop_flash_roofline", ms, flops, nbytes,
        f"{forward_calls:g} forward calls an application of a block; ")
