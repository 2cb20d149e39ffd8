"""Milliseconds a step of device time in the flash-attention kernels of
multi-head latent attention: the Mosaic calls named ``hvd_flash_fwd``,
``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv`` whose ``op_name`` also
holds the program's scope ``hvd.attn.latent`` (``models/joyai.py``
``LatentAttention`` plants it around the attention call on the expanded
q, k and v: 32 heads, q and k 192 wide, v 128, sequence 8192, on the
streamed path with blocks ``(1, block, 192)`` and ``(1, block, 128)``),
every attending block's together, the multi-token-prediction module's
among them, forward, recomputed forward and backward. ``None`` from a
program that plants no such scope."""

from layer_metrics import attn_window_ms

SCOPE = "hvd.attn.latent"


def read(run):
    return attn_window_ms.ms_a_step(run, SCOPE)
