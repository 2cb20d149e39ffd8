"""Milliseconds a step of device time in the flash-attention kernels at
head width 64 on the streamed path: the Mosaic calls named
``hvd_flash_fwd``, ``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv`` whose
``op_name`` also holds the program's scope ``hvd.attn.full``
(``models/lfm2.py`` plants it around the attention call: 32 query heads
over 8, sequence 8192, blocks ``(1, block, 64)``, half of a tile's 128
lanes), forward, recomputed forward and backward together: what
``attn_full_ms`` reads, under a name of this cell's own. ``None`` from a
program that plants no such scope."""

from layer_metrics import attn_full_ms


def read(run):
    return attn_full_ms.read(run)
