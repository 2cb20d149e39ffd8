"""Milliseconds a step of device time in the flash-attention kernels of a
looped decoder: the Mosaic calls named ``hvd_flash_fwd``,
``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv`` whose ``op_name`` also
holds the program's scope ``hvd.attn.full`` (``models/ouro.py`` plants it
around the attention call: 16 heads of width 128, no grouping, every
earlier key, sequence 8192 on the streamed path), forward, recomputed
forward and backward of every application of a block together (layers x
passes of them): what ``attn_full_ms`` reads, under a name of this cell's
own. ``None`` from a program that plants no such scope."""

from layer_metrics import attn_full_ms


def read(run):
    return attn_full_ms.read(run)
