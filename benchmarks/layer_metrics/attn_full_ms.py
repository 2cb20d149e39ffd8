"""Milliseconds a step of device time in the flash-attention kernels of
the layers that see every earlier key: the Mosaic calls named
``hvd_flash_fwd``, ``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv`` whose
``op_name`` also holds the program's scope ``hvd.attn.full``
(``models/laguna.py`` plants it around the attention call of a full
layer), forward, recomputed forward and backward together. ``None`` from
a program that plants no such scope."""

from layer_metrics import attn_window_ms

SCOPE = "hvd.attn.full"


def read(run):
    return attn_window_ms.ms_a_step(run, SCOPE)
