"""Milliseconds a step of device time in the three flash-attention
kernels together (``hvd_flash_fwd``, ``hvd_flash_bwd_dq``,
``hvd_flash_bwd_dkv``), on either path of the shape rule, recomputed
forward calls included."""

from harness import scopes

KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


def read(run):
    found = [scopes.kernel_ms_a_step(run, k) for k in KERNELS]
    if all(ms is None for ms in found):
        return None
    return sum(ms or 0.0 for ms in found)
