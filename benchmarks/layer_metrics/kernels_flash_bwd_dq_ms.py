"""Milliseconds a step in the flash-attention backward dq kernel: the
Mosaic calls traced under the ``pallas_call`` name
``hvd_flash_bwd_dq``."""

from harness import scopes


def read(run):
    return scopes.kernel_ms_a_step(run, "hvd_flash_bwd_dq")
