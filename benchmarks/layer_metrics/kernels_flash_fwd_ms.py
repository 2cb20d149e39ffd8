"""Milliseconds a step in the flash-attention forward kernel: the Mosaic
calls traced under the ``pallas_call`` name ``hvd_flash_fwd``."""

from harness import scopes


def read(run):
    return scopes.kernel_ms_a_step(run, "hvd_flash_fwd")
