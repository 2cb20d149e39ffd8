"""Milliseconds a step of device time in the flash-attention kernels of
the layers that see a window of keys: the Mosaic calls named
``hvd_flash_fwd``, ``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv`` whose
``op_name`` also holds the program's scope ``hvd.attn.window``
(``models/laguna.py`` plants it around the attention call of a sliding
layer), forward, recomputed forward and backward together. ``None`` from
a program that plants no such scope."""

from harness import scope_time, scopes
from layer_metrics import attn_flash_ms

SCOPE = "hvd.attn.window"


def kernels_under(run, scope, kernels=attn_flash_ms.KERNELS):
    """Names of the traced Mosaic calls of ``kernels`` that were traced
    under ``scope``; ``None`` without a device trace or where the program
    plants no such scope."""
    trace, text = run.get("trace"), run["compiled_text"]
    if trace is None or not trace.devices:
        return None
    under = scope_time.names_under(text, (scope,))
    if not under:
        return None
    return {name for kernel in kernels
            for name in scopes.kernel_names(trace, text, kernel)} & under


def ms_a_step(run, scope):
    """Milliseconds a step in the kernels traced under ``scope`` (a
    device runs one kernel at a time: the union of their intervals is
    their sum)."""
    keep = kernels_under(run, scope)
    return scope_time.union_ms_a_step(run, keep) if keep else None


def read(run):
    return ms_a_step(run, SCOPE)
