"""Milliseconds a step of device time in the optimizer's update:
operations traced under the program's ``hvd.update`` scope
(``DistributedOptimizer``) and under no other phase's. XLA puts most of
the update into the epilogue of the gradient's last operation (one chip)
or of the exchange's averaging (several): those fusions read ``mixed``,
and this reads what is left alone, down to 0. Nothing to read from a
program that plants no such scope."""

from harness import scopes


def read(run):
    return scopes.phase_ms_a_step(run, ("update",))
