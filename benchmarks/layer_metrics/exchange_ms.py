"""Milliseconds a step of device time in the gradient exchange: the
operations traced under the program's ``hvd.exchange`` /
``hvd.allreduce.<prefix>.<i>`` scopes and under no other phase's (the
collectives with compression's casts and the averaging XLA left beside
them), per device the union of their intervals, on the worst device.
Nothing to read from a program that plants no such scope; 0.0 where XLA
fused all of it into other phases' operations. An asynchronous
collective counts while its ``-start`` and ``-done`` operations run, not
in between: what is in flight under other work is not the device's
time."""

from harness import exchange


def read(run):
    return exchange.worst_device_ms_a_step(run, exchange.busy_ns)
