"""Milliseconds a step of the gradient exchange's device time (the
operations ``exchange_ms`` counts) during which no operation of another
phase runs on that device, on the worst device: what the step waits for.
Chosen by the program's scopes; ``collective_exposed_ms`` chooses by
opcode."""

from harness import exchange, trace_reduce


def read(run):
    return exchange.worst_device_ms_a_step(run, trace_reduce.exposed_ns)
