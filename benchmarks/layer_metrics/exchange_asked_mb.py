"""Megabytes (1e6 bytes) a step's gradient exchange puts on the wire, as
the program itself recorded them while it traced the step:
``bytes_wire`` of the newest ``hvd.profiler.exchanges()`` record stamped
before the window began. What was asked for; ``collective_payload_mb``
is what XLA made of it. Nothing to read from a program that keeps no
such record."""

import json

from harness import exchange


def read(run):
    record = exchange.record_of_the_step(run)
    if record is None:
        return None
    print("[exchange] " + json.dumps(record), flush=True)
    return record["bytes_wire"] / 1e6
