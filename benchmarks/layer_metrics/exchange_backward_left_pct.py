"""Of a step's backward device time, the percentage that runs after that
step's first exchange operation has started, the mean over steps and
devices: what there is for the exchange to hide behind. 0 where one
combined all-reduce waits for the last gradient. How the steps are told
apart in a device's event list, and what counts as backward work:
``harness/exchange.py``."""

from harness import exchange


def read(run):
    return exchange.backward_left_pct(run)
