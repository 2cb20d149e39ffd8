"""The busiest held expert's load over the mean load, the worst layer's:
from the assignments each held expert received in the last checked step,
which the program counts and returns with its state (``moe_load``). 1 is
an even spread; the grouped products run tile by tile over every group,
so an uneven one costs tiles, not dropped tokens."""


def read(run):
    load = run.get("moe_load")
    if not load:
        return None
    return max(max(layer) * len(layer) / sum(layer) for layer in load
               if sum(layer))
