"""The busiest held expert's load over the mean load, the worst sparse
block's, at the last checked step (``moe_load``, which the program counts
and returns with its state): ``moe_load_max_over_mean``'s number under a
name of this cell's own. The spread is the router's and the seeded bias's
together, over 8 held experts of 256 at 512 rows each when even: an
uneven spread costs whole tiles of the grouped products. 1 is an even
spread."""

from layer_metrics import moe_load_max_over_mean


def read(run):
    return moe_load_max_over_mean.read(run)
