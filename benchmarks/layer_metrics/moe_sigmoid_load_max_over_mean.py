"""The busiest held expert's load over the mean load, the worst sparse
layer's, at the last checked step (``moe_load``, which the program counts
and returns with its state): ``moe_load_max_over_mean``'s number under a
name of this cell's own. Here the spread is the router's and the seeded
bias's together: the bias enters the choice, so an expert whose bias is
high is chosen more often whatever its score. 1 is an even spread."""

from layer_metrics import moe_load_max_over_mean


def read(run):
    return moe_load_max_over_mean.read(run)
