"""Median host milliseconds for one call of the compiled step to return:
what the mesh, the placement and the jitted ``shard_map`` call cost the
host for each step, whatever the device is doing."""

import statistics


def read(run):
    calls = run["window"]["dispatch"]
    return statistics.median(calls) * 1e3 if calls else None
