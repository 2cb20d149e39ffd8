"""Seconds to lower and compile (or load from the persistent cache) the
step program: Python tracing, lowering and XLA together. The number of
cache entries the run wrote is printed beside it on the ``[spans]`` line;
a run after a cell's first writes none."""


def read(run):
    return run["spans"].get("compile")
