"""Seconds to import the program's package (``import horovod_tpu``, jax
included): the program's own span ``import``, stamped on the first and
the last line of ``horovod_tpu/__init__.py``."""

from harness import program_log


def read(run):
    return program_log.span_seconds(run, ("import",))
