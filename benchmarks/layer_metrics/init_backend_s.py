"""Seconds ``hvd.init()`` waits for the devices: the program's span
``init.backend`` (``common/basics.py`` ``_acquire_backend``: libtpu
coming up), apart from ``jax.distributed``, topology detection and the
controller choice, which are its siblings."""

from harness import program_log


def read(run):
    return program_log.span_seconds(run, ("init.backend",))
