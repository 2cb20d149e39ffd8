"""Seconds inside the backend before the window: jax's
``backend_compile_duration`` records as the program's
``compile_events()`` kept them, which time XLA's compilation or, on a
persistent-cache hit, the load of the cached executable; every program of
set-up together."""

from harness import program_log

EVENTS = ("/jax/core/compile/backend_compile_duration",)


def read(run):
    return program_log.compile_seconds(run, EVENTS)
