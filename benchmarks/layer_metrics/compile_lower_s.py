"""Seconds of Python tracing and lowering before the window: jax's
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` records
as the program's ``compile_events()`` kept them, every program of set-up
together (the step and the helpers around it). No cache saves this
part."""

from harness import program_log

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")


def read(run):
    return program_log.compile_seconds(run, EVENTS)
