"""What the program itself recorded while it set up: its span log and its
compile records (``horovod_tpu.profiler.spans()`` and
``compile_events()``), read in the run's own process after the window.

Both are lists of tuples on ``time.perf_counter_ns``, the clock of the
window's ``start``. A program that keeps no such log (an older commit)
gives ``None``, and so does every reader built on it. A test hands a
reader its records under ``run["program_spans"]`` and
``run["program_compile_events"]`` instead.
"""

from . import trace_reduce


def _from_program(name):
    try:
        from horovod_tpu.common import profiler
    except ImportError:
        return None
    read = getattr(profiler, name, None)
    return None if read is None else [tuple(r) for r in read()]


def spans(run):
    """``[(name, start_ns, end_ns, parent)]`` or ``None``."""
    if "program_spans" in run:
        return run["program_spans"]
    return _from_program("spans")


def compile_events(run):
    """``[(event, fun_name, seconds or None, at_ns)]`` or ``None``."""
    if "program_compile_events" in run:
        return run["program_compile_events"]
    return _from_program("compile_events")


def window_start_ns(run):
    return run["window"]["start"] * 1e9


def span_seconds(run, names):
    """Seconds inside the spans called one of ``names`` that ended before
    the window began (their union: a span nested in another of the same
    list counts once); ``None`` where the program kept no such span."""
    log = spans(run)
    if log is None:
        return None
    limit = window_start_ns(run)
    found = [(start, end) for name, start, end, _ in log
             if name in names and end <= limit]
    if not found:
        return None
    return trace_reduce.total(trace_reduce.union(found)) / 1e9


def compile_seconds(run, events):
    """Seconds inside the compile records of the ``events`` given that
    arrived before the window began. A record is the interval that ends
    at its stamp, and the union of the intervals is taken: jax times an
    inner ``jit``'s tracing inside the outer one's, and a program that
    kept both would otherwise count it twice. ``None`` where the program
    kept no compile records at all (0.0 where it kept some and none of
    these)."""
    log = compile_events(run)
    if log is None:
        return None
    limit = window_start_ns(run)
    found = [(at - seconds * 1e9, at) for event, _, seconds, at in log
             if event in events and seconds is not None and at <= limit]
    return trace_reduce.total(trace_reduce.union(found)) / 1e9


def compile_count(run, event):
    """How many ``event`` records arrived before the window began."""
    log = compile_events(run)
    if log is None:
        return None
    limit = window_start_ns(run)
    return sum(1 for e, _, _, at in log if e == event and at <= limit)
