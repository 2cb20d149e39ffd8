"""Programs the persistent compile cache did not hold before the window:
jax's ``cache_misses`` events as the program's ``compile_events()`` kept
them. 0 on a warm run."""

from harness import program_log


def read(run):
    return program_log.compile_count(
        run, "/jax/compilation_cache/cache_misses")
