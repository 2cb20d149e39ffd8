"""Persistent XLA compile cache for the entry scripts that run on the chip.

Every sealed chip call starts with no compiled code, and the ResNet-50
step, the LM step, prefill and decode each take tens of seconds to
compile. :func:`enable` is called by the scripts that run there
(``chip_smoke.py``, ``benchmarks/run.py`` and the benchmark's tools) —
never by ``hvd.init()`` and never by the tests.

The rule (one, so the cache can be placed from outside):

* ``JAX_COMPILATION_CACHE_DIR`` set — leave the directory alone. jax
  reads the variable itself; no code sets another directory.
* unset — point jax at ``<checkout>/.jax_cache`` (git-ignored). The path
  is built from the checkout's location alone: a directory that moves
  between runs (``tempfile``, a pid, a timestamp) would never hit. The
  minimum-compile-time and minimum-size thresholds drop to zero so every
  program of a run is kept and a second run compiles nothing.

Either way the cache's key includes the program's location metadata
(``jax_compilation_cache_include_metadata_in_key``). jax leaves it out by
default, and a program that differs from a cached one only in a
``named_scope`` then gets the cached executable back *with the cached
names*: a trace read by scope (``common/profiler.py``) would show another
commit's. The price: two commits that differ only in the line numbers of
traced code no longer share an entry; within one commit every run after
the first stays warm.
"""

from __future__ import annotations

import os

from ..common.config import env_str

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_dir() -> str:
    """The fixed in-checkout cache directory used when ``ENV_VAR`` is
    unset."""
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on (see module docstring) and
    return the directory in use. Call before the first compilation."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = env_str(ENV_VAR)
    if placed:
        return placed
    path = default_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def entry_count(path: str) -> int:
    """Number of cached executables under ``path`` (0 when it does not
    exist yet) — what the smoke prints to show a warm run added none."""
    try:
        return sum(1 for name in os.listdir(path)
                   if not name.endswith("-atime"))
    except OSError:
        return 0
