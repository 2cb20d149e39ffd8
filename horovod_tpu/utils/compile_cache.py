"""Persistent XLA compile cache for the entry scripts that run on the chip.

Every sealed chip call starts with no compiled code, and the ResNet-50
step, the LM step, prefill and decode each take tens of seconds to
compile. :func:`enable` is called by the scripts that run there
(``chip_smoke.py``, ``bench.py``'s measurement children and, through
``runpy``, the example mains they drive) — never by ``hvd.init()`` and
never by the tests.

The rule (one, so the cache can be placed from outside):

* ``JAX_COMPILATION_CACHE_DIR`` set — do nothing. jax reads the variable
  itself; no code sets another directory.
* unset — point jax at ``<checkout>/.jax_cache`` (git-ignored). The path
  is built from the checkout's location alone: a directory that moves
  between runs (``tempfile``, a pid, a timestamp) would never hit. The
  minimum-compile-time and minimum-size thresholds drop to zero so every
  program of a run is kept and a second run compiles nothing.
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
    placed = env_str(ENV_VAR)
    if placed:
        return placed
    import jax

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
