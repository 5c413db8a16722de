"""Where JAX's persistent compilation cache lives for this checkout.

The path is part of what makes a cache hit possible: a directory named
after a temporary file, a pid or the time is never found again. So the
rule is fixed:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment wins, and nothing
  here names another directory (JAX reads the variable itself; an empty
  value turns the cache off);
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

The entry-size and compile-time floors are zeroed so that the small
executables of quick CPU runs cache too.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on. -> the directory in use
    ('' when the environment turned the cache off)."""
    if ENV_VAR in os.environ:
        path = os.environ[ENV_VAR]
    else:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    if path:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
