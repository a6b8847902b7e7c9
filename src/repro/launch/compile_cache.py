"""Where JAX's persistent compilation cache lives, for entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``use_compile_cache(checkout)`` once, before they compile anything.
Importing the library never does: a library must not pick a directory on
its caller's disk.

The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  else is configured here.
* unset: the cache goes to ``<checkout>/.jax_cache``. The path is part of
  the cache key, so it is fixed — never a temp name, pid or time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DIRNAME = ".jax_cache"


def use_compile_cache(checkout: str) -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    path = os.path.join(os.path.abspath(checkout), DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
