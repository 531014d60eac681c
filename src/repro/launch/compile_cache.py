"""JAX's persistent compilation cache for the entry points.

One rule, applied by `enable_compile_cache` (called when `launch.serve`
or `launch.train` runs as a program, and by `chip_smoke.py`, at start-up
— never at import, and not from the library entry points `main` / `run`,
so tests that call them share no compiled code through the disk):

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; nothing
    else is configured here;
  * otherwise — the fixed directory ``<checkout>/.jax_cache`` (listed in
    ``.gitignore``).  The path is part of the cache key, so it never
    depends on a temp name, a pid or the time: a later run of the same
    checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
