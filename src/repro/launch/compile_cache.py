"""JAX's persistent compilation cache for the program's entry points.

A cold run compiles every step of the served path; at ee-llm-7b widths
that is minutes.  Entry points call ``enable_compile_cache()`` once,
before their first compile, so a later process on the same machine finds
those programs again."""
from __future__ import annotations

import os
import pathlib

import jax

# fixed, inside the checkout and listed in .gitignore: a cache is only
# found again under the same path, so never a temp-, pid- or time-derived
# directory
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no other
    directory is set; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
