"""Persistent compilation cache for the entry points.

Compiling the full-width serving step takes a large share of a cold run, so
every entry point (chip_smoke.py and the sample/serve/train/tune launchers)
calls `enable_compile_cache()` before it compiles anything. Tests do not:
they keep JAX's default of no persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, since the directory is part of what
# makes a cached entry found again (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory and return it. Where
    JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and nothing is set
    here; otherwise the cache goes to `DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
