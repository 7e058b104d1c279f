"""JAX's persistent compilation cache for the command-line entry points.

Every CLI ``main()`` (and ``chip_smoke.py``) calls
:func:`enable_compile_cache` first, so a second run of the same program
loads its executables instead of compiling them again.  Nothing here
runs at import: tests and library users keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore); a fixed
#: path, because the directory is part of every cache key
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, names the directory and
    JAX already reads it; otherwise the cache lives at
    :data:`DEFAULT_DIR`.  Returns the directory in use.
    ``JAX_ENABLE_COMPILATION_CACHE=false`` still turns the cache off.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
