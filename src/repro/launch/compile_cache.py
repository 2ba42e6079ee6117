"""JAX's persistent compilation cache for the command-line entry points.

Compiling the serving forward at the paper's widths takes seconds per bucket
on a TPU; the cache keeps those executables across processes.  Entry points
(``chip_smoke.py``, ``launch/serve_snn.py``, ``launch/socket_serve.py``)
call :func:`enable_compile_cache` before their first compile.  Importing
``repro`` never turns the cache on, so tests and library users get JAX's
own default.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    else is set; otherwise the cache lives at ``<checkout>/.jax_cache``, a
    fixed path, so every run from this checkout finds it again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
