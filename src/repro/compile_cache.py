"""JAX's persistent compilation cache, in one fixed place.

``enable_compile_cache()`` is called by the entry points that run on a
chip (``chip_smoke.py``, ``benchmarks/run.py``, the examples) before their
first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that directory
  and nothing else is set here;
* otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed path
  (the path is part of the cache key, so a directory that moved would
  never hit), listed in ``.gitignore``.

``LIBTPU_INIT_ARGS`` is never read or written here.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory.  Every executable is
    cached, however fast it compiled: a seal's CubeGraph build compiles
    dozens of small programs per segment, which together dominate a cold
    load on the chip."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
