"""Where JAX keeps its persistent compilation cache.

``use_compile_cache()`` is called by program entry points (``chip_smoke.py``,
``benchmarks/serve_throughput.py``), never on import of a library module.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself; no other
  directory is set in code.
* unset: the cache lives at ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``). The path is fixed — never derived from a temporary name,
  a process id or the time — because the directory is part of the cache key:
  a path that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
