"""JAX's persistent compilation cache, placed once per process.

A cold process recompiles every cycle program (tens of seconds each at
the Nature geometry). The command-line entry points, ``chip_smoke.py``
and ``benchmarks/run.py`` call :func:`enable` before their first
compile so that a second process on the same checkout finds them
again:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  nothing is set here;
* otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed path
  derived from this file's location (the path is part of the cache key,
  so a directory that moves never hits). It is listed in ``.gitignore``.

Either way the cache key includes the programs' metadata: op_name (where
``jax.named_scope`` lands) and source locations. JAX leaves it out by
default, so a program that differs from a cached one only in its named
scopes would load an executable without them, and a profile of it could
not attribute its ops (``core/concurrent.py`` ``CYCLE_SCOPES``).

``main()`` functions called in-process (the tests do) never enable it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
