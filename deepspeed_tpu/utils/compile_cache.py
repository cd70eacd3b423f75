"""Where the persistent XLA compilation cache lives.

The directory is part of the cache key's stability: a path that moves between
runs (temp name, pid, timestamp) never hits. So the location is decided by
exactly one rule, for every entry point (``chip_smoke.py``,
``benchmark/run.py``, ``bin/ds_aot``):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no directory is set
  here.
- unset: ``<checkout>/.jax_cache`` (git-ignored), exported through the
  environment so worker subprocesses inherit it.

Either way every program is cached, not only those over JAX's 1 s default: a
serving engine is a dozen sub-second programs, and on the chip they were most
of what a warm start still compiled (PERF.md, PR 21).
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get(_ENV)
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    os.environ[_ENV] = path
    # jax read its config defaults from the environment at import
    jax.config.update("jax_compilation_cache_dir", path)
    return path
