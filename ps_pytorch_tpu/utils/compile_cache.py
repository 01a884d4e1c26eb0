"""Persistent XLA compile cache — one rule for every entry point.

The first compile of a big train step takes tens of seconds on a TPU; the
disk cache lets every later process with the same program skip it. The
cache's path is part of its key, so the directory must not move between
runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself at import, and
  this module sets no directory in code — the operator's choice stands.
- unset: ``<checkout>/.jax_cache`` (git-ignored), next to the code that
  produced the programs. Never ``/tmp``, a pid or a timestamp.

Call first thing in ``main`` — jax decides once, at its first compile,
whether the process has a cache. The cache keys on the HLO hash: a sweep
whose candidates differ in a baked constant (cli/tune's lr grid) still
compiles each DISTINCT candidate once, and re-running it compiles nothing.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return env_dir or REPO_CACHE_DIR
