"""JAX's persistent compilation cache, placed by the entry points.

Only entry points call :func:`use_persistent_cache` (``chip_smoke.py``,
``benchmarks/run.py``); importing the library never touches the cache.
The cache directory is part of what makes an entry hit, so it is fixed:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``
(git-ignored), never a temporary or per-process name.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_persistent_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every compiled program is kept, however fast it compiled: a cold
    process pays each Pallas kernel's compile once per cache, not once
    per run."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
