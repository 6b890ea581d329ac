"""Persistent XLA compilation cache for the serving entry points.

Compiling the engine's programs at published widths takes seconds each,
and a fresh process would otherwise compile every decode window and
prefill bucket again.  ``enable_compile_cache`` is called at the top of a
launcher's ``main()`` and never on import, so tests never turn it on.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the repository's own cache directory (``src/repro/launch`` -> root).
#: Fixed, because the path is part of the cache key: a directory that
#: moves between runs never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` at
    the repository root.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
