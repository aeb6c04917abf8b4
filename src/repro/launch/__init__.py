"""Launch layer: production mesh construction, input stand-ins, step
functions, the multi-pod dry-run driver and the train/serve CLIs."""

from __future__ import annotations

import os
from pathlib import Path


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed directory, so a
    compile made by one run is found by the next; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has taken it already and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``:
    the path is part of the cache key, so it is never built from a temporary
    name, a pid or the time.  Entry points call this from ``main()``, never
    at import."""

    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    # this file is <checkout>/src/repro/launch/__init__.py
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
