"""JAX's persistent compilation cache, set up once by each entry point.

Entry points (``chip_smoke.py``, ``launch/sidecar.py``, ``launch/serve.py``,
``benchmarks/run.py``) call :func:`setup_compile_cache` from ``main``;
importing a module never touches the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<repo root>/.jax_cache`` (git-ignored).  A fixed path: a run finds
#: what an earlier run on the same checkout compiled.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, the
    cache is left to it and no other directory is set here.  Otherwise
    the cache lives in :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    DEFAULT_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
