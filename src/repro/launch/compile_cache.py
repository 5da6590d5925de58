"""JAX's persistent compilation cache, set in one place.

Called from the ``main()`` of ``launch/train.py``, ``launch/serve.py`` and
``benchmarks/run.py`` and from ``chip_smoke.py``, never at import, before
the first compile (JAX decides once per process whether the cache is on).
The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
    set here;
  * unset: the cache lives at one fixed path inside the checkout,
    ``<repo>/.jax_cache`` (git-ignored). A fixed path matters: the cache
    directory is part of what a later run must find again.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
