"""Placement of JAX's persistent compilation cache for the entry points.

Called at the top of ``chip_smoke.py``, ``bench.py`` and
``examples/movie_view_ratings/run_on_tpu.py`` — never on library import,
so an application that embeds the library keeps its own cache policy.

The cache key includes the directory, so the path must be the same from
one run to the next: it is never built from a temp name, a pid or the
time.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def configure(checkout_dir: str) -> str:
    """Points JAX's persistent compilation cache at a fixed directory and
    returns it.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
    and nothing is set here. Otherwise the cache lives in
    ``<checkout_dir>/.jax_cache`` (listed in .gitignore).
    """
    import jax

    from_env = os.environ.get(CACHE_DIR_ENV)
    if from_env:
        return from_env
    path = os.path.join(os.path.abspath(checkout_dir), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
