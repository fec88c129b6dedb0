"""Where JAX keeps compiled programs between runs.

One rule for every entry point (``chip_smoke.py``, ``bench.py``, the
``benchmarks/`` scripts): the operator places the cache from outside with
``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself; only when it is unset
does the program pick a directory, and then always the same one — the path
is part of the cache key's environment, so a name that moves (temp dir, pid,
timestamp) never hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def ensure_compile_cache() -> str:
    """Make sure a persistent compile cache is configured; return its path.

    Call before the first compile. With ``JAX_COMPILATION_CACHE_DIR`` set
    this touches nothing; otherwise it points JAX at ``<checkout>/.jax_cache``
    (gitignored).
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
