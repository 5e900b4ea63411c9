"""JAX's persistent compilation cache, placed from outside or in the checkout.

Scripts that drive the solver call `enable_compile_cache(root)` before
their first compile.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads
it itself and this sets nothing.  Otherwise the cache goes to `.jax_cache`
under `root` (the checkout): a fixed path, so a second run in the same
checkout finds what the first one wrote.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(root: str) -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax

    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
