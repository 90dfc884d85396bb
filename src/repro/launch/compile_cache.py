"""Where JAX keeps its persistent compilation cache.

The cache directory is part of every entry's key, so it must not move
between runs: a path built from a temporary name, a process id or the time
never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins (JAX reads the
variable itself); otherwise the cache lives at the fixed ``.jax_cache``
directory of the checkout, which ``.gitignore`` lists.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
