"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache directory, so
the directory is fixed: either the one the environment names through
``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself), or one
place inside the checkout.  Entry points call ``use_compile_cache()`` once,
before their first compile; library code and tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# <checkout>/.jax_cache — listed in .gitignore
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the persistent-cache directory in use, setting the checkout's
    own only when ``JAX_COMPILATION_CACHE_DIR`` is unset."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
