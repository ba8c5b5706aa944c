"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it.  Otherwise the entry points put the cache in
one fixed directory of the checkout.  The directory is part of the
cache's key, so it is never a temporary name, a pid or a time.

Called by the entry points (``chip_smoke.py``, the train and serve
launchers), never at import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (git-ignored)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
