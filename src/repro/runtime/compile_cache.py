"""Where JAX keeps its persistent compilation cache for this checkout.

Called by the entry points (``chip_smoke.py``, ``benchmarks/run.py``),
never at import time: a library that sets a cache directory on import
would override the caller's choice.
"""

from __future__ import annotations

import os

import jax

#: Directory under the checkout used when the environment names none.
#: Fixed, not derived from a temporary name, pid or time: the path is part
#: of the cache key, so a directory that moves never hits.
CACHE_SUBDIR = ".jax_cache"


def configure_compile_cache(root: str) -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself; otherwise the cache goes to ``<root>/.jax_cache``.  The
    minimum compile time for an entry drops to zero: the simulator's tick
    programs compile in well under JAX's default one-second threshold and
    would never be written."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), CACHE_SUBDIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
