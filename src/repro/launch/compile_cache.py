"""Where JAX keeps compiled programs between processes.

Entry points (``launch/serve.py``'s ``main``, ``chip_smoke.py``) call
``use_persistent_compile_cache`` once at start-up; importing ``repro`` never
touches the cache.  The directory is part of the cache's key, so it is a
fixed path: ``$JAX_COMPILATION_CACHE_DIR`` when that is set, otherwise
``<repo>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

#: the fallback cache directory: ``.jax_cache`` at the repository root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
