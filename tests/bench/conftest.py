"""The CPU device for the tests that drive a cell through ``bench/run.py``'s
``measure``."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@pytest.fixture(scope="module")
def cpu(tmp_path_factory):
    """The CPU device, with the run's caches under a temporary directory;
    JAX's cache settings are put back for the tests that follow."""
    import jax

    from bench import run

    saved_cache = run.CACHE
    saved = {k: jax.config.values[k] for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs", "jax_compilation_cache_max_size")}
    run.CACHE = tmp_path_factory.mktemp("bench_cache")
    yield jax.devices("cpu")
    run.CACHE = saved_cache
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
