"""Where the persistent XLA compilation cache lives.

One rule for every entry point (chip_smoke.py, bench.py,
tools/serving_replica.py): where JAX_COMPILATION_CACHE_DIR is set, jax
reads it itself and no directory is set in code; where it is not, the
cache is `<checkout>/.jax_cache`. The path is part of the cache key, so
it is never a temp name, a pid or the time. The flash-tile cache
(ops/pallas/autotune.py) sits in the same directory.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory in effect, without touching jax."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point jax at the cache directory (entry points call this once,
    before the first compile). Returns the directory in effect."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
