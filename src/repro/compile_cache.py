"""JAX's persistent compilation cache for the entry points.

Only entry points call `enable()` (``chip_smoke.py``,
``examples/train_gnn.py``, ``benchmarks/run.py``, ``launch/serve.py``);
importing the package sets nothing, so tests that compile for a
described chip never write into the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed path: the cache key includes it, so a directory that moves
# never hits
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory. Where
    JAX_COMPILATION_CACHE_DIR is set, JAX reads it and nothing is set
    here; otherwise the cache lives in ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
