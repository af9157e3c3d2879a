"""The one place that configures JAX for this program.

Every entry point that reaches a device (``__main__.main``, bench.py,
chip_smoke.py, tools/tpu_doctor.py) calls :func:`setup_jax` before its
first device use.  The production Mosaic programs take minutes to
compile (docs/pow_pipeline.md), so a process without a persistent
compile cache re-pays them at every start.

Placement rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set in code; otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (git-ignored).  The path is part
of the cache key, so it is never a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache — two levels above this package
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_jax() -> str:
    """Place the persistent compile cache; returns the directory in
    effect.  Imports JAX but initializes no backend."""
    cache_dir = os.environ.get(CACHE_ENV)
    if cache_dir:
        return cache_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
