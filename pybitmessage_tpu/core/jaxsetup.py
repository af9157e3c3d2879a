"""The one place that configures JAX for this program.

Every entry point that reaches a device (``__main__.main``, bench.py,
chip_smoke.py, tools/tpu_doctor.py) calls :func:`setup_jax` before its
first device use.  A start pays for a device program in three parts:
the trace of its Python, the lowering of the result, and the backend's
compile.  JAX's persistent compile cache, placed here, keeps the
third (seconds for a SHA-512 search kernel since PR 26, half a minute
and more for the secp256k1 and ``pow_verify`` programs); its key is
the lowered module, so by itself it saves nothing of the first two.
For the search kernels those are 4-8 s a shape, and
``core/programcache.py`` keeps them too: the lowered program is
written under ``<cache dir>/programs/`` by a machine's first start and
loaded by every later one.  A warm start then pays, for a kernel, a
parse of the stored module and a cache read.

Placement rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set in code; otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (git-ignored).  The path is part
of the cache key, so it is never a temp name, a pid or a time.
:func:`cache_dir` is that rule, for the persisted programs too.

It is also where the program first touches the backend, so that the
wall of that initialisation is the program's own number
(``jax_backend_init_seconds``), and where JAX's compile events are
wired to the registry (``jax_compile_*``, docs/observability.md).
"""

from __future__ import annotations

import logging
import os
import time
from pathlib import Path

logger = logging.getLogger("pybitmessage_tpu.core")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache — two levels above this package
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_backend_timed = False


def cache_dir() -> str:
    """The directory of the placement rule above: the compile cache,
    and under it the persisted programs (``core/programcache.py``)."""
    return os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)


def setup_jax() -> str:
    """Place the persistent compile cache, listen to JAX's compile
    events and initialise the backend; returns the cache directory in
    effect.  Safe to call again: the listener is installed and the
    backend timed once a process.  A backend that fails to initialise
    is logged and left to the caller's first device use, which meets
    the same error where it is handled."""
    import jax

    from ..observability.devicetelemetry import install_compile_listener
    install_compile_listener()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    _time_backend_init(jax)
    return cache_dir()


def _time_backend_init(jax) -> None:
    global _backend_timed
    if _backend_timed:
        return
    _backend_timed = True
    from ..observability.devicetelemetry import BACKEND_INIT_SECONDS
    t0 = time.monotonic()
    try:
        jax.devices()
    except Exception:
        logger.exception("JAX backend failed to initialise")
        return
    BACKEND_INIT_SECONDS.set(time.monotonic() - t0)
