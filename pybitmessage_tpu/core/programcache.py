"""A kernel is traced and lowered once a machine, not once a process.

JAX's persistent compile cache (``core/jaxsetup.py``) is keyed by the
LOWERED module, so it saves a later process the backend compile and
nothing before it: every start still walks the kernel's Python (the
SHA-512 search kernels unroll 160 rounds, some 20,600 primitives) and
lowers the result to Mosaic, 4-8 s a kernel shape, to arrive at bytes
that are the same every time.  :func:`persisted_jit` keeps those bytes:
the first process on a machine that launches a shape on an accelerator
exports the lowered program (``jax.export``) and writes it under
``<cache dir>/programs/``; every later one deserialises the file and
calls ``jax.jit(exported.call)``, whose own trace and lowering are a
parse of the stored module (0.1-0.2 s).  Cold or warm, a launch runs
through the exported program, so the backend compiles ONE module a
shape and the device runs the same Mosaic bytes either way.

What decides, read from the call and the backend and never from a
setting: a call with ``interpret=True``, on the ``cpu`` backend, under
a trace (``jit``, ``shard_map``: the arguments are tracers) or with a
static argument passed by position goes straight to the jitted
function and writes nothing.

A file is named by :func:`program_key`: the entry's name, its static
arguments, the arguments' shapes and dtypes, the versions of jax and
jaxlib, the backend's platform, ``platform_version`` (libtpu's build)
and device kind, and a digest of the source files the program is
traced from.  Change any of them and the next start is a ``miss``.  A
file that does not deserialise, or was made for other avals or another
platform, is ``stale`` and replaced; a program that cannot be
exported or written is an ``error``, logged once a shape at WARNING:
the call then traces live as it always did, or runs through the
export that could not be saved.  An export runs under a frame with a
chunk of its own (:func:`_roomy_frame`), so how long its trace takes
does not depend on how deep its caller lies.
``program_cache_total{program,outcome}`` counts
each, ``program_cache_load_seconds{program}`` times the loads, and the
span ``program.load`` stands around the load or the export.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import logging
import os
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from ..observability import DEVICE_TELEMETRY, REGISTRY, trace
from .jaxsetup import cache_dir

logger = logging.getLogger("pybitmessage_tpu.core")

#: under the compile cache's directory
PROGRAMS_DIR = "programs"
SUFFIX = ".jaxexport"

PROGRAM_CACHE = REGISTRY.counter(
    "program_cache_total",
    "Device programs looked up in the persisted-program store at "
    "their first launch of a shape (hit: loaded; miss: exported and "
    "written; stale: unreadable or made for other avals, replaced; "
    "error: not exported or not written, traced live)",
    ("program", "outcome"))
LOAD_SECONDS = REGISTRY.histogram(
    "program_cache_load_seconds",
    "Seconds from a persisted program's file to a callable "
    "(read + deserialise), per hit", ("program",))


def program_key(name: str, static: dict, avals, environment,
                sources: str) -> str:
    """The file name's digest: every part that makes the lowered
    program what it is.  ``avals`` is a sequence of (shape, dtype),
    ``environment`` what :func:`environment` gives, ``sources``
    what :func:`source_digest` gives."""
    text = repr((name, sorted(static.items()),
                 [(tuple(shape), np.dtype(dtype).name)
                  for shape, dtype in avals],
                 tuple(environment), sources))
    return hashlib.sha256(text.encode()).hexdigest()[:40]


@functools.lru_cache(maxsize=None)
def source_digest(paths: tuple) -> str:
    """SHA-256 over the bytes of the files a program is traced from,
    read once a process."""
    digest = hashlib.sha256()
    for path in paths:
        digest.update(Path(path).read_bytes())
        digest.update(b"\0")
    return digest.hexdigest()


@functools.lru_cache(maxsize=None)
def environment() -> tuple:
    """What the lowered bytes depend on besides the program: read once
    a process, at the first launch on an accelerator."""
    import jax
    import jaxlib
    device = jax.devices()[0]
    return (jax.__version__, jaxlib.__version__, device.platform,
            device.client.platform_version, device.device_kind)


def accelerator() -> str | None:
    """The platform a program is persisted for: the default backend's,
    None on the ``cpu`` backend, where nothing is."""
    import jax
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


def write_whole(path: Path, blob: bytes) -> None:
    """``blob`` at ``path``, whole or not at all: written under a
    temporary name in the same directory and renamed in, so a reader
    or a second writer of the key never meets half a file."""
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=path.name + ".")
    try:
        with os.fdopen(fd, "wb") as out:
            out.write(blob)
        os.replace(tmp, path)
    except BaseException:
        os.unlink(tmp)
        raise


#: local variables of the frame an export runs under: 8 bytes each,
#: a little over 128 KiB in all
ROOMY_LOCALS = 16_400


@functools.lru_cache(maxsize=None)
def _roomy_frame():
    """``roomy(fn)`` calls ``fn`` from a frame of some 131 KB.

    CPython 3.12 keeps a thread's frames in chunks of 16 KiB and frees
    and maps one at every call that crosses a chunk's end, so a trace
    whose hot calls sit on such a boundary takes 2.5 times as long on
    the chip's host (PERF.md section 6, PR 29), and where the boundary
    falls depends on every frame between the thread's start and the
    kernel.  A frame that does not fit a chunk gets one of the next
    power of two that holds it: this one gets 256 KiB with half of it
    free, and all the frames of the trace below it lie in that one
    chunk, whatever called the export.  Measured (PERF.md section 6,
    PR 48): the batch kernel's export 4.0-4.6 s at every depth tried
    here, 4.0-11.4 s without; built at the first export, never
    before."""
    scope: dict = {}
    exec("def roomy(fn):\n    if fn is None:\n        %s = fn\n"
         "    return fn()\n"
         % " = ".join("v%d" % i for i in range(ROOMY_LOCALS)), scope)
    return scope["roomy"]


def export_program(jitted, platform: str, avals, static: dict,
                   in_shardings=None):
    """``jitted`` traced and lowered for ``platform`` at ``avals``
    ((shape, dtype) pairs): the trace and lowering a first launch
    pays, kept as a ``jax.export.Exported``.  ``in_shardings``, one an
    argument, for a program over several devices."""
    import jax
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for (shape, dtype), sharding in zip(
                 avals, in_shardings or [None] * len(avals))]
    export = jax.export.export(jitted, platforms=(platform,))
    return _roomy_frame()(lambda: export(*specs, **static))


def _fits(exported, platform: str, avals, devices: int = 1) -> bool:
    return (tuple(exported.platforms) == (platform,)
            and exported.nr_devices == devices
            and [(a.shape, a.dtype) for a in exported.in_avals]
            == [(tuple(shape), dtype) for shape, dtype in avals])


def fetch(path: Path, jitted, platform: str, avals, static: dict,
          in_shardings=None):
    """``(outcome, exported)``: the program at ``path`` if it is whole
    and fits, else a new export written there (``error`` where it
    could not be written).  What the export itself raises is the
    caller's."""
    import jax
    devices = _device_count(in_shardings)
    outcome = "miss"
    try:
        blob = path.read_bytes()
    except (FileNotFoundError, NotADirectoryError):
        blob = None
    if blob is not None:
        try:
            exported = jax.export.deserialize(bytearray(blob))
            if _fits(exported, platform, avals, devices):
                return "hit", exported
        except Exception as exc:    # counted by the caller, as ``stale``
            logger.warning("persisted program %s does not deserialise "
                           "(%r)", path, exc)
        outcome = "stale"
    exported = export_program(jitted, platform, avals, static,
                              in_shardings)
    try:
        write_whole(path, exported.serialize())
    except OSError as exc:
        logger.warning("persisted program %s cannot be written (%r): "
                       "the next start traces it again", path, exc)
        outcome = "error"
    return outcome, exported


def _device_count(in_shardings) -> int:
    return len(in_shardings[0].mesh.devices.flat) if in_shardings else 1


def persisted_jit(*, sources, static_argnames, shardings=None,
                  **jit_kwargs):
    """``jax.jit`` for a kernel's entry point whose lowered program is
    kept beside the compile cache (module docstring).  ``sources`` are
    the files the program is traced from.  The entry keeps the
    function's signature, ``__wrapped__`` (the function itself) and
    the jitted function's ``lower`` and ``trace``.

    ONE program over several devices (a ``shard_map`` inside) gives
    its ``shardings``: ``(in_shardings, out_shardings)``, the first
    one an argument, ``NamedSharding``s of one mesh.  The export is
    made for as many devices as that mesh has, their number is in the
    program's key, and the loaded program is called under them (the
    jitted function itself takes its from the ``shard_map``: a jit
    with ``in_shardings`` takes no static argument by name)."""
    import jax

    def decorate(fun):
        jitted = jax.jit(fun, static_argnames=static_argnames,
                         **jit_kwargs)
        name = fun.__name__
        signature = inspect.signature(fun)
        #: (static arguments, avals) -> the loaded program's jit, or
        #: None for a call that goes to ``jitted``
        loaded: dict = {}
        lock = threading.Lock()

        def resolve(key, args, kwargs):
            with lock:
                if key in loaded:
                    return loaded[key]
                bound = signature.bind(*args, **kwargs)
                bound.apply_defaults()
                static = {n: bound.arguments[n] for n in static_argnames}
                platform = None if static.get("interpret") \
                    else accelerator()
                run = None if platform is None else _program(
                    jitted, name, platform, static, key[1], sources,
                    jit_kwargs, shardings)
                loaded[key] = run
                return run

        @functools.wraps(fun)
        def entry(*args, **kwargs):
            for a in args:
                if isinstance(a, jax.core.Tracer):
                    return jitted(*args, **kwargs)
            try:
                key = (tuple(sorted(kwargs.items())),
                       tuple((a.shape, a.dtype) for a in args))
                run = loaded[key]
            except KeyError:
                run = resolve(key, args, kwargs)
            except (AttributeError, TypeError):
                # a static argument by position, an unhashable one
                run = None
            if run is None:
                return jitted(*args, **kwargs)
            return run(*args)

        entry.lower = jitted.lower
        entry.trace = jitted.trace
        return entry
    return decorate


def _program(jitted, name: str, platform: str, static: dict, avals,
             sources, jit_kwargs, shardings=None):
    """The jit of ``jitted``'s persisted program at ``avals``, loaded
    or exported now; None where it has to be traced live."""
    import jax
    program = DEVICE_TELEMETRY.program_of(name) or name
    in_shardings, out_shardings = shardings or (None, None)
    keyed = static if in_shardings is None else dict(
        static, devices=_device_count(in_shardings))
    with trace("program.load", program=program) as span:
        try:
            path = Path(cache_dir()) / PROGRAMS_DIR / (
                name + "-" + program_key(name, keyed, avals,
                                         environment(),
                                         source_digest(sources))
                + SUFFIX)
            t0 = time.monotonic()
            outcome, exported = fetch(path, jitted, platform, avals,
                                      static, in_shardings)
            if outcome == "hit":
                LOAD_SECONDS.labels(program=program).observe(
                    time.monotonic() - t0)
        except Exception:
            logger.warning("program %s cannot be loaded or exported "
                           "for %s: it is traced live, at every start",
                           name, platform, exc_info=True)
            outcome, exported = "error", None
        span.attrs["outcome"] = outcome
    PROGRAM_CACHE.labels(program=program, outcome=outcome).inc()
    if exported is None:
        return None

    def call(*arrays):
        return exported.call(*arrays)
    # JAX's compile events and the module carry the entry's name
    call.__name__ = call.__qualname__ = name
    if shardings:
        jit_kwargs = dict(jit_kwargs, in_shardings=in_shardings,
                          out_shardings=out_shardings)
    return jax.jit(call, **jit_kwargs)
