"""Device telemetry plane: compile/launch/transfer attribution and
MFU accounting for every jitted/Pallas program (ISSUE 16).

All five observability layers shipped so far see only the *host* —
device time was a black box.  This module is the accelerator-side
instrument panel: a process-wide :class:`DeviceTelemetry` registry
that every launch site in ops/, parallel/, crypto/ and pow/ routes
through (the bmlint ``devicelaunch`` checker enforces the routing).
Per named program it attributes:

- **compiles vs persistent-cache hits** — from JAX's own monitoring
  events (:func:`install_compile_listener`, installed by
  ``core/jaxsetup.setup_jax``), never guessed from launches: every
  trace, lowering and backend compile is counted and timed by phase
  (``jax_compile_events_total`` / ``jax_compile_seconds_total``), and
  where an event's ``fun_name`` is a cataloged program's jitted
  function it also feeds that program's compile count, compile
  seconds and persistent-cache hits.  A recompile storm (an unstable
  static argument) or a shape first wanted mid-run is a counter that
  moves, with the seconds it cost.
- **dispatch vs execute wait** — host seconds spent issuing the
  launch vs blocking on the device->host fetch
  (``block_until_ready``/``np.asarray`` bracketing).
- **device-busy seconds, double-buffer aware** — each launch
  contributes its (dispatch_start, fetch_end) span to a per-program
  union-of-intervals watermark, so two overlapping in-flight slabs
  credit the overlap ONCE (a naive sum would report >100% busy).
- **host<->device bytes and donation hit-rate** — upload/readback
  volume per program plus bytes moved through ``donate_argnums``
  buffers (the packed kernel donates bases/targets).
- **derived rates** — ``device_hashrate_hps`` (EWMA work items per
  busy second) and ``device_mfu_ratio`` against the documented
  flops-per-item model below.

Everything lands in ``observability.REGISTRY`` with bounded labels,
so it rides ``GET /metrics``, federation pushes, costStatus (its
"device" block), clientStatus/deviceStatus, and the flight
recorder's stall dumps for free.  On-demand ``jax.profiler`` device
traces are served behind ``profileDevice [seconds]`` and
``GET /debug/device?seconds=N`` via :func:`capture_device_trace`.

Flops-per-item model (documented estimates, BASELINE.md "Arithmetic
utilization"): one double-SHA512 PoW trial executes
:data:`POW_FLOPS_PER_HASH` = 20600 vector 32-bit ops (counted from the
jaxpr of the Pallas kernels' body, which is what the v5e's compiler
issues: tests/test_sha512_pallas_body.py); one ECDSA verify is ~3.6e6 u32 ops
(Strauss-Shamir 256-step double ladder over 20x13-bit limbs), one
ECDH ~2.4e6 (single 256-step Montgomery-style ladder).  Peak comes
from :data:`DEVICE_PEAK_OPS`, keyed by the ``device_kind`` JAX
reports; a device that is not in the table (the CPU backend, an
unlisted chip) reports NO MFU rather than one against another
device's peak.

Program catalog (lockstep with the ``devicelaunch`` checker: every
row below must be ``register_program()``-ed by a launch module, and
every registration must have a row here):

``pow_slab`` — XLA windowed single-chip nonce search
  (``ops/pow_search.pow_search_jit`` under the ``solve`` host driver).
``pow_verify`` — batched incoming-object PoW verification
  (``ops/pow_search.pow_verify_batch``).
``pallas_slab`` — Mosaic single-object slab kernel
  (``ops/sha512_pallas.pallas_search`` under ``solve``).
``ici_slab`` — the single-object slab kernel as ONE program over
  several chips, the first hit stopping the others over ICI
  (``ops/sha512_ici.ici_search``; the pipeline's lone object on the
  chips of an accelerator).
``batch_search`` — per-object batch kernel
  (``ops/sha512_pallas.pallas_batch_search``; also the pipeline's
  batched mode).
``packed_search`` — packed multi-object Mosaic kernel, the storm
  path (``ops/sha512_pallas.pallas_packed_search``).
``packed_search_xla`` — XLA stand-in of the packed kernel
  (``pow/pipeline._packed_search_xla``; the CPU-CI pipeline path).
``sharded_search`` — pod-wide XLA windowed search with psum
  early-exit (``parallel/pow_sharded.sharded_solve``).
``sharded_batch`` — pod-wide XLA batch search over a 2D mesh
  (``parallel/pow_sharded.sharded_solve_batch``).
``pod_slab`` — pod-wide Pallas single-object slab
  (``parallel/pow_pallas_sharded.pallas_sharded_solve``).
``pod_batch`` — pod-wide Pallas batch
  (``parallel/pow_pallas_sharded.pallas_sharded_solve_batch``).
``secp_verify`` — batch ECDSA acceptance lanes
  (``ops/secp256k1_pallas`` via ``crypto/tpu.TpuSecp``).
``secp_ecdh`` — batch ECDH / fixed-base-mult lanes
  (``ops/secp256k1_pallas`` via ``crypto/tpu.TpuSecp``).

JAX is never imported at module import (the lazy-probe rule
``crypto/tpu.py`` set): device/memory enumeration peeks at the
already-imported module and degrades to empty on hosts where JAX was
never initialized.  Recording never raises into a launch path — a
failed
update counts into ``device_telemetry_dropped_total`` instead.

See docs/observability.md ("Device telemetry") for the metric
catalog and runbook.
"""

from __future__ import annotations

import logging
import sys
import threading
import time

from .metrics import REGISTRY

logger = logging.getLogger("pybitmessage_tpu.observability")

#: vector 32-bit ops per double-SHA512 trial: the tile-shaped equations
#: in the jaxpr of ``ops/sha512_pallas._double_sha512_tile`` with scalar
#: initial-hash words (tests/test_sha512_pallas_body.py guards the
#: count).  Since PR 26 the compiler's final bundles hold the same
#: number (20,593 a vreg of trials); before it the jaxpr read 21,979
#: and the bundles 23,220 (BASELINE.md "Arithmetic utilization")
POW_FLOPS_PER_HASH = 20600.0
#: ~order-of-magnitude u32 ops per batch ECDSA verify: Strauss-Shamir
#: 256-step double ladder, ~7 field mults/step x ~400 limb ops x 2
#: points + inversions (documented model, not a measurement)
SECP_VERIFY_FLOPS = 3.6e6
#: one 256-step scalar-mult ladder (ECDH / fixed-base)
SECP_ECDH_FLOPS = 2.4e6
#: VPU peak u32 issue rate per chip — the denominator of every MFU
#: figure — keyed by ``jax.devices()[0].device_kind``.  Source of the
#: one row: an ESTIMATE, 8x128 lanes x 4 ALUs x ~1.5 GHz (BASELINE.md
#: "Arithmetic utilization"), not a published figure.
DEVICE_PEAK_OPS: dict[str, float] = {"TPU v5 lite": 6.1e12}

#: bound on remembered (program, static-key) launch shapes
MAX_LAUNCH_KEYS = 4096
#: EWMA smoothing for the derived hashrate gauge
RATE_ALPHA = 0.3

#: bounded per-device label values ("d00".."d15", then "overflow") —
#: raw ``str(i)`` label values are exactly what the metric-labels
#: lint exists to stop
_MAX_DEVICE_LABELS = 16
_DEVICE_LABELS = tuple("d%02d" % i for i in range(_MAX_DEVICE_LABELS)
                       ) + ("overflow",)


def _device_label(index: int) -> str:
    return _DEVICE_LABELS[min(int(index), _MAX_DEVICE_LABELS)]


COMPILES = REGISTRY.counter(
    "device_program_compiles_total",
    "Backend compiles of a named device program that the persistent "
    "cache did not serve (JAX's backend_compile event)", ("program",))
CACHE_HITS = REGISTRY.counter(
    "device_program_cache_hits_total",
    "Backend compiles of a named device program served from the "
    "persistent compile cache (lowered first: traced live, or parsed "
    "from its persisted program, program_cache_total)",
    ("program",))
COMPILE_SECONDS = REGISTRY.histogram(
    "device_program_compile_seconds",
    "Trace + lowering + backend-compile (or cache load) seconds of "
    "one new shape of a named device program, from JAX's events",
    ("program",))
JAX_COMPILE_EVENTS = REGISTRY.counter(
    "jax_compile_events_total",
    "JAX compile-pipeline events of the whole process by phase "
    "(trace | lower | backend_compile); one lower per program shape, "
    "cached executable or not", ("phase",))
JAX_COMPILE_SECONDS = REGISTRY.counter(
    "jax_compile_seconds_total",
    "Seconds JAX spent in each compile-pipeline phase, less the events "
    "nested inside one (a traced program's callees): the phases add up "
    "to the wall spent compiling", ("phase",))
JAX_COMPILE_CACHE = REGISTRY.counter(
    "jax_compile_cache_total",
    "Persistent compile cache lookups by result (hit | miss)",
    ("result",))
BACKEND_INIT_SECONDS = REGISTRY.gauge(
    "jax_backend_init_seconds",
    "Wall seconds of the process's first JAX backend initialisation "
    "(core/jaxsetup.setup_jax's device enumeration)")
LAUNCHES = REGISTRY.counter(
    "device_launches_total",
    "Device program launches by program name", ("program",))
DISPATCH_SECONDS = REGISTRY.histogram(
    "device_dispatch_seconds",
    "Host seconds spent issuing one launch (async dispatch call, "
    "excludes the blocking fetch)", ("program",))
EXECUTE_WAIT_SECONDS = REGISTRY.histogram(
    "device_execute_wait_seconds",
    "Host seconds blocked on the device->host fetch of one launch "
    "(the on-device execute proxy under double buffering)",
    ("program",))
BUSY_SECONDS = REGISTRY.counter(
    "device_busy_seconds_total",
    "Union-of-spans device-busy seconds per program: overlapping "
    "double-buffered launches credit their overlap once",
    ("program",))
H2D_BYTES = REGISTRY.counter(
    "device_h2d_bytes_total",
    "Host->device bytes uploaded as launch operands", ("program",))
D2H_BYTES = REGISTRY.counter(
    "device_d2h_bytes_total",
    "Device->host bytes fetched as launch results", ("program",))
DONATED_BYTES = REGISTRY.counter(
    "device_donated_bytes_total",
    "Uploaded bytes whose device buffer was donated back "
    "(donate_argnums — the donation hit-rate numerator over "
    "device_h2d_bytes_total)", ("program",))
WORK_ITEMS = REGISTRY.counter(
    "device_work_items_total",
    "Work items (PoW trial hashes, crypto lane items) executed per "
    "program — the hashrate/MFU numerator", ("program",))
HASHRATE = REGISTRY.gauge(
    "device_hashrate_hps",
    "EWMA work items per second per program, from launch spans and "
    "the kernel's known items-per-launch", ("program",))
MFU = REGISTRY.gauge(
    "device_mfu_ratio",
    "Model flops utilization: hashrate x documented flops-per-item "
    "over the device peak (DEVICE_PEAK_OPS x devices)", ("program",))
DEVICE_MEMORY = REGISTRY.gauge(
    "device_memory_bytes",
    "Live device memory where the backend exposes memory_stats() "
    "(bytes_in_use / bytes_limit per bounded device label)",
    ("device", "kind"))
DEVICE_INFO = REGISTRY.gauge(
    "device_backend_info",
    "Device count by backend platform and device kind (a presence/"
    "topology gauge for federation panes)", ("platform", "kind"))
TELEMETRY_DROPPED = REGISTRY.counter(
    "device_telemetry_dropped_total",
    "record_launch updates that raised and were dropped (telemetry "
    "must never fail the launch path it observes)")


class DeviceTelemetry:
    """Process-wide device-program registry + launch recorder.

    ``register_program`` is called at import time by each launch
    module with a LITERAL program name (the ``devicelaunch`` checker
    reads those literals for the catalog lockstep); ``record_launch``
    is called per launch from host drivers and never raises.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: dict[str, dict] = {}
        #: jitted function name -> program, for JAX's compile events
        self._jit_names: dict[str, str] = {}
        self._seen_keys: set[tuple] = set()
        #: per-program busy-span watermark (monotonic end time of the
        #: union of all credited spans) — spans complete in fetch
        #: order, so a watermark is an exact union-of-intervals
        self._busy_end: dict[str, float] = {}
        self._rate: dict[str, float] = {}

    # -- registration --------------------------------------------------------

    def register_program(self, name: str, *,
                         flops_per_item: float | None = None,
                         module: str = "",
                         jit_names: tuple[str, ...] = ()) -> None:
        """Declare a named device program (idempotent).

        ``flops_per_item`` feeds the MFU model; ``module`` is the
        defining module for the deviceStatus table; ``jit_names`` are
        the names of its jitted functions, by which JAX's compile
        events find the program."""
        with self._lock:
            spec = self._programs.setdefault(
                name, {"flops_per_item": None, "module": ""})
            if flops_per_item is not None:
                spec["flops_per_item"] = float(flops_per_item)
            if module:
                spec["module"] = module
            for jit_name in jit_names:
                self._jit_names[jit_name] = name

    def program_of(self, fun_name: str) -> str | None:
        """The cataloged program a compile event's ``fun_name``
        (``my_prog`` or ``jit(my_prog)``) belongs to."""
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        return self._jit_names.get(fun_name)

    def programs(self) -> dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._programs.items()}

    # -- recording -----------------------------------------------------------

    def record_launch(self, program: str, *, key=None,
                      dispatch_seconds: float = 0.0,
                      wait_seconds: float = 0.0,
                      span: tuple[float, float] | None = None,
                      items: float = 0, bytes_in: int = 0,
                      bytes_out: int = 0, bytes_donated: int = 0,
                      devices: int = 1) -> None:
        """Attribute one finished launch.  Never raises.

        ``key`` is the program's static-shape tuple, remembered for
        :meth:`launched_keys` (compiles are counted from JAX's own
        events, not from here).  ``span`` is (dispatch_start, fetch_end) in ``time.monotonic``
        terms; overlap with the previous span is credited once.
        """
        try:
            self._record(program, key, float(dispatch_seconds),
                         float(wait_seconds), span, float(items),
                         int(bytes_in), int(bytes_out),
                         int(bytes_donated), max(1, int(devices)))
        except Exception:
            try:
                TELEMETRY_DROPPED.inc()
            # a broken registry must still not raise into the launch
            # path — the debug log below is the only trace
            except Exception:  # bmlint: allow(silent-swallow)
                pass  # pragma: no cover — last resort
            logger.debug("device telemetry update dropped",
                         exc_info=True)

    def _record(self, program, key, dispatch_seconds, wait_seconds,
                span, items, bytes_in, bytes_out, bytes_donated,
                devices):
        LAUNCHES.labels(program=program).inc()
        DISPATCH_SECONDS.labels(program=program).observe(
            dispatch_seconds)
        EXECUTE_WAIT_SECONDS.labels(program=program).observe(
            wait_seconds)
        if bytes_in:
            H2D_BYTES.labels(program=program).inc(bytes_in)
        if bytes_out:
            D2H_BYTES.labels(program=program).inc(bytes_out)
        if bytes_donated:
            DONATED_BYTES.labels(program=program).inc(bytes_donated)
        if items:
            WORK_ITEMS.labels(program=program).inc(items)

        if key is not None:
            with self._lock:
                if len(self._seen_keys) < MAX_LAUNCH_KEYS:
                    self._seen_keys.add((program, key))

        if span is None:
            busy = dispatch_seconds + wait_seconds
        else:
            start, end = float(span[0]), float(span[1])
            with self._lock:
                watermark = self._busy_end.get(program, start)
                busy = max(0.0, end - max(start, watermark))
                self._busy_end[program] = max(watermark, end)
        if busy > 0:
            BUSY_SECONDS.labels(program=program).inc(busy)

        if items and busy > 0:
            inst = items / busy
            with self._lock:
                prev = self._rate.get(program)
                rate = inst if prev is None else (
                    prev + RATE_ALPHA * (inst - prev))
                self._rate[program] = rate
                flops = self._programs.get(program, {}).get(
                    "flops_per_item")
            HASHRATE.labels(program=program).set(rate)
            peak = device_peak_ops()
            if flops and peak:
                MFU.labels(program=program).set(
                    min(rate * flops / (peak * devices), 1.0))

    def launched_keys(self) -> list[tuple]:
        """Every (program, static-shape key) launched so far
        (chip_smoke.py prints the table and looks for interpret
        mode in it)."""
        with self._lock:
            return sorted(self._seen_keys, key=repr)

    def reset(self) -> None:
        """Drop launched-shape/busy state (tests; counters stay
        monotonic as the registry requires)."""
        with self._lock:
            self._seen_keys.clear()
            self._busy_end.clear()
            self._rate.clear()


#: the process-wide registry every launch site routes through
DEVICE_TELEMETRY = DeviceTelemetry()


def register_program(name: str, *, flops_per_item: float | None = None,
                     module: str = "",
                     jit_names: tuple[str, ...] = ()) -> None:
    DEVICE_TELEMETRY.register_program(
        name, flops_per_item=flops_per_item, module=module,
        jit_names=jit_names)


def record_launch(program: str, **kwargs) -> None:
    DEVICE_TELEMETRY.record_launch(program, **kwargs)


# ---------------------------------------------------------------------------
# JAX's compile events -> counters
# ---------------------------------------------------------------------------

_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
#: a program's trace, lowering, cache lookup and backend compile run
#: one after another on the thread that launches it: what the earlier
#: phases took, and how the lookup went, wait here for the last one
_compiling = threading.local()
#: bound on the top-level compile intervals a thread remembers
_MAX_INTERVALS = 1024
_listener_installed = False
_listener_lock = threading.Lock()


def _own_seconds(seconds: float) -> float:
    """``seconds`` of the event that has just ended on this thread,
    less the events that ran inside it: tracing a program traces every
    jitted function it calls, and each reports its own duration, so
    the plain sum would count those seconds twice.  With this the
    phases add up to the wall the thread spent compiling."""
    end = time.monotonic()
    start = end - seconds
    stack = getattr(_compiling, "intervals", None)
    if stack is None:
        stack = _compiling.intervals = []
    inside = 0.0
    # an event that began after this one began ran inside it (the
    # phases of one program follow each other and do not qualify)
    while stack and stack[-1][0] >= start:
        inside += stack.pop()[1]
    stack.append((start, seconds))
    if len(stack) > _MAX_INTERVALS:
        del stack[:_MAX_INTERVALS // 2]
    return max(seconds - inside, 0.0)


def _on_compile_duration(event: str, seconds: float, **kw) -> None:
    phase = _COMPILE_PHASES.get(event)
    if phase is None:
        return
    try:
        JAX_COMPILE_EVENTS.labels(phase=phase).inc()
        JAX_COMPILE_SECONDS.labels(phase=phase).inc(_own_seconds(seconds))
        program = DEVICE_TELEMETRY.program_of(kw.get("fun_name") or "")
        pending = getattr(_compiling, "seconds", None)
        if pending is None:
            pending = _compiling.seconds = {}
        if phase != "backend_compile":
            if program is not None:
                pending[program] = pending.get(program, 0.0) + seconds
            return
        result, _compiling.cache = getattr(_compiling, "cache", None), None
        if program is None:
            return
        COMPILE_SECONDS.labels(program=program).observe(
            seconds + pending.pop(program, 0.0))
        (CACHE_HITS if result == "hit" else COMPILES).labels(
            program=program).inc()
    except Exception:
        # a listener that raises would fail the compile it watches
        logger.debug("compile event dropped", exc_info=True)


def _on_compile_event(event: str, **_kw) -> None:
    result = _CACHE_RESULTS.get(event)
    if result is None:
        return
    try:
        JAX_COMPILE_CACHE.labels(result=result).inc()
        _compiling.cache = result
    except Exception:
        logger.debug("compile cache event dropped", exc_info=True)


def install_compile_listener() -> None:
    """Feed the ``jax_compile_*`` and per-program compile series from
    JAX's monitoring events.  Once a process however often it is
    called: JAX cannot drop a listener.  Imports JAX."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(
        _on_compile_duration)
    jax.monitoring.register_event_listener(_on_compile_event)


# ---------------------------------------------------------------------------
# device / backend enumeration (lazy: never initializes a backend)
# ---------------------------------------------------------------------------


def _live_jax():
    """The jax module IF some subsystem already imported it — this
    plane must never be the reason a backend initializes."""
    return sys.modules.get("jax")


def device_peak_ops() -> float | None:
    """Peak u32 ops/s of the live device's kind, or None when JAX is
    not loaded or the kind has no row in :data:`DEVICE_PEAK_OPS`."""
    jax = _live_jax()
    if jax is None:
        return None
    return DEVICE_PEAK_OPS.get(str(jax.devices()[0].device_kind))


def update_device_gauges() -> list[dict]:
    """Refresh per-device labels/memory gauges; returns the device
    table (empty when JAX was never imported or has no backend)."""
    jax = _live_jax()
    if jax is None:
        return []
    try:
        devices = jax.devices()
    except Exception:
        return []
    by_platform: dict[tuple[str, str], int] = {}
    table = []
    for i, dev in enumerate(devices):
        platform = str(getattr(dev, "platform", "unknown"))
        kind = str(getattr(dev, "device_kind", "unknown"))
        by_platform[(platform, kind)] = \
            by_platform.get((platform, kind), 0) + 1
        row = {"id": int(getattr(dev, "id", i)),
               "label": _device_label(i),
               "platform": platform, "kind": kind}
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats:
            for k in ("bytes_in_use", "bytes_limit",
                      "peak_bytes_in_use"):
                if k in stats:
                    row[k] = int(stats[k])
            label = _device_label(i)
            if "bytes_in_use" in stats:
                DEVICE_MEMORY.labels(
                    device=label, kind="bytes_in_use").set(
                    stats["bytes_in_use"])
            if "bytes_limit" in stats:
                DEVICE_MEMORY.labels(
                    device=label, kind="bytes_limit").set(
                    stats["bytes_limit"])
        table.append(row)
    for (platform, kind), n in by_platform.items():
        DEVICE_INFO.labels(platform=platform, kind=kind).set(n)
    return table


def env_fingerprint() -> dict:
    """jax/jaxlib/libtpu versions + backend/device identity — the
    self-describing stamp bench.py writes into every BENCH/MULTICHIP
    JSON and the doctor leads its report with."""
    import platform as _platform
    out: dict = {"python": _platform.python_version()}
    jax = _live_jax()
    if jax is None:
        try:
            import jax  # the doctor/bench call sites want the probe
        except Exception as exc:
            out["jax"] = None
            out["error"] = repr(exc)
            return out
    out["jax"] = getattr(jax, "__version__", None)
    try:
        import jaxlib
        out["jaxlib"] = getattr(jaxlib, "__version__", None)
    except Exception:
        out["jaxlib"] = None
    out["libtpu"] = _libtpu_version()
    try:
        out["backend"] = jax.default_backend()
        devices = jax.devices()
        out["device_count"] = len(devices)
        out["device_kind"] = str(getattr(
            devices[0], "device_kind", "unknown")) if devices else None
    except Exception as exc:
        out["backend"] = None
        out["error"] = repr(exc)
    return out


def _libtpu_version() -> str | None:
    try:
        from importlib import metadata
    except Exception:  # pragma: no cover — py<3.8 only
        return None
    for dist in ("libtpu", "libtpu-nightly"):
        try:
            return metadata.version(dist)
        # absent distribution — probing, not failing
        except Exception:  # bmlint: allow(silent-swallow)
            continue
    return None


# ---------------------------------------------------------------------------
# status documents / on-demand trace capture
# ---------------------------------------------------------------------------


def _series(name: str, program: str):
    fam = REGISTRY.get(name)
    if fam is None:
        return None
    for values, child in fam.children():
        if values == (program,):
            return child
    return None


def _counter_value(name: str, program: str) -> float:
    child = _series(name, program)
    return float(child.value) if child is not None else 0.0


def _hist_stats(name: str, program: str) -> tuple[int, float]:
    child = _series(name, program)
    if child is None:
        return 0, 0.0
    _, total_sum, count = child.snapshot()
    return count, total_sum


def device_status() -> dict:
    """The ``deviceStatus`` document: per-program attribution table +
    device/backend identity (JSON-able, read-only, never raises into
    the API path beyond what the registry itself would)."""
    programs = {}
    for name, spec in sorted(DEVICE_TELEMETRY.programs().items()):
        launches = _counter_value("device_launches_total", name)
        _, dispatch_sum = _hist_stats("device_dispatch_seconds", name)
        _, wait_sum = _hist_stats("device_execute_wait_seconds", name)
        h2d = _counter_value("device_h2d_bytes_total", name)
        donated = _counter_value("device_donated_bytes_total", name)
        programs[name] = {
            "module": spec.get("module", ""),
            "flopsPerItem": spec.get("flops_per_item"),
            "launches": int(launches),
            "compiles": int(_counter_value(
                "device_program_compiles_total", name)),
            "cacheHits": int(_counter_value(
                "device_program_cache_hits_total", name)),
            "compileSeconds": round(_hist_stats(
                "device_program_compile_seconds", name)[1], 6),
            "dispatchSeconds": round(dispatch_sum, 6),
            "executeWaitSeconds": round(wait_sum, 6),
            "busySeconds": round(_counter_value(
                "device_busy_seconds_total", name), 6),
            "h2dBytes": int(h2d),
            "d2hBytes": int(_counter_value(
                "device_d2h_bytes_total", name)),
            "donatedBytes": int(donated),
            "donationRate": round(donated / h2d, 4) if h2d else 0.0,
            "workItems": int(_counter_value(
                "device_work_items_total", name)),
            "hashrateHps": round(REGISTRY.sample(
                "device_hashrate_hps", {"program": name}), 2),
            "mfu": round(REGISTRY.sample(
                "device_mfu_ratio", {"program": name}), 6),
        }
    return {
        "devices": update_device_gauges(),
        "env": env_fingerprint() if _live_jax() is not None else
               {"jax": None, "note": "jax not imported yet"},
        "programs": programs,
        "dropped": REGISTRY.sample("device_telemetry_dropped_total"),
    }


def device_cost_block() -> dict:
    """The ``costStatus`` ``device`` block: the attribution shares a
    cost view needs, without the full per-program table."""
    progs = DEVICE_TELEMETRY.programs()
    busy = {p: _counter_value("device_busy_seconds_total", p)
            for p in progs}
    total_busy = sum(busy.values())
    return {
        "busySeconds": round(total_busy, 6),
        "byProgram": {p: round(s, 6) for p, s in sorted(busy.items())
                      if s > 0},
        "compileSeconds": round(sum(
            _hist_stats("device_program_compile_seconds", p)[1]
            for p in progs), 6),
        "executeWaitSeconds": round(sum(
            _hist_stats("device_execute_wait_seconds", p)[1]
            for p in progs), 6),
        "launches": int(sum(
            _counter_value("device_launches_total", p)
            for p in progs)),
    }


#: bound on one on-demand capture — a forgotten long trace would hold
#: the profiler (and its buffer growth) for the whole session
MAX_TRACE_SECONDS = 60.0


def capture_device_trace(seconds: float,
                         out_dir: str | None = None) -> dict:
    """Run ``jax.profiler.trace`` for ``seconds`` and report the
    artifact paths (the ``profileDevice`` / ``GET /debug/device``
    backend).  Blocking — API callers run it in an executor."""
    import os
    import tempfile
    seconds = float(seconds)
    if not 0 < seconds <= MAX_TRACE_SECONDS:
        raise ValueError("trace seconds must be in (0, %g]"
                         % MAX_TRACE_SECONDS)
    try:
        import jax
    except Exception as exc:  # pragma: no cover — jax is baked in
        return {"ok": False, "error": "jax unavailable: %r" % exc}
    trace_dir = out_dir or tempfile.mkdtemp(prefix="bmtpu_devtrace_")
    t0 = time.monotonic()
    try:
        with jax.profiler.trace(trace_dir):
            # launches from worker threads land in the trace while we
            # hold it open
            time.sleep(seconds)
    except Exception as exc:
        return {"ok": False, "error": repr(exc),
                "traceDir": trace_dir}
    files = []
    for root, _dirs, names in os.walk(trace_dir):
        for fname in names:
            path = os.path.join(root, fname)
            try:
                size = os.path.getsize(path)
            except OSError:
                size = 0
            files.append({"path": os.path.relpath(path, trace_dir),
                          "bytes": size})
    return {"ok": True, "traceDir": trace_dir,
            "seconds": round(time.monotonic() - t0, 3),
            "files": sorted(files, key=lambda f: f["path"])}
