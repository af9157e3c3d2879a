"""Solver ladder: farm -> TPU -> C++ -> pure Python, with fallthrough.

Reference semantics (proofofwork.py:288-325): try the fastest backend;
on failure log and fall through to the next; every tier is
interruptible; the winning nonce is host-verified before being trusted
(the TPU tier already re-checks internally, ops/pow_search.py).

An attached :class:`~pybitmessage_tpu.powfarm.FarmSolverTier`
(``attach_farm``) leads the ladder: jobs are delegated to a shared
solver farm with deadline propagation and per-job trace contexts; ANY
farm failure (dial, admission reject, expired deadline, bad nonce) is
an ordinary tier failure — its breaker opens and the batch is
requeued on the local ladder, so an unreachable farm degrades to
exactly the pre-farm node (docs/pow_farm.md).

Tier health is managed by per-tier circuit breakers
(resilience/policy.py) instead of the old permanent latch: a failing
tier opens after ``threshold`` consecutive failures (1 for the device
tiers — a failed Mosaic compile costs ~75 s and must not be re-paid
per solve), fallbacks stop paying the failure latency while it is
open, and a half-open probe after the cooldown lets a recovered
device rejoin the ladder.  ``pow.device_launch`` is a chaos injection
site (docs/resilience.md); slab-level stall detection lives in
pipeline.py and surfaces here as an ordinary tier failure.
"""

from __future__ import annotations

import hashlib
import logging
import time
from typing import Callable

from ..observability import REGISTRY, trace
from ..ops.pow_search import PowInterrupted
from ..resilience import CircuitBreaker, inject
from ..resilience.policy import ERRORS
from ..resilience.watchdog import STALL_RECOVERY_SECONDS
from .native import NativeSolver

logger = logging.getLogger("pybitmessage_tpu.pow")

#: slab-stall deadline handed to the pipeline: seconds ONE harvest (the
#: blocking device->host fetch of a dispatched slab) may take; 0
#: disables the watchdog.  A cold Mosaic compile (minutes) is not inside
#: it: jit compiles synchronously in the dispatch call, on the solving
#: thread, before the guarded fetch starts — chip_smoke.py's compile
#: table shows it as first-launch DISPATCH seconds.
DEFAULT_STALL_TIMEOUT = 120.0

SOLVE_SECONDS = REGISTRY.histogram(
    "pow_solve_seconds",
    "Solve-only latency of one PoW launch (single object or fused "
    "batch), excluding the dispatcher's host verification",
    ("backend",))
HOST_VERIFY_SECONDS = REGISTRY.histogram(
    "pow_host_verify_seconds",
    "Host-side double-SHA512 re-check of a winning nonce")
ATTEMPTS = REGISTRY.counter(
    "pow_attempts_total", "Solve attempts entering each ladder tier",
    ("backend",))
FALLBACKS = REGISTRY.counter(
    "pow_fallback_total",
    "Ladder fallthrough events (a tier failed and a slower one took "
    "over)", ("from", "to"))


def _note_fallback(frm: str, to: str) -> None:
    """One ladder fallthrough: counted AND flight-recorded — the tier
    history right before a stall is post-mortem gold."""
    FALLBACKS.labels(**{"from": frm, "to": to}).inc()
    from ..observability.flightrec import record as _flight
    _flight("pow_fallback", frm=frm, to=to)
TRIALS = REGISTRY.counter(
    "pow_trials_total", "Double-SHA512 trial hashes executed",
    ("backend",))
MESH_COMPILES = REGISTRY.counter(
    "pow_mesh_compiles_total",
    "Device mesh constructions, one per distinct (ndev, obj) shape — "
    "a proxy for per-shape XLA compiles", ("shape",))


def host_trial(nonce: int, initial_hash: bytes) -> int:
    """One double-SHA512 trial value — THE PoW formula.

    ``python_solve`` inlines the same computation for loop speed; keep
    the two in lockstep."""
    sha512 = hashlib.sha512
    return int.from_bytes(sha512(sha512(
        nonce.to_bytes(8, "big") + initial_hash).digest()
    ).digest()[:8], "big")


def python_solve(initial_hash: bytes, target: int, *,
                 start_nonce: int = 0,
                 should_stop: Callable[[], bool] | None = None,
                 progress: Callable[[int], None] | None = None):
    """The always-works tier (reference _doSafePoW, proofofwork.py:157-171).

    ``progress(next_nonce)``, when given, checkpoints resumable search
    state at the same 4096-trial cadence as the stop poll: every nonce
    below the reported value has been searched without a hit.
    """
    nonce = start_nonce
    trials = 0
    sha512 = hashlib.sha512
    while True:
        if should_stop is not None and trials % 4096 == 0 and should_stop():
            raise PowInterrupted("python PoW interrupted")
        if progress is not None and trials % 4096 == 0 and trials:
            progress(nonce)
        value = int.from_bytes(sha512(sha512(
            nonce.to_bytes(8, "big") + initial_hash).digest()
        ).digest()[:8], "big")
        trials += 1
        if value <= target:
            return nonce, trials
        nonce += 1


class PowDispatcher:
    """Callable solver with the GPU->C->python fallback ladder.

    When more than one accelerator device is visible, single solves are
    range-partitioned across the whole mesh (``sharded_solve``) and
    :meth:`solve_batch` maps a queue of pending objects onto a 2D
    (objects x nonce-range) mesh — the pod-wide production path.

    Timing attributes (also exported through the metrics registry):

    ``last_rate``
        trials/sec over the WALL time of the last ``solve()`` /
        ``solve_batch()`` call — solve plus the dispatcher's host
        re-verification of the winning nonce.  This is the end-to-end
        figure a caller experiences and what clientStatus reports.
    ``last_solve_seconds`` / ``last_solve_rate``
        solve-only time (device/native/python search, no host verify)
        and the corresponding trials/sec — the number to compare
        against bench.py kernel rates.
    ``last_verify_seconds``
        host double-SHA512 re-check time of the last winning nonce.
    """

    def __init__(self, *, use_tpu: bool = True, use_native: bool = True,
                 tpu_kwargs: dict | None = None, num_threads: int = 0,
                 stall_timeout: float = DEFAULT_STALL_TIMEOUT,
                 breakers: dict[str, CircuitBreaker] | None = None,
                 farm=None):
        self.tpu_kwargs = tpu_kwargs or {}
        #: optional FarmSolverTier leading the ladder (attach_farm)
        self.farm = farm
        self._tpu_enabled = use_tpu
        self._native = NativeSolver(num_threads) if use_native else None
        self.last_backend = ""
        self.last_rate = 0.0
        self.last_solve_seconds = 0.0
        self.last_solve_rate = 0.0
        self.last_verify_seconds = 0.0
        self._meshes: dict = {}
        #: per-harvest slab stall deadline for the pipelined path
        self.stall_timeout = stall_timeout
        #: per-tier circuit breakers (threshold 1 on the device tiers:
        #: one failure is a dead/miscompiling device and re-probing it
        #: costs a full compile — the half-open probe after cooldown
        #: replaces the old permanent latch)
        self.breakers = breakers or {
            "tpu": CircuitBreaker("pow.tier.tpu", threshold=1,
                                  cooldown=300.0),
            "tpu-pallas": CircuitBreaker("pow.tier.tpu-pallas",
                                         threshold=1, cooldown=600.0),
            "cpp": CircuitBreaker("pow.tier.cpp", threshold=3,
                                  cooldown=60.0),
        }
        #: monotonic time of the last slab stall — recovery latency is
        #: observed when a fallback tier completes the rescued work
        self._stalled_at: float | None = None

    # -- device topology -----------------------------------------------------

    def _device_count(self) -> int:
        """Raises when JAX cannot initialise: callers probe inside a
        tier handler, so that counts as a device-tier failure."""
        import jax
        return len(jax.devices())

    def _batch_topology(self):
        """``(device count, on accelerator)`` for the batch paths.  A
        failed probe is counted and logged as a failure of the ``tpu``
        tier (never read in silence as "no accelerator") and answers
        ``(0, False)``, which no device branch below takes."""
        try:
            return self._device_count(), self._on_accelerator()
        except Exception:
            self.breakers["tpu"].record_failure()
            ERRORS.labels(site="pow.tier.tpu").inc()
            logger.exception(
                "JAX device probe failed; batch goes to the per-object "
                "ladder")
            _note_fallback("tpu", "ladder")
            return 0, False

    def _record_recovery(self) -> None:
        """A solve completed after a slab stall: export how long the
        rescued work took to land on a fallback tier."""
        if self._stalled_at is not None:
            STALL_RECOVERY_SECONDS.observe(
                time.monotonic() - self._stalled_at)
            self._stalled_at = None

    def _note_stall(self, exc: Exception) -> None:
        from ..resilience.watchdog import SlabStallError
        if isinstance(exc, SlabStallError) and self._stalled_at is None:
            self._stalled_at = time.monotonic()

    def _mesh(self, ndev: int, batch: int):
        """(obj x nonce) mesh for ``batch`` objects; 1D when batch == 1."""
        obj_size = 1
        if batch > 1:
            for d in range(min(ndev, batch), 0, -1):
                if ndev % d == 0:
                    obj_size = d
                    break
        key = (ndev, obj_size)
        if key not in self._meshes:
            from ..parallel import make_mesh
            # shape values are bounded by the pod topology (device
            # count x slab obj_size), not by traffic
            MESH_COMPILES.labels(shape="%dx%d" % key).inc()  # bmlint: allow(metric-labels)
            if obj_size == 1:
                self._meshes[key] = make_mesh(ndev)
            else:
                self._meshes[key] = make_mesh(
                    ndev, obj_axis="obj", obj_size=obj_size)
        return self._meshes[key]

    def attach_farm(self, farm) -> None:
        """Register a FarmSolverTier as the ladder's top rung."""
        self.farm = farm

    def _try_farm(self, items, should_stop, starts):
        """Attempt the farm tier; ``None`` means fall through to the
        local ladder (requeue-on-farm-failure — the accepted jobs are
        re-solved locally, and the farm's journal dedupe makes any
        overlap benign)."""
        farm = self.farm
        if farm is None or not farm.breaker.allow():
            return None
        try:
            self.last_backend = "farm"
            ATTEMPTS.labels(backend="farm").inc()
            results = farm.solve_batch(items, should_stop=should_stop,
                                       start_nonces=starts)
            farm.breaker.record_success()
            return results
        except PowInterrupted:
            farm.breaker.release_probe()
            raise
        except Exception as exc:
            farm.breaker.record_failure()
            ERRORS.labels(site="pow.tier.farm").inc()
            logger.warning(
                "farm tier failed (%r); requeueing %d job(s) on the "
                "local ladder (breaker: %s)", exc, len(items),
                farm.breaker.state)
            next_tier = "tpu" if self._tpu_enabled else (
                "native" if self._native is not None
                and self._native.available else "python")
            _note_fallback("farm", next_tier)
            return None

    def backends(self) -> list[str]:
        """Currently-usable tiers: statically enabled AND not sitting
        behind an open (pre-cooldown) circuit breaker."""
        out = []
        if self.farm is not None and self.farm.breaker.available():
            out.append("farm")
        if self._tpu_enabled and self.breakers["tpu"].available():
            out.append("tpu")
        if self._native is not None and self._native.available and \
                self.breakers["cpp"].available():
            out.append("cpp")
        out.append("python")
        return out

    def __call__(self, initial_hash: bytes, target: int, *,
                 start_nonce: int = 0,
                 should_stop: Callable[[], bool] | None = None):
        with trace("pow.solve") as span:
            t0 = time.monotonic()
            nonce, trials = self._solve(
                initial_hash, target, start_nonce, should_stop)
            solve_dt = max(time.monotonic() - t0, 1e-9)
            # host re-check of the winning nonce (reference
            # proofofwork semantics), timed apart from the search so
            # last_solve_rate stays a pure solver figure
            v0 = time.monotonic()
            value = host_trial(nonce, initial_hash)
            verify_dt = time.monotonic() - v0
            if value > target:
                logger.warning(
                    "backend %s returned nonce failing host verification",
                    self.last_backend)
            span.attrs["backend"] = self.last_backend
            span.attrs["trials"] = trials
        self._record_recovery()
        self.last_solve_seconds = solve_dt
        self.last_solve_rate = trials / solve_dt
        self.last_verify_seconds = verify_dt
        self.last_rate = trials / (solve_dt + verify_dt)
        SOLVE_SECONDS.labels(backend=self.last_backend).observe(solve_dt)
        HOST_VERIFY_SECONDS.observe(verify_dt)
        TRIALS.labels(backend=self.last_backend).inc(trials)
        return nonce, trials

    # keep the explicit name too
    solve = __call__

    def solve_batch(self, items, *, should_stop=None, start_nonces=None,
                    progress=None):
        """Solve ``[(initial_hash, target), ...]`` -> ``[(nonce, trials)]``.

        All pending objects go down in ONE pod-wide launch when a
        multi-device mesh is available (objects data-parallel x nonce
        range partitioned); otherwise objects are solved sequentially
        through the normal ladder.

        Resumable-PoW hooks: ``start_nonces`` (one offset per item)
        resumes each object's search from a journaled checkpoint, and
        ``progress(i, next_nonce)`` is called as slabs harvest with
        the highest offset known fully searched for item ``i`` — the
        pipelined single-chip path, the pod-sharded Pallas batch loop
        and the sequential ladder all honor both (the XLA
        ``sharded_solve_batch`` rescue tier still re-searches from 0
        but remains correct).
        """
        items = list(items)
        if not items:
            return []
        starts = list(start_nonces) if start_nonces else [0] * len(items)
        t0 = time.monotonic()
        pb = self.breakers["tpu-pallas"]
        tb = self.breakers["tpu"]
        with trace("pow.solve_batch", objects=len(items)) as span:
            # the farm rung leads the ladder; a farm failure falls
            # through to the local tiers below with nothing lost
            results = self._try_farm(items, should_stop, starts)
            ndev, on_accel = (
                self._batch_topology()
                if results is None and self._tpu_enabled else (0, False))
            if len(items) > 1:
                if ndev > 1:
                    if on_accel and pb.allow():
                        try:
                            inject("pow.device_launch")
                            from ..parallel import pallas_sharded_solve_batch
                            self.last_backend = "tpu-pallas-sharded-batch"
                            ATTEMPTS.labels(backend=self.last_backend).inc()
                            results = pallas_sharded_solve_batch(
                                items, self._mesh(ndev, len(items)),
                                should_stop=should_stop,
                                start_nonces=starts, progress=progress)
                            pb.record_success()
                            tb.record_success()
                        except PowInterrupted:
                            pb.release_probe()
                            raise
                        except Exception as exc:
                            logger.exception(
                                "sharded batched Pallas PoW failed; using "
                                "sharded XLA batch")
                            self._pallas_failed(exc, "tpu-xla")
                    if results is None and tb.allow():
                        try:
                            inject("pow.device_launch")
                            from ..parallel import sharded_solve_batch
                            self.last_backend = "tpu-batch"
                            ATTEMPTS.labels(backend=self.last_backend).inc()
                            results = sharded_solve_batch(
                                items, self._mesh(ndev, len(items)),
                                should_stop=should_stop,
                                **self._xla_kwargs())
                            tb.record_success()
                        except PowInterrupted:
                            tb.release_probe()
                            raise
                        except Exception as exc:
                            self._note_stall(exc)
                            tb.record_failure()
                            ERRORS.labels(site="pow.tier.tpu").inc()
                            logger.exception(
                                "batched TPU PoW failed; falling back to "
                                "per-object solves")
                            _note_fallback("tpu-batch", "ladder")
                elif on_accel and pb.allow():
                    # single chip: the async double-buffered pipeline
                    # plans the launch shape (multi-object slab packing
                    # for storms, the per-object (objects x chunks)
                    # batch grid for network difficulty, a synchronous
                    # latency-optimal launch for one tiny object) and
                    # keeps slabs dispatched ahead of harvest
                    try:
                        inject("pow.device_launch")
                        from .pipeline import solve_batch_pipelined
                        self.last_backend = "tpu-pallas-batch"
                        ATTEMPTS.labels(backend=self.last_backend).inc()
                        results = solve_batch_pipelined(
                            items, should_stop=should_stop,
                            start_nonces=starts, progress=progress,
                            stall_timeout=self.stall_timeout)
                        pb.record_success()
                    except PowInterrupted:
                        pb.release_probe()
                        raise
                    except Exception as exc:
                        # breaker opens like the per-object ladder: a
                        # broken Mosaic kernel must not re-pay a ~75 s
                        # failed compile on every subsequent batch
                        logger.exception(
                            "batched Pallas PoW failed; falling back to "
                            "per-object solves")
                        self._pallas_failed(exc, "ladder")
            if (results is None and len(items) == 1 and on_accel
                    and ndev <= 1 and pb.allow()):
                # degenerate case: ONE object.  If it is tiny (expected
                # to finish inside the first small launch) the pipeline
                # takes its latency-optimal synchronous path instead of
                # paying a full production slab + speculative dispatch.
                try:
                    inject("pow.device_launch")
                    from .pipeline import plan_batch, solve_batch_pipelined
                    if plan_batch(items).mode == "single-sync":
                        self.last_backend = "tpu-pallas-batch"
                        ATTEMPTS.labels(backend=self.last_backend).inc()
                        results = solve_batch_pipelined(
                            items, should_stop=should_stop,
                            start_nonces=starts, progress=progress,
                            stall_timeout=self.stall_timeout)
                        pb.record_success()
                    else:
                        pb.release_probe()
                except PowInterrupted:
                    pb.release_probe()
                    raise
                except Exception as exc:
                    logger.exception(
                        "pipelined single-object PoW failed; using the "
                        "ladder")
                    self._pallas_failed(exc, "ladder")
                    results = None
            if results is None:
                results = []
                for i, (ih, t) in enumerate(items):
                    prog = None
                    if progress is not None:
                        prog = (lambda n, _i=i: progress(_i, n))
                    # the batch already tried (or skipped) the farm —
                    # per-item retries against a failing farm would
                    # just re-pay its timeout N times
                    results.append(self._solve(ih, t, starts[i],
                                               should_stop, progress=prog,
                                               try_farm=False))
            span.attrs["backend"] = self.last_backend
        self._record_recovery()
        dt = max(time.monotonic() - t0, 1e-9)
        trials = sum(r[1] for r in results)
        self.last_solve_seconds = dt
        self.last_solve_rate = trials / dt
        self.last_rate = trials / dt
        SOLVE_SECONDS.labels(backend=self.last_backend).observe(dt)
        TRIALS.labels(backend=self.last_backend).inc(trials)
        return results

    def _on_accelerator(self) -> bool:
        import jax
        return jax.default_backend() != "cpu"

    def _xla_kwargs(self) -> dict:
        """Slab sizing for the XLA tier: the TPU sweet spot (2^19 x 64)
        is minutes of work per slab for a host CPU backend, so without
        an accelerator default to a small slab."""
        if self.tpu_kwargs:
            return self.tpu_kwargs
        if not self._on_accelerator():
            return {"lanes": 1 << 12, "chunks_per_call": 8}
        return {}

    def _pallas_failed(self, exc: Exception, to: str) -> None:
        """Bookkeeping shared by every Mosaic-tier failure path."""
        self._note_stall(exc)
        self.breakers["tpu-pallas"].record_failure()
        ERRORS.labels(site="pow.tier.tpu-pallas").inc()
        _note_fallback("tpu-pallas", to)

    def _solve(self, initial_hash, target, start_nonce, should_stop,
               progress=None, try_farm=True):
        if try_farm:
            farmed = self._try_farm([(initial_hash, target)],
                                    should_stop, [start_nonce])
            if farmed is not None:
                return farmed[0]
        tb = self.breakers["tpu"]
        pb = self.breakers["tpu-pallas"]
        if self._tpu_enabled and tb.allow():
            try:
                inject("pow.device_launch")
                ndev = self._device_count()
                if ndev > 1:
                    # pod-wide nonce partition over ICI, production
                    # Pallas kernel per chip (VERDICT r2 #1: the pod
                    # tier must not run the 3.3x-slower XLA kernel)
                    if self._on_accelerator() and pb.allow():
                        try:
                            from ..parallel import pallas_sharded_solve
                            self.last_backend = "tpu-pallas-sharded"
                            ATTEMPTS.labels(backend=self.last_backend).inc()
                            result = pallas_sharded_solve(
                                initial_hash, target, self._mesh(ndev, 1),
                                start_nonce=start_nonce,
                                should_stop=should_stop,
                                progress=progress)
                            pb.record_success()
                            tb.record_success()
                            return result
                        except PowInterrupted:
                            pb.release_probe()
                            raise
                        except Exception as exc:
                            logger.exception(
                                "sharded Pallas PoW failed; using "
                                "sharded XLA search")
                            self._pallas_failed(exc, "tpu-xla")
                    from ..parallel import sharded_solve
                    self.last_backend = "tpu-sharded"
                    ATTEMPTS.labels(backend=self.last_backend).inc()
                    result = sharded_solve(
                        initial_hash, target, self._mesh(ndev, 1),
                        start_nonce=start_nonce, should_stop=should_stop,
                        **self._xla_kwargs())
                    tb.record_success()
                    return result
                if self._on_accelerator() and pb.allow():
                    # Mosaic kernel: 290.6 MH/s on a v5e chip
                    # (kernel_mhash_per_s.slab of a traced single_send
                    # run, PERF.md section 5, PR 27; the XLA path below
                    # it was 25.8 MH/s in BASELINE.md and has no cell)
                    # — the fastest usable backend leads the ladder,
                    # reference proofofwork.py:288-325 / openclpow
                    # wiring.  One static shape: see sha512_pallas.solve
                    try:
                        from ..ops.sha512_pallas import solve as pl_solve
                        self.last_backend = "tpu-pallas"
                        ATTEMPTS.labels(backend=self.last_backend).inc()
                        result = pl_solve(initial_hash, target,
                                          start_nonce=start_nonce,
                                          should_stop=should_stop,
                                          progress=progress)
                        pb.record_success()
                        tb.record_success()
                        return result
                    except PowInterrupted:
                        pb.release_probe()
                        raise
                    except Exception as exc:
                        logger.exception(
                            "Pallas PoW failed; using XLA search")
                        self._pallas_failed(exc, "tpu-xla")
                from ..ops.pow_search import solve as tpu_solve
                self.last_backend = "tpu"
                ATTEMPTS.labels(backend=self.last_backend).inc()
                kwargs = self._xla_kwargs()
                if not self.tpu_kwargs:
                    # no explicit powlanes/powchunks override: let the
                    # measured-latency autotuner size the XLA slab
                    # (a shape is cheap here; the Mosaic tiers above
                    # have one static shape each and never ask it)
                    from .pipeline import AUTOTUNER
                    kwargs = dict(kwargs, tuner=AUTOTUNER)
                result = tpu_solve(initial_hash, target,
                                   start_nonce=start_nonce,
                                   should_stop=should_stop,
                                   progress=progress,
                                   **kwargs)
                tb.record_success()
                return result
            except PowInterrupted:
                tb.release_probe()
                raise
            except Exception as exc:
                self._note_stall(exc)
                tb.record_failure()
                ERRORS.labels(site="pow.tier.tpu").inc()
                logger.exception(
                    "TPU PoW failed; falling through to C++ "
                    "(breaker open, half-open probe after cooldown)")
                next_tier = ("native"
                             if self._native is not None
                             and self._native.available else "python")
                _note_fallback("tpu", next_tier)
        if self._native is not None and self._native.available:
            cb = self.breakers["cpp"]
            if cb.allow():
                try:
                    self.last_backend = "cpp"
                    ATTEMPTS.labels(backend=self.last_backend).inc()
                    result = self._native.solve(initial_hash, target,
                                                start_nonce=start_nonce,
                                                should_stop=should_stop)
                    cb.record_success()
                    return result
                except PowInterrupted:
                    cb.release_probe()
                    raise
                except Exception:
                    cb.record_failure()
                    ERRORS.labels(site="pow.tier.cpp").inc()
                    logger.exception(
                        "C++ PoW failed; falling through to python")
                    _note_fallback("native", "python")
        self.last_backend = "python"
        ATTEMPTS.labels(backend=self.last_backend).inc()
        return python_solve(initial_hash, target, start_nonce=start_nonce,
                            should_stop=should_stop, progress=progress)
