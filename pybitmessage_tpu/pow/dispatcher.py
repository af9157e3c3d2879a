"""Solver ladder: farm -> TPU -> C++ -> pure Python, with fallthrough.

Reference semantics (proofofwork.py:288-325): try the fastest backend;
on failure log and fall through to the next; every tier is
interruptible; the winning nonce is host-verified before being trusted
(the device tiers already re-check internally).

This module is the top one of the send path's three boxes
(docs/pow_pipeline.md): it decides WHICH RUNG runs and whether that
rung is healthy, and nothing about kernels or shapes — those are
``pow/pipeline.py``'s (``plan_batch`` and the one dispatch-ahead loop),
which in turn launches the kernels of ``ops/sha512_pallas.py``.  The
rungs the topology admits are walked in order (:meth:`PowDispatcher.
solve_batch` for a queue, :meth:`PowDispatcher._solve` for one object,
which is also where a queue of one and a failed queue go), and every
rung runs under the same bookkeeping, written once
(:meth:`PowDispatcher._run_rung`): breaker gate, chaos site, attempt
counter, then success closing its breakers, an interrupt giving the
half-open probe back, or a failure counted, logged and noted as a
fallback.  What differs from rung to rung is data (:class:`_Rung`).

An attached :class:`~pybitmessage_tpu.powfarm.FarmSolverTier`
(``attach_farm``) leads the ladder: jobs are delegated to a shared
solver farm with deadline propagation and per-job trace contexts; ANY
farm failure (dial, admission reject, expired deadline, bad nonce) is
an ordinary tier failure — its breaker opens and the batch is
requeued on the local ladder, so an unreachable farm degrades to
exactly the pre-farm node (docs/pow_farm.md).

Tier health is managed by per-tier circuit breakers
(resilience/policy.py): a failing tier opens after ``threshold``
consecutive failures (1 for the device tiers — a failed Mosaic compile
must not be re-paid per solve), fallbacks stop paying the failure
latency while it is open, and a half-open probe after the cooldown
lets a recovered device rejoin the ladder.  ``pow.device_launch`` is a
chaos injection site (docs/resilience.md); slab-level stall detection
lives in pipeline.py and surfaces here as an ordinary tier failure.
"""

from __future__ import annotations

import hashlib
import logging
import time
from typing import Callable, NamedTuple

# imported with the dispatcher and not at a rung's first call: that
# call is a solve somebody waits for
from .. import parallel
from ..observability import REGISTRY, trace
from ..ops.pow_search import PowInterrupted
from ..resilience import CircuitBreaker, inject
from ..resilience.policy import ERRORS
from ..resilience.watchdog import STALL_RECOVERY_SECONDS
from .native import NativeSolver

logger = logging.getLogger("pybitmessage_tpu.pow")

#: slab-stall deadline handed to the pipeline: seconds ONE harvest (the
#: blocking device->host fetch of a dispatched slab) may take; 0
#: disables the watchdog.  A cold Mosaic compile is not inside it: jit
#: compiles synchronously in the dispatch call, which the driver waits
#: for without a deadline, before the guarded fetch starts —
#: chip_smoke.py's compile table shows it as first-launch DISPATCH
#: seconds.
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


class _Rung(NamedTuple):
    """What differs between rungs, for :meth:`PowDispatcher._run_rung`.
    Where the rungs disagree (which breakers a success closes, what a
    failure is called) each keeps what it always did."""

    backend: str | None     # pow_attempts_total label; None: the body's
    breaker: str            # gates the rung, takes its failures, and
    #                         names its error site pow.tier.<breaker>
    closes: tuple           # breakers a success closes besides its own
    chaos: bool             # pow.device_launch is injected on entry
    frm: str                # pow_fallback_total{from,to} of a failure;
    to: str | None          # None: the next host tier (native | python)
    message: str            # logged with the failure's traceback


# a queue: the pipeline on the accelerator's chips, then on several
# devices the XLA search sharded over them
_PIPELINE_BATCH = _Rung(
    "tpu-pallas-batch", "tpu-pallas", (), True, "tpu-pallas", "ladder",
    "batched Pallas PoW failed; falling back to the next rung")
_XLA_SHARDED_BATCH = _Rung(
    "tpu-batch", "tpu", (), True, "tpu-batch", "ladder",
    "batched TPU PoW failed; falling back to per-object solves")
# one object: the ``tpu`` rung holds the topology probe, the Mosaic
# rung (the pipeline with a batch of one, over every chip; its own
# breaker inside this one) and the XLA search, whose failures are this
# rung's
_TPU = _Rung(
    None, "tpu", (), True, "tpu", None,
    "TPU PoW failed; falling through to C++ (breaker open, half-open "
    "probe after cooldown)")
_PALLAS = _Rung(
    "tpu-pallas", "tpu-pallas", ("tpu",), False, "tpu-pallas", "tpu-xla",
    "Pallas PoW failed; using XLA search")
_CPP = _Rung(
    "cpp", "cpp", (), False, "native", "python",
    "C++ PoW failed; falling through to python")


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
    """Callable solver with the farm -> TPU -> C++ -> python ladder.

    On an accelerator a queue goes through
    ``pow.pipeline.solve_batch_pipelined`` however many chips there
    are: its launch groups are dealt over them, an object's whole nonce
    range on one chip.  A lone object goes the same way: on one chip a
    batch of one, on several its nonce space shared out over the
    pipeline's lanes, a share a chip, first hit wins.  Several devices
    that are no accelerator (a CPU mesh) take a queue as one XLA search
    on a 2D (objects x nonce-range) mesh, and a lone object as one
    partitioned over the mesh (``sharded_solve``).

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
        #: ``_batch_topology``'s answer, once a probe has succeeded
        self._topology: tuple[int, bool] | None = None

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
        ``(0, False)``, which no device branch below takes; a probe
        that succeeded is kept (``streams`` asks at every window)."""
        if self._topology is not None:
            return self._topology
        try:
            self._topology = self._device_count(), self._on_accelerator()
            return self._topology
        except Exception:
            self.breakers["tpu"].record_failure()
            ERRORS.labels(site="pow.tier.tpu").inc()
            logger.exception(
                "JAX device probe failed; batch goes to the per-object "
                "ladder")
            _note_fallback("tpu", "ladder")
            return 0, False

    @staticmethod
    def _placement(ndev: int):
        """The devices the pipeline places a solve over: every chip
        where there are several, else None (JAX's default device)."""
        import jax
        return jax.devices()[:ndev] if ndev > 1 else None

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
            # shape values are bounded by the pod topology (device
            # count x slab obj_size), not by traffic
            MESH_COMPILES.labels(shape="%dx%d" % key).inc()  # bmlint: allow(metric-labels)
            if obj_size == 1:
                self._meshes[key] = parallel.make_mesh(ndev)
            else:
                self._meshes[key] = parallel.make_mesh(
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
            _note_fallback("farm", "tpu" if self._tpu_enabled
                           else self._host_tier())
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

    def streams(self, items, expect: int = 0) -> bool:
        """Whether a solve of ``items``, laid out for ``expect``
        objects, would now take late arrivals in (``feed``): the rung
        that would serve it is the pipeline (the TPU rungs enabled, no
        farm ahead of them, an accelerator of one chip or several, the
        pipeline's breaker not open), and the plan for it is the mode
        that takes in (``batched``: objects at network difficulty; a
        queue of tiny objects is packed and holds who it starts with).
        ``PowService`` asks per window, and starts a sweep's solve at
        its first member only where this says yes, and then hands the
        solve an ``expect`` above what it holds: that is the verdict,
        and ``solve_batch`` does not ask again.  No breaker's probe is
        consumed by asking, and the topology is probed once a process
        (``_batch_topology``)."""
        if not self._tpu_enabled or not \
                self.breakers["tpu-pallas"].available():
            return False
        if self.farm is not None and self.farm.breaker.available():
            return False
        if not self._batch_topology()[1]:
            return False
        from .pipeline import plan_batch
        return plan_batch(items, expect=expect).mode == "batched"

    def solve_batch(self, items, *, should_stop=None, start_nonces=None,
                    progress=None, on_solved=None, feed=None,
                    expect: int = 0):
        """Solve ``[(initial_hash, target), ...]`` -> ``[(nonce, trials)]``.

        On an accelerator the queue is the pipeline's, placed over
        every chip; on a multi-device mesh that is none, all pending
        objects go down in ONE launch (objects data-parallel x nonce
        range partitioned); otherwise objects are solved sequentially
        through the normal ladder.

        Resumable-PoW hooks: ``start_nonces`` (one offset per item)
        resumes each object's search from a journaled checkpoint, and
        ``progress(i, next_nonce)`` is called as slabs harvest with
        the highest offset known fully searched for item ``i`` — the
        pipeline and the sequential ladder honor both (the XLA
        ``sharded_solve_batch`` rescue tier still re-searches from 0
        but remains correct).

        The solve as a stream (docs/pow_pipeline.md): ``on_solved(i,
        (nonce, trials))`` is called once for every item, as soon as
        its nonce is known — from the harvest that found it on the
        pipeline, when the rung returns on the rungs that cannot
        stream — and ``feed(room)`` lets the pipeline's
        ``batched`` mode take queued requests ``(initial_hash, target,
        start_nonce)`` into freed slots; they are numbered on from
        ``len(items)`` and their results follow the items' in what is
        returned.  A rung that fails after some objects resolved hands
        only the rest to the rungs below.  ``pow_trials_total`` is
        credited as objects resolve and ``pow_attempts_total`` counts
        every time the solve takes objects in (its start and each
        refill), so both move inside a solve that outlives a window.

        ``expect`` is the number of objects the solve should be laid
        out for, where more are announced than ``items`` holds (a
        sweep's solve that starts at its first member): it goes to the
        streaming rung only, and a lone item with ``expect`` above 1 is
        offered that rung, and no other batch rung, as a queue is.
        """
        items = list(items)
        if not items:
            return []
        starts = list(start_nonces) if start_nonces else [0] * len(items)
        solved: dict[int, tuple] = {}

        def resolve(i, result):
            # once an item, whichever rung found its nonce
            if i in solved:
                return
            solved[i] = result
            TRIALS.labels(backend=self.last_backend).inc(result[1])
            if on_solved is not None:
                on_solved(i, result)

        def take(room):
            arrived = feed(room)
            if arrived:
                for initial_hash, target, start in arrived:
                    items.append((initial_hash, target))
                    starts.append(start)
                ATTEMPTS.labels(backend=self.last_backend).inc()
            return arrived

        t0 = time.monotonic()
        expect = max(expect, len(items))
        with trace("pow.solve_batch", objects=len(items),
                   expect=expect) as span:
            # the farm rung leads the ladder; a farm failure falls
            # through to the local tiers below with nothing lost
            results = self._try_farm(items, should_stop, starts)
            # a lone item with announced company is a queue: the
            # caller has asked ``streams``, and only the rung that
            # takes company in is offered it (``_batch_rungs``)
            if results is None and self._tpu_enabled and expect > 1:
                for rung, call in self._batch_rungs(
                        items, starts, should_stop, progress, resolve,
                        take if feed is not None else None, expect):
                    results = self._run_rung(rung, call)
                    if results is not None:
                        break
            # one object, or a queue no device rung took (or finished):
            # each object still unsolved walks the per-object rungs
            if results is None:
                results = []
                for i, (ih, t) in enumerate(items):
                    if i in solved:
                        results.append(solved[i])
                        continue
                    prog = None
                    if progress is not None:
                        prog = (lambda n, _i=i: progress(_i, n))
                    # the batch already tried (or skipped) the farm —
                    # per-item retries against a failing farm would
                    # just re-pay its timeout N times
                    results.append(self._solve(ih, t, starts[i],
                                               should_stop, progress=prog,
                                               try_farm=False))
                    resolve(i, results[i])
            for i, result in enumerate(results):
                resolve(i, result)
            span.attrs["backend"] = self.last_backend
        self._record_recovery()
        dt = max(time.monotonic() - t0, 1e-9)
        trials = sum(r[1] for r in results)
        self.last_solve_seconds = dt
        self.last_solve_rate = trials / dt
        self.last_rate = trials / dt
        SOLVE_SECONDS.labels(backend=self.last_backend).observe(dt)
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

    # -- the ladder ----------------------------------------------------------

    def _enter(self, backend: str) -> None:
        self.last_backend = backend
        ATTEMPTS.labels(backend=backend).inc()

    def _host_tier(self) -> str:
        return ("native" if self._native is not None
                and self._native.available else "python")

    def _run_rung(self, rung: _Rung, call):
        """One step of the ladder: run ``call`` as ``rung``.  Returns
        its result, or None when the rung's breaker is open or the
        rung failed (counted, logged, noted as a fallback) and the
        walk goes on to the next."""
        breaker = self.breakers[rung.breaker]
        if not breaker.allow():
            return None
        try:
            if rung.chaos:
                inject("pow.device_launch")
            if rung.backend is not None:
                self._enter(rung.backend)
            result = call()
            breaker.record_success()
            for name in rung.closes:
                self.breakers[name].record_success()
            return result
        except PowInterrupted:
            # an interrupt is not evidence of health
            breaker.release_probe()
            raise
        except Exception as exc:
            # the breaker opens: a broken Mosaic kernel must not re-pay
            # a failed compile on every subsequent solve
            self._note_stall(exc)
            breaker.record_failure()
            site = "pow.tier." + rung.breaker
            ERRORS.labels(site=site).inc()
            logger.exception(rung.message)
            _note_fallback(rung.frm, rung.to or self._host_tier())
            return None

    def _batch_rungs(self, items, starts, should_stop, progress,
                     on_solved, feed, expect):
        """The device rungs the topology admits for a queue, in order,
        each with the call that runs it.  Only the pipeline streams: it
        alone is given ``on_solved``, ``feed`` and ``expect``, and on
        several chips the devices to place its launch groups on."""
        ndev, on_accel = self._batch_topology()

        def xla_sharded_batch():
            return parallel.sharded_solve_batch(
                items, self._mesh(ndev, len(items)),
                should_stop=should_stop, **self._xla_kwargs())

        def pipeline_batch():
            from .pipeline import solve_batch_pipelined
            return solve_batch_pipelined(
                items, should_stop=should_stop, start_nonces=starts,
                progress=progress, stall_timeout=self.stall_timeout,
                on_solved=on_solved, feed=feed, expect=expect,
                devices=self._placement(ndev))

        if on_accel:
            yield _PIPELINE_BATCH, pipeline_batch
        # a lone item laid out for company: this rung cannot take it in
        if ndev > 1 and len(items) > 1:
            yield _XLA_SHARDED_BATCH, xla_sharded_batch

    def _solve(self, initial_hash, target, start_nonce, should_stop,
               progress=None, try_farm=True):
        item = (initial_hash, target)
        if try_farm:
            farmed = self._try_farm([item], should_stop, [start_nonce])
            if farmed is not None:
                return farmed[0]
        if self._tpu_enabled:
            result = self._run_rung(_TPU, lambda: self._solve_on_device(
                item, start_nonce, should_stop, progress))
            if result is not None:
                return result
        if self._native is not None and self._native.available:
            result = self._run_rung(_CPP, lambda: self._native.solve(
                initial_hash, target, start_nonce=start_nonce,
                should_stop=should_stop))
            if result is not None:
                return result
        self._enter("python")
        return python_solve(initial_hash, target, start_nonce=start_nonce,
                            should_stop=should_stop, progress=progress)

    def _solve_on_device(self, item, start_nonce, should_stop, progress):
        """The body of one object's ``tpu`` rung: on an accelerator the
        Mosaic rung — the pipeline with a batch of one, given every
        chip where there are several (an object that is alone has
        nobody to share them with: each searches a share of its nonce
        space) — then the XLA search."""
        initial_hash, target = item
        ndev = self._device_count()

        def pipeline_one():
            from .pipeline import solve_batch_pipelined
            return solve_batch_pipelined(
                [item], should_stop=should_stop,
                start_nonces=[start_nonce],
                progress=(None if progress is None
                          else lambda _i, nxt: progress(nxt)),
                stall_timeout=self.stall_timeout,
                devices=self._placement(ndev))[0]

        if self._on_accelerator():
            result = self._run_rung(_PALLAS, pipeline_one)
            if result is not None:
                return result
        if ndev > 1:
            self._enter("tpu-sharded")
            return parallel.sharded_solve(
                initial_hash, target, self._mesh(ndev, 1),
                start_nonce=start_nonce, should_stop=should_stop,
                **self._xla_kwargs())
        from ..ops.pow_search import solve as tpu_solve
        self._enter("tpu")
        kwargs = self._xla_kwargs()
        if not self.tpu_kwargs:
            # no explicit powlanes/powchunks override: let the
            # measured-latency autotuner size the XLA slab (a shape is
            # cheap here; the Mosaic kernels have one static shape
            # each and never ask it)
            from .pipeline import AUTOTUNER
            kwargs = dict(kwargs, tuner=AUTOTUNER)
        return tpu_solve(initial_hash, target, start_nonce=start_nonce,
                         should_stop=should_stop, progress=progress,
                         **kwargs)
