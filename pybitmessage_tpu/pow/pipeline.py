"""Asynchronous double-buffered PoW execution pipeline (ISSUE 2).

BENCH_r05 measured the device kernel at 202.9M H/s per chip while the
batched-queue config aggregated only 135.6M H/s and the broadcast storm
(10k tiny objects) collapsed to 35.7M H/s — the host pipeline was
giving back most of the kernel's gains.  Three levers close the gap:

1. **Multi-object slab packing** (``ops.sha512_pallas.
   pallas_packed_search``): several pending objects share ONE device
   slab along the lane axis with per-lane object identity and
   per-object targets, so a storm of small objects fills the grid
   instead of paying a full launch + host sync per object.
2. **Dispatch-ahead double buffering** (:func:`_PipelineDriver.run`):
   slab N+1 is issued before slab N's hit flags are read back, hiding
   host verification/serialization behind device compute (the
   sync-slab penalty: 136.6M vs 202.9M H/s).
3. **One static shape a kernel.**  ``chunks`` is a static argument of
   every Mosaic kernel, so each value is a program of its own to trace,
   lower and compile, and one the chip may refuse (``pallas_search``
   at 1024 chunks needs more SMEM than a v5e has).  The planner hands
   each kernel the one chunk count measured on the chip (PERF.md
   section 6, PR 27); :class:`SlabAutotuner` sizes the XLA tier's
   slabs only (``ops.pow_search.solve``), where a shape is cheap.

The planner (:func:`plan_batch`) chooses per batch between the packed
kernel (many small objects), the per-object batch kernel (few large
objects) and a latency-optimal synchronous single launch (the
degenerate one-tiny-object case must not pay speculative dispatch).
Every stage reports through ``observability.REGISTRY`` — device-busy
fraction, dispatch-ahead depth, pack occupancy — per the conventions
in docs/observability.md; see docs/pow_pipeline.md for the full
architecture.

On hosts without an accelerator (the CI virtual CPU mesh) the Mosaic
kernels are replaced by an XLA equivalent with the identical
(pack, 3)-row output contract (``impl="xla"``), so the planning,
pipelining and metrics logic is fully exercised without a TPU —
the same pattern ``parallel/pow_pallas_sharded.py`` uses.
"""

from __future__ import annotations

import functools
import logging
import math
import threading
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp

from ..observability import DEFAULT_SIZE_BUCKETS, REGISTRY, trace
from ..observability.devicetelemetry import (POW_FLOPS_PER_HASH,
                                             record_launch,
                                             register_program)
from ..observability.flightrec import record as _flight
from ..ops.pow_search import PowInterrupted
from ..resilience.chaos import inject
from ..resilience.watchdog import STALLS, SlabStallError
from ..ops.sha512_jax import double_sha512_trial
from ..ops.sha512_pallas import (DEFAULT_ROWS, LANE_COLS,
                                 pallas_packed_search)
from ..ops.u64 import U32
from ..utils.hashes import double_sha512

logger = logging.getLogger("pybitmessage_tpu.pow")

_MASK64 = (1 << 64) - 1
#: always-hit target for pad slots (every trial value is <= 2^64-1)
_ALWAYS_HIT = _MASK64

DEVICE_BUSY = REGISTRY.gauge(
    "pow_pipeline_device_busy_ratio",
    "Fraction of the last pipelined solve's wall time the host spent "
    "blocked on device results — a lower bound on true device "
    "occupancy; the sync-path penalty shows up as this dropping")
PIPELINE_DEPTH = REGISTRY.gauge(
    "pow_pipeline_depth", "Slabs currently in flight (dispatch-ahead)")
DISPATCH_AHEAD = REGISTRY.histogram(
    "pow_pipeline_dispatch_ahead_size",
    "In-flight slab count sampled at each harvest",
    buckets=DEFAULT_SIZE_BUCKETS)
DEVICE_WAIT = REGISTRY.histogram(
    "pow_pipeline_device_wait_seconds",
    "Blocking wait for one slab's results at harvest time")
PACK_SIZE = REGISTRY.histogram(
    "pow_pack_size",
    "Live (non-pad, unsolved) objects sharing one packed slab launch",
    buckets=DEFAULT_SIZE_BUCKETS)
PACK_OCCUPANCY = REGISTRY.gauge(
    "pow_pack_occupancy_ratio",
    "Fraction of the last packed slab's lanes owned by live objects")
PIPELINE_MODE = REGISTRY.counter(
    "pow_pipeline_mode_total",
    "Pipelined solve launches by execution mode", ("mode",))
SLAB_SECONDS = REGISTRY.histogram(
    "pow_slab_seconds",
    "Wall latency of one XLA-tier slab (dispatch to harvested) — the "
    "autotuner's input, with pow_autotune_steps_total", ("kind",))
AUTOTUNE_CHUNKS = REGISTRY.gauge(
    "pow_slab_autotune_chunks",
    "Chunks-per-launch the autotuner currently suggests", ("kind",))
AUTOTUNE_STEPS = REGISTRY.counter(
    "pow_autotune_steps_total",
    "Grid steps fed to the autotuner with their seconds: the steps a "
    "slab really ran, not the steps it was launched with", ("kind",))
AUTOTUNE_SHAPE_CHANGES = REGISTRY.counter(
    "pow_autotune_shape_changes_total",
    "Times the autotuner asked a kind for another chunk count than "
    "the last time (each is a program to compile)", ("kind",))
# the one kind that asks exists at 0 from the start: "no change" then
# reads 0, not "no such series"
AUTOTUNE_SHAPE_CHANGES.labels(kind="xla")
LAUNCHES = REGISTRY.counter(
    "pow_pipeline_launches_total",
    "Search-kernel launches dispatched by the PoW host loops, by kind "
    "(batch | packed | single-sync | slab)", ("kind",))
ABANDONED_LAUNCHES = REGISTRY.counter(
    "pow_pipeline_abandoned_launches_total",
    "Speculative launches dispatched and never fetched because every "
    "result was already in (the device still runs them)", ("kind",))
EXECUTED_TRIALS = REGISTRY.counter(
    "pow_pipeline_executed_trials_total",
    "Trials the device computed in harvested launches, counted by the "
    "grid steps each really ran (abandoned launches are not read "
    "back, so their trials are not in here)", ("kind",))


class SlabAutotuner:
    """Derives the XLA tier's slab size from measured latency.

    Tracks an EWMA of seconds per grid step per slab ``kind`` and
    suggests a power-of-two chunk count, within the bounds its caller
    allows, whose expected slab latency is closest to
    ``target_seconds`` — the hit-poll / shutdown-poll granularity.
    ``record`` takes the steps the slab really ran: a search leaves its
    slab at the first hit, and a slab timed as if it had run whole
    reads as a fast device (PR 24 on the chip: the tuner then asked
    ``pallas_search`` for a shape the v5e cannot compile, and the
    breaker took every solve off the chip).  The Mosaic kernels do
    not come here: each has one measured shape (see :func:`plan_batch`
    and ``ops.sha512_pallas.solve``).  The EWMA plus a 10x outlier
    clamp make one slow observation (a fresh jit compile, a host
    stall) decay instead of permanently shrinking slabs.  Thread-safe:
    the dispatcher's executor and the asyncio service may solve
    concurrently.
    """

    def __init__(self, *, target_seconds: float = 0.5,
                 min_chunks: int = 4, max_chunks: int = 2048,
                 alpha: float = 0.4):
        self.target_seconds = target_seconds
        self.min_chunks = min_chunks
        self.max_chunks = max_chunks
        self.alpha = alpha
        self._per_chunk: dict[str, float] = {}
        self._asked: dict[str, int] = {}
        self._lock = threading.Lock()

    def record(self, kind: str, steps: int, seconds: float) -> None:
        """Feed one measured slab: the grid steps it really ran (up to
        its first hit) and its dispatch->harvest wall seconds."""
        if steps <= 0 or seconds <= 0:
            return
        per = seconds / steps
        with self._lock:
            prev = self._per_chunk.get(kind)
            if prev is not None and per > 10 * prev:
                # compile / stall outlier: cap its influence so
                # one bad slab cannot crater the suggestion
                per = 10 * prev
            self._per_chunk[kind] = per if prev is None else (
                self.alpha * per + (1 - self.alpha) * prev)
        AUTOTUNE_STEPS.labels(kind=kind).inc(steps)
        SLAB_SECONDS.labels(kind=kind).observe(seconds)

    def suggest(self, kind: str, default: int,
                lo: int | None = None, hi: int | None = None) -> int:
        """Chunk count within ``[lo, hi]`` targeting ``target_seconds``
        per slab; ``default`` until a slab of ``kind`` was recorded."""
        with self._lock:
            per = self._per_chunk.get(kind)
            chunks = default
            if per is not None and per > 0:
                raw = self.target_seconds / per
                chunks = 1 << max(0, round(math.log2(max(raw, 1.0))))
                chunks = max(lo or self.min_chunks,
                             min(hi or self.max_chunks, chunks))
            changed = self._asked.get(kind, default) != chunks
            self._asked[kind] = chunks
        if changed:
            AUTOTUNE_SHAPE_CHANGES.labels(kind=kind).inc()
        AUTOTUNE_CHUNKS.labels(kind=kind).set(chunks)
        return chunks

    def seconds_per_chunk(self, kind: str) -> float | None:
        """EWMA seconds per grid step (None until first record)."""
        with self._lock:
            return self._per_chunk.get(kind)


#: process-wide autotuner of the XLA tier (``PowDispatcher._solve``)
AUTOTUNER = SlabAutotuner()


def default_impl() -> str:
    """"pallas" on an accelerator backend, "xla" on host CPU.  A JAX
    that fails to initialise raises into the caller's tier handler."""
    return "pallas" if jax.default_backend() != "cpu" else "xla"


def expected_trials(target: int) -> float:
    """Mean trials to beat ``target`` (trial values uniform on u64)."""
    return 2.0 ** 64 / max(target & _MASK64, 1)


# ---------------------------------------------------------------------------
# XLA stand-in for the packed Mosaic kernel (CPU mesh / CI)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("lanes", "chunks"))
def _packed_search_xla(ih_words, bases, targets, lanes: int, chunks: int):
    """Same output contract as ``pallas_packed_search`` in pure XLA.

    Each object scans ``chunks`` chunks of ``lanes`` consecutive
    nonces (``lanes`` = the object's per-step lane share) — identical
    ranges and winner ordering to the packed/batch kernels, so hosts
    without Mosaic (the CI CPU mesh) exercise the exact pipeline and
    planner logic.  Returns (B, 3) uint32 rows ``[hit_step + 1,
    nonce_hi, nonce_lo]``.
    """

    def one(ihw, base, target):
        lane = jnp.arange(lanes, dtype=U32)

        def step(carry, _):
            b_hi, b_lo = carry
            lo = b_lo + lane
            c = (lo < b_lo).astype(U32)
            hi = jnp.broadcast_to(b_hi, lo.shape) + c
            v_hi, v_lo = double_sha512_trial(hi, lo, ihw[:, 0], ihw[:, 1])
            ok = (v_hi < target[0]) | ((v_hi == target[0])
                                       & (v_lo <= target[1]))
            idx = jnp.argmax(ok)
            n_lo = b_lo + jnp.uint32(lanes)
            n_hi = b_hi + (n_lo < b_lo).astype(U32)
            return (n_hi, n_lo), (jnp.any(ok), hi[idx], lo[idx])

        _, (hits, nhs, nls) = jax.lax.scan(
            step, (base[0], base[1]), None, length=chunks)
        first = jnp.argmax(hits)
        found = jnp.any(hits)
        step1 = jnp.where(found, first + 1, 0).astype(U32)
        return jnp.stack([step1, nhs[first], nls[first]])

    return jax.vmap(one)(ih_words, bases, targets)


register_program("packed_search_xla", flops_per_item=POW_FLOPS_PER_HASH,
                 module="pow/pipeline.py",
                 jit_names=("_packed_search_xla",))


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

#: pack-factor ladder: rows//pack stays >= 8 (one VPU sublane) at the
#: production row count
PACK_CHOICES = (16, 8, 4, 2)
#: grid steps of one packed launch; at pack=16 that is 8*128*chunks
#: trials per object per launch
DEFAULT_PACKED_CHUNKS = 64
#: grid steps an object of the per-object batch kernel: the shape every
#: chip run of PR 24-26 settled at under the tuner (64 -> 128 in the
#: first sweep of a 256-object storm), now the only one.  An object
#: leaves at its hit, so a longer grid costs no trials, only fewer
#: launches
DEFAULT_BATCH_CHUNKS = 128
#: leading-grid-axis cap of one packed launch: up to 64 tiles *
#: pack objects ride one kernel call (the storm's launch-overhead
#: amortization); group counts round up to powers of two so the
#: compile cache stays a short ladder per pack
PACKED_GROUPS_MAX = 64
#: a single object expected to finish inside this many full-tile grid
#: steps takes the latency-optimal synchronous path — speculative
#: dispatch-ahead would only add latency (the degenerate case)
SYNC_SINGLE_STEPS = 8


class BatchPlan:
    """Execution plan for one pipelined batch (see :func:`plan_batch`)."""

    __slots__ = ("mode", "pack", "chunks", "order")

    def __init__(self, mode: str, pack: int, chunks: int, order):
        self.mode = mode        # "packed" | "batched" | "single-sync"
        self.pack = pack        # objects per slab (packed mode)
        self.chunks = chunks    # grid steps per launch
        self.order = order      # item indices, difficulty-sorted

    def __repr__(self):  # pragma: no cover - debug aid
        return ("BatchPlan(mode=%r, pack=%d, chunks=%d, n=%d)"
                % (self.mode, self.pack, self.chunks, len(self.order)))


def plan_batch(items, *, rows: int = DEFAULT_ROWS,
               unroll: int = 1) -> BatchPlan:
    """Choose packing and slab geometry from the batch's difficulty.

    The pack factor is sized so one launch covers roughly every
    object's expected work: tiny (storm) objects pack 16 per slab,
    network-default objects keep whole tiles (pack=1 -> the per-object
    batch kernel), and a single small object degenerates to one
    synchronous latency-optimal launch.  Objects are difficulty-sorted
    so each packed group is homogeneous (a straggler would otherwise
    hold its whole group's rows live).  Each mode has ONE chunk count,
    so a node compiles each kernel once and launches nothing the chip
    has not already accepted.
    """
    n = len(items)
    exp = [expected_trials(t) for _, t in items]
    tile_step = rows * LANE_COLS * unroll      # full-tile trials/step
    if n == 1 and exp[0] <= SYNC_SINGLE_STEPS * tile_step:
        return BatchPlan("single-sync", 1, SYNC_SINGLE_STEPS, [0])
    order = sorted(range(n), key=lambda i: exp[i])
    med = sorted(exp)[n // 2]
    for p in PACK_CHOICES:
        # with pack p each object gets chunks*(rows/p)*128*unroll
        # trials per launch; take the largest p that still covers the
        # median object's expected work in ~one launch
        if p <= n and med * p <= DEFAULT_PACKED_CHUNKS * tile_step:
            return BatchPlan("packed", p, DEFAULT_PACKED_CHUNKS, order)
    return BatchPlan("batched", 1, DEFAULT_BATCH_CHUNKS, order)


# ---------------------------------------------------------------------------
# dispatch-ahead driver
# ---------------------------------------------------------------------------


class _PipelineDriver:
    """Generic dispatch-ahead loop: keep up to ``depth`` slabs in
    flight, harvesting the oldest while newer ones run on device.

    ``next_launch()`` returns an opaque (tag, device_future) pair or
    None when no work remains; ``harvest(tag, host_result)`` consumes
    one finished slab.  ``fetch`` pulls a device value to the host
    (the blocking transfer whose wait time is the device-busy proxy).
    """

    def __init__(self, *, depth: int = 2,
                 should_stop: Callable[[], bool] | None = None,
                 fetch=None, stall_timeout: float = 0.0,
                 kind: str = "batch"):
        import numpy as np

        def default_fetch(dev):
            # chaos site: a failed/poisoned device->host transfer
            inject("pow.readback")
            return np.asarray(dev)

        self.depth = max(1, depth)
        self.should_stop = should_stop
        self.fetch = fetch or default_fetch
        #: per-harvest stall deadline (0 disables the watchdog); a
        #: wedged transfer raises SlabStallError out of run(), which
        #: the dispatcher treats as a tier failure and requeues the
        #: batch to the next ladder tier
        self.stall_timeout = stall_timeout
        #: one reusable guard worker per driver — the guarded path must
        #: not pay a thread spawn per harvest; only a stall abandons it
        #: (the wedged thread keeps the old executor, a fresh one takes
        #: over)
        self._guard_pool = None
        #: label of this driver's launches in the pipeline counters
        self.kind = kind
        self.wait_seconds = 0.0
        #: blocking wait of the latest fetch (its ``pow.fetch`` span)
        self.last_wait = 0.0
        self.wall_seconds = 0.0
        self.slabs = 0

    def _fetch(self, dev):
        with trace("pow.fetch") as span:
            host = self._guarded_fetch(dev)
        self.last_wait = span.duration
        self.wait_seconds += span.duration
        DEVICE_WAIT.observe(span.duration)
        return host

    def _guarded_fetch(self, dev):
        if not self.stall_timeout or self.stall_timeout <= 0:
            return self.fetch(dev)
        import concurrent.futures as cf
        if self._guard_pool is None:
            self._guard_pool = cf.ThreadPoolExecutor(
                1, thread_name_prefix="bmtpu-pow-slab-guard")
        fut = self._guard_pool.submit(self.fetch, dev)
        try:
            return fut.result(self.stall_timeout)
        except cf.TimeoutError:
            STALLS.labels(site="pow.slab").inc()
            # black box: dump the ring while the pre-stall context
            # (launches, breaker flips, chaos fires) is still in it
            from ..observability.flightrec import FLIGHT_RECORDER
            FLIGHT_RECORDER.record("stall", site="pow.slab",
                                   timeout=self.stall_timeout)
            FLIGHT_RECORDER.dump("stall")
            logger.error("pow.slab stalled: harvest exceeded %.1fs; "
                         "abandoning the launch and falling back",
                         self.stall_timeout)
            # consume whatever the wedged worker eventually produces so
            # its late exception is not reported as never-retrieved
            fut.add_done_callback(lambda f: f.exception())
            self._guard_pool.shutdown(wait=False)
            self._guard_pool = None
            raise SlabStallError(
                "pow.slab exceeded %.1fs stall deadline"
                % self.stall_timeout)

    def run(self, next_launch, harvest, done=None) -> None:
        inflight: deque = deque()
        t_start = time.monotonic()
        try:
            while True:
                if done is not None and done():
                    # every result is in: any remaining in-flight slab
                    # is pure speculation — abandon it unfetched (the
                    # device finishes it in the background) instead of
                    # paying a blocking readback for nothing
                    if inflight:
                        ABANDONED_LAUNCHES.labels(kind=self.kind).inc(
                            len(inflight))
                        inflight.clear()
                    break
                if self.should_stop is not None and self.should_stop():
                    # drain what is already in flight — a pending slab
                    # may hold the answer the caller checkpoints on
                    while inflight:
                        tag, dev = inflight.popleft()
                        harvest(tag, self._fetch(dev))
                    raise PowInterrupted("pipelined PoW interrupted")
                while len(inflight) < self.depth:
                    nxt = next_launch()
                    if nxt is None:
                        break
                    inflight.append(nxt)
                    self.slabs += 1
                    LAUNCHES.labels(kind=self.kind).inc()
                    PIPELINE_DEPTH.set(len(inflight))
                    _flight("slab_launch", n=self.slabs,
                            inflight=len(inflight))
                if not inflight:
                    break
                DISPATCH_AHEAD.observe(len(inflight))
                tag, dev = inflight.popleft()
                host = self._fetch(dev)
                PIPELINE_DEPTH.set(len(inflight))
                _flight("slab_harvest",
                        wait_ms=round(self.last_wait * 1e3, 2),
                        inflight=len(inflight))
                harvest(tag, host)
        finally:
            PIPELINE_DEPTH.set(0)
            if self._guard_pool is not None:
                self._guard_pool.shutdown(wait=False)
                self._guard_pool = None
            self.wall_seconds = max(time.monotonic() - t_start, 1e-9)
            DEVICE_BUSY.set(self.busy_ratio)

    @property
    def busy_ratio(self) -> float:
        if self.wall_seconds <= 0:
            return 0.0
        return min(self.wait_seconds / self.wall_seconds, 1.0)


# ---------------------------------------------------------------------------
# the pipelined batch solve (production entry)
# ---------------------------------------------------------------------------


class _LaunchGroup:
    """Host state for one launch-wide slab group (``width`` objects)."""

    __slots__ = ("idx", "ih_words", "targets", "t_arr", "bases",
                 "trials", "done", "launches", "width")

    def __init__(self, items, idx, width, starts=None):
        import numpy as np

        pad = width - len(idx)
        ihs = [items[i][0] for i in idx] + [b"\x00" * 64] * pad
        self.targets = ([items[i][1] & _MASK64 for i in idx]
                        + [_ALWAYS_HIT] * pad)
        words = [[int.from_bytes(ih[j:j + 8], "big")
                  for j in range(0, 64, 8)] for ih in ihs]
        self.ih_words = jnp.array(
            [[[w >> 32, w & 0xFFFFFFFF] for w in ws] for ws in words],
            dtype=U32)
        self.t_arr = np.array(
            [[t >> 32, t & 0xFFFFFFFF] for t in self.targets],
            dtype=np.uint32)
        self.idx = list(idx)
        self.width = width
        # resumable PoW: each object's search starts at its journaled
        # checkpoint offset instead of 0 (pad slots stay at 0)
        self.bases = ([(starts[i] if starts else 0) & _MASK64
                       for i in idx] + [0] * pad)
        self.trials = [0] * width
        self.done = [i >= len(idx) for i in range(width)]
        self.launches = 0

    @property
    def finished(self) -> bool:
        return all(self.done)

    def live(self) -> int:
        return sum(1 for d in self.done if not d)


def _pow2_at_least(n: int, cap: int) -> int:
    p = 1
    while p < n and p < cap:
        p *= 2
    return min(p, cap)


def solve_batch_pipelined(items, *, rows: int = DEFAULT_ROWS,
                          unroll: int = 1, depth: int = 2,
                          impl: str | None = None,
                          interpret: bool = False,
                          plan: BatchPlan | None = None,
                          stats: dict | None = None,
                          should_stop: Callable[[], bool] | None = None,
                          start_nonces=None, progress=None,
                          stall_timeout: float = 0.0):
    """Solve ``[(initial_hash, target), ...]`` through the async
    double-buffered pipeline.  Returns ``[(nonce, trials), ...]``
    aligned with ``items``; raises :class:`PowInterrupted` on
    shutdown.

    Mode selection (see :func:`plan_batch`): a storm of small objects
    runs packed (up to ``PACKED_GROUPS_MAX * pack`` objects per
    launch), network-difficulty batches run the per-object batch
    kernel geometry (full tile per object), and a single tiny object
    takes one synchronous latency-optimal launch with no speculative
    dispatch.  Every returned nonce is host re-verified.  ``stats``
    (optional dict) receives executed-trials/launch/wall accounting:
    per-object ``trials`` in the results credit only the lanes the
    object itself searched, while ``stats["executed_trials"]``
    estimates total device hashing including straggler and pad waste —
    the two diverge exactly where packing removes waste.

    Resilience hooks (docs/resilience.md): ``start_nonces`` resumes
    each object from a checkpointed offset; ``progress(i, next)`` is
    invoked at every harvest with the end of the slab range just
    proven miss-free for item ``i`` (safe resume point — speculative
    dispatch-ahead never moves a checkpoint before its slab is
    harvested); ``stall_timeout > 0`` bounds each harvest's blocking
    device wait.
    """
    import numpy as np

    n = len(items)
    if n == 0:
        return []
    if impl is None:
        impl = default_impl()
    if plan is None:
        with trace("pow.plan", objects=n) as span:
            plan = plan_batch(items, rows=rows, unroll=unroll)
            span.attrs.update(mode=plan.mode, chunks=plan.chunks)
    PIPELINE_MODE.labels(mode=plan.mode).inc()

    if plan.mode == "single-sync":
        return [_solve_single_sync(
            items[0], rows=rows, unroll=unroll,
            chunks=plan.chunks, impl=impl, interpret=interpret,
            should_stop=should_stop,
            start_nonce=(start_nonces[0] if start_nonces else 0),
            progress=(None if progress is None
                      else (lambda nxt: progress(0, nxt))))]

    if plan.mode == "packed":
        pack = plan.pack
        # one launch carries groups*pack objects on the leading grid
        # axis — the storm's launch-overhead amortization
        n_groups = _pow2_at_least(-(-n // pack), PACKED_GROUPS_MAX)
        width = n_groups * pack
        step_trials = (rows // pack) * LANE_COLS * unroll
        kind = "packed"
    else:
        from ..ops.sha512_pallas import BATCH_OBJS, BATCH_UNROLL
        pack = 1
        width = BATCH_OBJS
        unroll = BATCH_UNROLL if impl == "pallas" else unroll
        step_trials = rows * LANE_COLS * unroll
        kind = "batch"
    slab_trials = step_trials * plan.chunks     # per object per launch

    # device-telemetry attribution: which jitted program this plan
    # actually launches, plus the static-shape key that decides
    # compile-vs-cache (mirrors each kernel's static_argnames)
    if impl != "pallas":
        tele_prog = "packed_search_xla"
        tele_key = (step_trials, plan.chunks)
    elif plan.mode == "packed":
        tele_prog = "packed_search"
        tele_key = (rows, plan.chunks, pack, unroll, interpret)
    else:
        tele_prog = "batch_search"
        tele_key = (rows, plan.chunks, unroll, interpret)

    with trace("pow.groups", objects=n, width=width):
        groups = [
            _LaunchGroup(items, plan.order[s:s + width], width,
                         starts=start_nonces)
            for s in range(0, n, width)
        ]
    results: list = [None] * n
    executed = {"trials": 0, "launches": 0}

    def search(g: _LaunchGroup):
        bases = np.array(
            [[(b >> 32) & 0xFFFFFFFF, b & 0xFFFFFFFF] for b in g.bases],
            dtype=np.uint32)
        if impl != "pallas":
            return _packed_search_xla(
                g.ih_words, jnp.asarray(bases), jnp.asarray(g.t_arr),
                lanes=step_trials, chunks=plan.chunks)
        if plan.mode == "packed":
            return pallas_packed_search(
                g.ih_words, jnp.asarray(bases), jnp.asarray(g.t_arr),
                rows=rows, chunks=plan.chunks, pack=pack, unroll=unroll,
                interpret=interpret)
        from ..ops.sha512_pallas import pallas_batch_search
        out = pallas_batch_search(
            g.ih_words, jnp.asarray(bases), jnp.asarray(g.t_arr),
            rows=rows, chunks=plan.chunks, unroll=unroll,
            interpret=interpret)
        return out

    rr = {"i": 0}
    inflight_groups: set = set()

    def next_launch():
        cand = None
        # round-robin over unfinished groups without an in-flight slab
        for off in range(len(groups)):
            g = groups[(rr["i"] + off) % len(groups)]
            if not g.finished and id(g) not in inflight_groups:
                cand = g
                rr["i"] = (rr["i"] + off + 1) % len(groups)
                break
        if cand is None:
            # speculate one slab ahead on a group that already proved
            # it needs more than one launch
            for g in groups:
                if not g.finished and g.launches >= 1:
                    cand = g
                    break
        if cand is None:
            return None
        live = cand.live()
        if plan.mode == "packed":
            # pack statistics describe lane sharing, which only the
            # packed kernel does — batched launches must not dilute
            # them (docs/observability.md semantics)
            PACK_SIZE.observe(live)
            PACK_OCCUPANCY.set(live / cand.width)
        with trace("pow.launch", program=tele_prog, chunks=plan.chunks,
                   live=live) as span:
            out = search(cand)
        t0, t1 = span.start, span.end
        inflight_groups.add(id(cand))
        cand.launches += 1
        executed["launches"] += 1
        for k in range(cand.width):
            if not cand.done[k]:
                cand.bases[k] = (cand.bases[k] + slab_trials) & _MASK64
        # snapshot of each object's post-slab offset: the safe resume
        # point to checkpoint once THIS slab harvests miss-free (the
        # live ``bases`` may already include speculative launches)
        end_bases = list(cand.bases)
        return ((cand, t0, t1, end_bases), out)

    def harvest(tag, out):
        with trace("pow.harvest") as span:
            _harvest(tag, out, span.start)

    def _harvest(tag, out, t_h):
        g, t0, t1, end_bases = tag
        inflight_groups.discard(id(g))
        before = executed["trials"]
        _record_pipeline_launch = functools.partial(
            record_launch, tele_prog, key=tele_key,
            dispatch_seconds=t1 - t0,
            # the driver fetched this slab just before calling us
            wait_seconds=driver.last_wait,
            span=(t0, t_h), bytes_in=16 * g.width,
            bytes_out=12 * g.width,
            # the packed Mosaic kernel donates its base/target input
            # buffers (see _solve_single_sync's fresh-per-iteration
            # note); XLA and batch launches keep theirs
            bytes_donated=(16 * g.width
                           if impl == "pallas" and plan.mode == "packed"
                           else 0))
        for k in range(g.width):
            if g.done[k]:
                # solved/pad slots still executed one always-hit step
                executed["trials"] += step_trials
                continue
            step1 = int(out[k, 0])
            if step1:
                g.trials[k] += step1 * step_trials
                executed["trials"] += step1 * step_trials
                nonce = (int(out[k, 1]) << 32) | int(out[k, 2])
                ih = items[g.idx[k]][0]
                check = double_sha512(nonce.to_bytes(8, "big") + ih)
                if int.from_bytes(check[:8], "big") > g.targets[k]:
                    raise ArithmeticError(
                        "accelerator returned an invalid PoW nonce")
                results[g.idx[k]] = (nonce, g.trials[k])
                g.done[k] = True
                # pad semantics: always-hit next launch, then idle
                g.t_arr[k] = (0xFFFFFFFF, 0xFFFFFFFF)
            else:
                g.trials[k] += slab_trials
                executed["trials"] += slab_trials
                if progress is not None:
                    # this slab proved [prev, end_bases[k]) miss-free:
                    # a resumed search may safely start there
                    progress(g.idx[k], end_bases[k])
        EXECUTED_TRIALS.labels(kind=kind).inc(executed["trials"] - before)
        _record_pipeline_launch(items=executed["trials"] - before)

    driver = _PipelineDriver(depth=depth, should_stop=should_stop,
                             stall_timeout=stall_timeout, kind=kind)
    try:
        driver.run(next_launch, harvest,
                   done=lambda: all(r is not None for r in results))
    except PowInterrupted:
        if any(r is None for r in results):
            raise
    if stats is not None:
        stats.update(
            mode=plan.mode, pack=pack, width=width, chunks=plan.chunks,
            launches=executed["launches"],
            executed_trials=executed["trials"],
            credited_trials=sum(r[1] for r in results),
            wall_seconds=driver.wall_seconds,
            device_busy_ratio=driver.busy_ratio)
    return results


def _solve_single_sync(item, *, rows: int, unroll: int, chunks: int,
                       impl: str, interpret: bool,
                       should_stop: Callable[[], bool] | None,
                       start_nonce: int = 0, progress=None):
    """Latency-optimal degenerate path: one object, small synchronous
    launches, no speculative dispatch-ahead (an extra in-flight slab
    would only delay the answer for work expected to finish in the
    first launch)."""
    import numpy as np

    initial_hash, target = item
    target &= _MASK64
    words = [int.from_bytes(initial_hash[i:i + 8], "big")
             for i in range(0, 64, 8)]
    ih_words = jnp.array([[[w >> 32, w & 0xFFFFFFFF] for w in words]],
                         dtype=U32)
    step_trials = rows * LANE_COLS * unroll
    slab_trials = step_trials * chunks

    base = start_nonce & _MASK64
    trials = 0
    while True:
        if should_stop is not None and should_stop():
            raise PowInterrupted("pipelined PoW interrupted")
        b_arr = jnp.array([[(base >> 32) & 0xFFFFFFFF,
                            base & 0xFFFFFFFF]], dtype=U32)
        # fresh per-iteration (not hoisted): the packed kernel donates
        # its base/target buffers
        t_arr = jnp.array([[target >> 32, target & 0xFFFFFFFF]],
                          dtype=U32)
        with trace("pow.launch", chunks=chunks, live=1,
                   program=("packed_search" if impl == "pallas"
                            else "packed_search_xla")) as launch:
            if impl == "pallas":
                out = pallas_packed_search(
                    ih_words, b_arr, t_arr, rows=rows, chunks=chunks,
                    pack=1, unroll=unroll, interpret=interpret)
            else:
                out = _packed_search_xla(ih_words, b_arr, t_arr,
                                         lanes=step_trials, chunks=chunks)
        LAUNCHES.labels(kind="single-sync").inc()
        with trace("pow.fetch") as fetch:
            inject("pow.readback")
            out = np.asarray(out)
        t0, t1, t2 = launch.start, launch.end, fetch.end
        if impl == "pallas":
            record_launch("packed_search",
                          key=(rows, chunks, 1, unroll, interpret),
                          dispatch_seconds=t1 - t0, wait_seconds=t2 - t1,
                          span=(t0, t2), items=slab_trials, bytes_in=16,
                          bytes_out=int(out.nbytes), bytes_donated=16)
        else:
            record_launch("packed_search_xla",
                          key=(step_trials, chunks),
                          dispatch_seconds=t1 - t0, wait_seconds=t2 - t1,
                          span=(t0, t2), items=slab_trials, bytes_in=16,
                          bytes_out=int(out.nbytes))
        step1 = int(out[0, 0])
        EXECUTED_TRIALS.labels(kind="single-sync").inc(
            step1 * step_trials if step1 else slab_trials)
        if step1:
            trials += step1 * step_trials
            nonce = (int(out[0, 1]) << 32) | int(out[0, 2])
            check = double_sha512(nonce.to_bytes(8, "big") + initial_hash)
            if int.from_bytes(check[:8], "big") > target:
                raise ArithmeticError(
                    "accelerator returned an invalid PoW nonce")
            return nonce, trials
        trials += slab_trials
        base = (base + slab_trials) & _MASK64
        if progress is not None:
            progress(base)


def pipeline_snapshot() -> dict:
    """Pipeline gauges for clientStatus / bench (one JSON-able dict)."""
    return {
        "deviceBusyRatio": round(
            REGISTRY.sample("pow_pipeline_device_busy_ratio"), 4),
        "depth": REGISTRY.sample("pow_pipeline_depth"),
        "packOccupancy": round(
            REGISTRY.sample("pow_pack_occupancy_ratio"), 4),
    }
