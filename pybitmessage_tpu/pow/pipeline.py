"""Plan and driver of the PoW search on an accelerator's chips: which
kernel at which shape serves a batch, which chip each launch group
lives on, and the one host loop that launches it.

The send path below ``PowService`` is three boxes whose arrows point
down (docs/pow_pipeline.md):

* ``pow/dispatcher.py`` — the ladder: which rung, and is it healthy;
* this module — :func:`plan_batch` (the only place that says which
  kernel and which static shape serve a batch, a lone object included)
  and :class:`_PipelineDriver` (the only dispatch-ahead loop, over
  one chip or several, with the one speculation rule, the stall
  watchdog, the ``pow.*`` spans and the launch counters);
* ``ops/sha512_pallas.py`` — the jitted kernels, and nothing else.

Four plan modes, one loop:

``slab``         one object alone at network difficulty (a send of
                 ``single_send``): ``pallas_search`` at 128 x 512 x 5,
                 a second slab in flight only for an object hard
                 enough to be unlikely to hit in the first; on several
                 chips every chip searches a share of the nonce space
                 of its own and the first hit wins: as ONE program over
                 the chips, whose kernels stop at the winner's flag
                 (``sha512_ici.ici_search``, 512 steps a chip; its XLA
                 equivalent under ``impl="xla"``); the Mosaic kernels'
                 stand-ins on the tests' virtual CPU devices alone
                 keep a launch a chip in shorter slabs;
``batched``      a queue of objects (``chan_storm_256``): the
                 per-object grid ``pallas_batch_search``, 64 objects a
                 launch at 1,024 steps of one tile of 64 rows;
``packed``       a storm of tiny objects sharing tiles along the lane
                 axis (``pallas_packed_search``);
``single-sync``  one tiny object: the packed kernel at pack 1, one
                 launch at a time (the speculation rule would withhold
                 the second by itself now: ROADMAP D11).

A ``batched`` solve is a stream (docs/pow_pipeline.md): an object
leaves it when its nonce has passed the hashlib re-check
(``on_solved``), and before a group's next launch each of its done
slots takes an object that has arrived since (``feed``).  A queue of at
most one launch's objects is laid out as two groups, so two launches
alternate and none is dispatched ahead of an unread one.  A solve that
is told how many objects to ``expect`` is planned and laid out for that
many: it may start with the first member of a sweep, the slots of the
members still to come start as pad slots, and ``feed`` fills them.
Given several ``devices`` the launch groups are dealt over them, at
least two a device: a group's arrays and launches stay on its chip, an
object's own nonce range with them, and each chip has its own launches
in flight.  A chip that has run out takes a nonce-range copy of
another chip's unresolved object, one a turn; the first slot to hit
resolves the object and every other slot of it is cancelled
(docs/pow_pipeline.md, "A solve placed over several chips").  An
object that is alone there (mode ``slab``) is laid out over every chip
from its first launch on, the same way: one slot a chip, each at its
own :func:`_copy_base`; where the plan says ``one_program``
(:func:`_one_program`) its slots are ONE group and a launch ONE program
over the devices.

``chunks`` is a static argument of every Mosaic kernel, so each value
is a program of its own to trace, lower and compile, and one the chip
may refuse (``pallas_search`` at 1024 chunks needs more SMEM than a
v5e has).  Each mode therefore has ONE chunk count, measured on the
chip (PERF.md section 6, PR 27); :class:`SlabAutotuner` sizes the XLA
tier's slabs only (``ops.pow_search.solve``), where a shape is cheap.

The kernels that the benchmark's launch log and the tests replace
(``pallas_search``, ``pallas_batch_search``, ``sha512_ici.ici_search``)
are looked up on their modules at every launch, never bound here by
name.

On hosts without an accelerator (the CI virtual CPU mesh) the Mosaic
kernels are replaced by an XLA equivalent with the identical
(objects, 3)-row output contract (``impl="xla"``;
:func:`_ici_search_xla` with ``ici_search``'s row a device for a lone
object on several), so planning, the driver and the metrics are
exercised without a TPU — the same pattern
``parallel/pow_pallas_sharded.py`` uses.
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

from ..observability import DEFAULT_SIZE_BUCKETS, REGISTRY, interval, trace
from ..observability.devicetelemetry import (POW_FLOPS_PER_HASH,
                                             record_launch,
                                             register_program)
from ..observability.flightrec import record as _flight
from ..ops.pow_search import PowInterrupted
from ..resilience.chaos import inject
from ..resilience.watchdog import STALLS, SlabStallError
from ..ops import sha512_ici, sha512_pallas
from ..ops.sha512_jax import double_sha512_trial
from ..ops.sha512_pallas import (BATCH_OBJS, BATCH_ROWS, BATCH_UNROLL,
                                 DEFAULT_CHUNKS,
                                 DEFAULT_ROWS, DEFAULT_UNROLL, LANE_COLS,
                                 pallas_packed_search)
from ..ops.u64 import U32
from ..utils.hashes import double_sha512

logger = logging.getLogger("pybitmessage_tpu.pow")

_MASK64 = (1 << 64) - 1
#: always-hit target for pad slots (every trial value is <= 2^64-1)
_ALWAYS_HIT = _MASK64

DEVICE_BUSY = REGISTRY.gauge(
    "pow_pipeline_device_busy_ratio",
    "Fraction of the last pipelined solve's lane time (its devices "
    "times its wall time) in which a device had a launch in flight: "
    "that solve's inflight seconds of pow_pipeline_lane_seconds_total "
    "over all three states'")
#: what a device's lane is doing while a solve is under way; the last
#: two are intervals in the profiler's trace (``_Lane``)
LANE_STATES = ("inflight", "turn", "starved")
LANE_SECONDS = REGISTRY.counter(
    "pow_pipeline_lane_seconds_total",
    "Host-clock seconds the devices of the pipelined solves spent in "
    "each lane state: a launch in flight (inflight), the queue empty "
    "and the host loop yet to launch or to find nothing (turn), "
    "nothing left to search on that device (starved); over one solve "
    "the three add up to its devices times its wall time",
    ("device", "state"))
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
DEVICE_LAUNCHES = REGISTRY.counter(
    "pow_pipeline_device_launches_total",
    "Search-kernel launches dispatched by the pipeline's host loop, by "
    "the device of the launch group (its index among the devices the "
    "solve was given; 0 where it was given none)", ("device",))
ABANDONED_LAUNCHES = REGISTRY.counter(
    "pow_pipeline_abandoned_launches_total",
    "Speculative launches dispatched and never fetched because every "
    "result was already in (the device still runs them)", ("kind",))
SPECULATION = REGISTRY.counter(
    "pow_pipeline_speculation_total",
    "Decisions of the speculation rule: each time no group without an "
    "unread launch was left and an unfinished one had a launch in "
    "flight, its next launch was dispatched ahead (launched) or held "
    "back until that one is read (withheld)", ("kind", "decision"))
REFILLS = REGISTRY.counter(
    "pow_pipeline_refills_total",
    "Objects a running solve took from the queue into a slot whose "
    "object had solved (or a pad slot), before the group's next launch",
    ("kind",))
COPIES = REGISTRY.counter(
    "pow_pipeline_copies_total",
    "Nonce-range copies of another device's unresolved object that a "
    "device with nothing live took into a done slot, credited when the "
    "copy's slot is retired: its own slot found the nonce (won) or "
    "another slot of the object did (cancelled); not in "
    "pow_pipeline_refills_total", ("kind", "outcome"))
LONE_WINS = REGISTRY.counter(
    "pow_pipeline_lone_wins_total",
    "Solves of one object laid out over several devices' lanes, by the "
    "lane whose launch found the nonce (0: the lane of the object's own "
    "range, which a resumed search goes on from)", ("lane",))
#: how a lane of a lone object's ONE program over several chips left
#: its launch (``ops/sha512_ici.py``)
LONE_OUTCOMES = ("won", "cancelled", "own_hit", "ran_out")
#: a row's ``why`` as the outcome of a lane whose hit was not taken
_LONE_LEFT = {sha512_ici.OWN_HIT: "own_hit",
              sha512_ici.CANCELLED: "cancelled",
              sha512_ici.RAN_OUT: "ran_out"}
LONE_LANES = REGISTRY.counter(
    "pow_pipeline_lone_lanes_total",
    "Lanes of the harvested launches of one object searched by several "
    "devices as ONE program whose kernels stop at the first hit, by how "
    "the lane left its launch: its hit is the one the host took (won), "
    "it read the flag the winner had raised (cancelled), it hit in the "
    "winner's step or before the flag reached it (own_hit), it ran "
    "every step (ran_out)", ("outcome",))
# every outcome exists at 0 from the start: "never" then reads 0, not
# "no such series"
for _outcome in LONE_OUTCOMES:
    LONE_LANES.labels(outcome=_outcome)
LONE_CANCEL_LAG = REGISTRY.histogram(
    "pow_pipeline_lone_cancel_lag_steps",
    "Grid steps a cancelled lane of such a launch ran past the step of "
    "the winner's hit, a lane an observation",
    buckets=(0.0,) + DEFAULT_SIZE_BUCKETS)
LONE_HEAD = REGISTRY.histogram(
    "pow_pipeline_lone_head_seconds",
    "From the entry of a solve of one object (mode slab) to the return "
    "of the last lane's first launch: what the host does before every "
    "device it was given searches; observed once a solve", ("lanes",))
SLOTS = REGISTRY.counter(
    "pow_pipeline_slots_total",
    "Slots of the launches dispatched: those that searched (live) and "
    "those that were solved or pad and cost one always-hit step (idle)",
    ("kind", "state"))
NEEDED_TRIALS = REGISTRY.counter(
    "pow_pipeline_needed_trials_total",
    "Trials of harvested launches that a search needed: a slot that "
    "missed, its whole slab (a copy's too: it proved a range empty); a "
    "slot that hit, up to its winning nonce; a solved or pad slot, or "
    "one whose object another slot had resolved by then, none; of a "
    "lone object's ONE program over several devices (kind slab), the "
    "winner's lane up to its nonce and every other lane up to the "
    "winner's step",
    ("kind",))
EXECUTED_TRIALS = REGISTRY.counter(
    "pow_pipeline_executed_trials_total",
    "Trials the device computed in harvested launches, counted by the "
    "grid steps each really ran (abandoned launches are not read "
    "back, so their trials are not in here; every lane of a lone "
    "object's ONE program over several devices reports its steps, the "
    "losers' too)", ("kind",))


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
    not come here: each has one measured shape (see
    :func:`plan_batch`).  The EWMA plus a 10x outlier clamp make one
    slow observation (a fresh jit compile, a host stall) decay instead
    of permanently shrinking slabs.  Thread-safe: the dispatcher's
    executor and the asyncio service may solve concurrently.
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


#: THE threshold of the speculation rule (:func:`worth_speculating`): a
#: group's next launch is dispatched ahead only while the chance that
#: its unread launches already finish it is below this.  From the costs
#: measured on the chip (PERF.md section 5, PR 29): a speculation that
#: was needed hides one host round trip R (``pow.harvest`` 0.85 ms +
#: ``pow.launch`` 0.97 ms + the transfer: 2-3 ms) behind the launch in
#: flight; one that was not leaves a launch on the device that runs to
#: its own first hit before the next solve can start, W (43 ms for a
#: lone object's slab, up to a whole batch launch of 144 ms for a
#: queue's stragglers).  Speculating pays while (1 - p) R > p W, that is
#: p < R / (R + W): 5.5 % for a slab, 1.7 % for a batch launch.  1/32
#: lies between, and the chances that occur lie far from it on either
#: side (a lone ack 0.98, a lone object of 4.9e9 trials 0.009 a slab, 64
#: objects in mid sweep 1e-14), so no mode needs a value of its own.
SPECULATE_BELOW = 1.0 / 32


def worth_speculating(covered: float, targets) -> bool:
    """Whether to dispatch a group's next launch before its unread
    ones are read: ``covered`` is the trials each live object is
    searched for in those unread launches, ``targets`` the live
    objects' targets.  The search is memoryless, so the chance that
    the unread launches finish every one of them is the product of
    ``1 - exp(-covered / expected_trials(t))``; True while that is
    below :data:`SPECULATE_BELOW`."""
    p_finish = 1.0
    for t in targets:
        p_finish *= -math.expm1(-covered / expected_trials(t))
        if p_finish < SPECULATE_BELOW:
            return True         # every further factor is at most 1
    return False


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


@functools.partial(jax.jit, static_argnames=("lanes", "chunks", "lag"))
def _ici_search_xla(operands, lanes: int, chunks: int, lag: int = 1):
    """Same output contract as ``sha512_ici.ici_search`` in pure XLA.

    ``operands`` is that entry's: a row a device (the words, that
    device's base, the target); each row scans ``chunks`` chunks of
    ``lanes`` consecutive nonces as :func:`_packed_search_xla` does.
    The rows whose hit lies in the earliest step hit (``OWN_HIT``),
    every other leaves ``lag`` steps past it (``CANCELLED``: on the
    chip a loser reads the flag at the next step it begins, PERF.md
    section 6, PR 49: 0.94 steps), and with no hit at all every row
    runs out.  Returns (devices, ``sha512_ici.ROW_WORDS``) uint32.
    """
    found = _packed_search_xla(
        operands[:, :16].reshape(-1, 8, 2), operands[:, 16:18],
        operands[:, 18:20], lanes=lanes, chunks=chunks)
    hit = jnp.where(found[:, 0] > 0, found[:, 0], chunks + 1)
    first = hit.min()
    own = (hit == first) & (first <= chunks)
    ran = jnp.where(own, hit, jnp.minimum(first + lag, chunks))
    why = jnp.where(own, sha512_ici.OWN_HIT, jnp.where(
        first <= chunks, sha512_ici.CANCELLED, sha512_ici.RAN_OUT))
    zero = jnp.zeros_like(hit)
    return jnp.stack(
        [jnp.where(own, hit, 0), jnp.where(own, found[:, 1], 0),
         jnp.where(own, found[:, 2], 0), ran, why, zero, zero, zero],
        axis=1).astype(U32)


register_program("packed_search_xla", flops_per_item=POW_FLOPS_PER_HASH,
                 module="pow/pipeline.py",
                 jit_names=("_packed_search_xla", "_ici_search_xla"))


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

#: pack-factor ladder: rows//pack stays >= 8 (one VPU sublane) at the
#: production row count
PACK_CHOICES = (16, 8, 4, 2)
#: grid steps of one packed launch; at pack=16 that is 8*128*chunks
#: trials per object per launch
DEFAULT_PACKED_CHUNKS = 64
#: steps an object of the per-object batch kernel: 1,024 of one tile of
#: 64 rows (``sha512_pallas.BATCH_ROWS``, ``BATCH_UNROLL``), 8,388,608
#: trials a launch as at the 128 steps of four tiles of 128 rows every
#: chip run of PR 24-39 launched (the tuner's 64 -> 128 in the first
#: sweep of a 256-object storm).  An object leaves at its hit and a
#: solved or pad slot after one step, so the step is what either throws
#: away: 8,192 trials since PR 40
DEFAULT_BATCH_CHUNKS = 1024
#: the most steps a launch of the XLA stand-in scans for a queue's
#: object (of 128 rows x 128 lanes): it has no early exit, so the Mosaic
#: kernel's 1,024 would cost a host without an accelerator sixteen
#: times the hashing for nothing
XLA_BATCH_CHUNKS = 128
#: leading-grid-axis cap of one packed launch: up to 64 tiles *
#: pack objects ride one kernel call (the storm's launch-overhead
#: amortization); group counts round up to powers of two so the
#: compile cache stays a short ladder per pack
PACKED_GROUPS_MAX = 64
#: a single object expected to finish inside this many full-tile grid
#: steps takes one small launch at a time (mode ``single-sync``)
SYNC_SINGLE_STEPS = 8
#: grid steps of ``pallas_search`` that the lanes of a lone object on
#: several chips launch TOGETHER where each lane is a launch of its own
#: (the lay-out of ``impl="pallas"`` on virtual CPU devices, that is
#: the tests'; everybody's until PR 49),
#: shared out evenly: 64 a chip on four (5.2e6 trials, 18 ms).  There a
#: lane's launch runs on to its OWN hit or its end after another lane
#: has won, and the next solve's launch on that chip queues behind it,
#: so a launch must not outlast the host's work between two solves by
#: much (7-8 ms in ``single_send``); all lanes together it still holds a
#: network-default object's expected work (1.1-1.6e7 trials) in one
#: launch each, and an object so hard that it needs many has its next
#: launch dispatched ahead.  Measured on four v5e chips, 200 lone
#: solves at ``single_send``'s difficulties, pairs of solves a second
#: by steps a lane: 32: 18.05, 64: 18.66, 128: 15.45, 512: 10.26; one
#: chip at 512: 9.06 (PERF.md section 6, PR 43).  Again with a round of
#: four launches at 2.7 ms of the host where it was 3.8
#: (``tools/lone_lanes_bench.py``, PR 44): 64: 20.58, 32: 20.26, 16:
#: 18.21.  Everywhere else the lanes are ONE program that stops at the
#: first hit (``ops/sha512_ici.py``), and a launch is as long as on one
#: chip (:func:`plan_batch`); this lay-out and its constant go with the
#: tests that hold it (ROADMAP D10)
LONE_LANES_CHUNKS = 256
#: launch groups a ``batched`` solve of at most one launch's objects
#: is laid out as: with two, the round-robin always finds a group with
#: no unread launch, so the device stays busy without speculation, as
#: it does under the storm's four (pad slots cost one step a launch)
MIN_BATCH_GROUPS = 2


class BatchPlan:
    """Which kernel at which static shape serves one batch (see
    :func:`plan_batch`)."""

    __slots__ = ("mode", "pack", "chunks", "order", "one_program")

    def __init__(self, mode: str, pack: int, chunks: int, order,
                 one_program: bool = False):
        self.mode = mode        # "slab" | "batched" | "packed" | "single-sync"
        self.pack = pack        # objects per tile (packed mode)
        self.chunks = chunks    # grid steps per launch
        self.order = order      # item indices, difficulty-sorted
        #: a lone object's lanes are ONE program over the devices that
        #: stops at the first hit, not a launch a lane (mode "slab")
        self.one_program = one_program

    def __repr__(self):  # pragma: no cover - debug aid
        return ("BatchPlan(mode=%r, pack=%d, chunks=%d, n=%d)"
                % (self.mode, self.pack, self.chunks, len(self.order)))


def plan_batch(items, *, rows: int = DEFAULT_ROWS,
               unroll: int = 1, expect: int = 0,
               lanes: int = 1, one_program: bool = False) -> BatchPlan:
    """Choose the kernel and its geometry from the batch's size and
    difficulty — the only place that does.  ``expect`` is the number of
    objects the solve is to be laid out for, where more are announced
    than are there: the mode is then that of a queue of ``expect``
    objects (one object with announced company is a queue, not a lone
    object), read from the targets that are there.  ``lanes`` is the
    number of devices the solve may be placed over, ``one_program``
    whether a lone object's lanes are launched as one program whose
    kernels stop at the first hit (:func:`_one_program`).

    One object alone searches whole slabs of ``pallas_search`` (mode
    ``slab``; on several ``lanes`` each searches a share of the nonce
    space: in slabs of ``LONE_LANES_CHUNKS / lanes`` steps where a lane
    is a launch of its own, in whole slabs where they are
    ``one_program``), or, when it
    is expected to finish inside ``SYNC_SINGLE_STEPS`` grid steps, takes
    one small launch at a time (``single-sync``).  For a queue the pack
    factor is sized so one
    launch covers roughly every object's expected work: tiny (storm)
    objects pack 16 per tile and network-default objects keep whole
    tiles (pack=1 -> the per-object batch kernel).  Objects are
    difficulty-sorted so each packed group is homogeneous (a straggler
    would otherwise hold its whole group's rows live).  Each mode has
    ONE chunk count, so a node compiles each kernel once and launches
    nothing the chip has not already accepted.
    """
    there = len(items)
    n = max(there, expect)
    exp = [expected_trials(t) for _, t in items]
    tile_step = rows * LANE_COLS * unroll      # full-tile trials/step
    if n == 1:
        if exp[0] <= SYNC_SINGLE_STEPS * tile_step:
            return BatchPlan("single-sync", 1, SYNC_SINGLE_STEPS, [0])
        # 512 is the largest power of two a v5e compiles for
        # pallas_search (1024 asks for 1.01M of its 1.00M of SMEM;
        # tests/test_tpu_compile.py), and the grid leaves at its first
        # hit, so a long slab costs a short solve nothing; on several
        # lanes that nothing stops it costs the next solve the losers'
        # run to their own hits, so their slabs are short
        chunks = DEFAULT_CHUNKS if lanes == 1 or one_program else max(
            LONE_LANES_CHUNKS // lanes, 1)
        return BatchPlan("slab", 1, chunks, [0],
                         one_program=one_program and lanes > 1)
    order = sorted(range(there), key=lambda i: exp[i])
    med = sorted(exp)[there // 2]
    for p in PACK_CHOICES:
        # with pack p each object gets chunks*(rows/p)*128*unroll
        # trials per launch; take the largest p that still covers the
        # median object's expected work in ~one launch
        if p <= n and med * p <= DEFAULT_PACKED_CHUNKS * tile_step:
            return BatchPlan("packed", p, DEFAULT_PACKED_CHUNKS, order)
    return BatchPlan("batched", 1, DEFAULT_BATCH_CHUNKS, order)


#: what each plan mode is called in the launch counters (``kind``)
_KIND = {"slab": "slab", "batched": "batch", "packed": "packed",
         "single-sync": "single-sync"}


# ---------------------------------------------------------------------------
# dispatch-ahead driver
# ---------------------------------------------------------------------------


#: ((program, static shape), device lane) pairs this process has
#: launched once: a program is lowered and compiled anew for each
#: device it runs on
_TRACED_SHAPES: set = set()
#: lanes -> the guard workers of solves that are over, for the next
#: solve on as many lanes to take: a lone object's solve lasts a dozen
#: milliseconds, and starting five threads for it and waking them to
#: end them cost it half of one (PERF.md section 6, PR 44)
_IDLE_GUARDS: dict = {}


class _PipelineDriver:
    """Generic dispatch-ahead loop: keep up to ``depth`` slabs in
    flight on each of ``lanes`` devices, harvesting a device's oldest
    while its newer ones run.

    ``next_launch(lane)`` returns an opaque (tag, device_future) pair
    for device ``lane`` or None when nothing is to be launched there
    now; ``harvest(tag, host_result)`` consumes one finished slab;
    ``load(lane)`` says how much a device has to do (its live slots),
    and of the devices with as many launches in flight the one with
    the least is asked first.
    ``fetch`` pulls a device value to the host (the blocking transfer
    whose wait time is the device-busy proxy).  A device's launches
    come in in the order they went out; with several devices the
    oldest launch of each is fetched on a thread of its own and the
    first to come in is harvested, so no device that has run out waits
    for another's launch to be read.

    While ``run`` is under way every lane is in one of
    :data:`LANE_STATES` (:class:`_Lane`); ``devices`` are the JAX ids
    of the lanes' devices, which name their planes in a profiler trace
    (the lanes' indices where none are given).  More ``devices`` than
    ``lanes`` are shared out evenly: a lane's launches are then ONE
    program over its devices (a lone object's, ``ops/sha512_ici.py``),
    and every one of them is in the lane's state.
    """

    def __init__(self, *, depth: int = 2, lanes: int = 1,
                 should_stop: Callable[[], bool] | None = None,
                 fetch=None, stall_timeout: float = 0.0,
                 kind: str = "batch", shape=None, devices=None):
        import numpy as np

        def default_fetch(dev):
            # chaos site: a failed/poisoned device->host transfer
            inject("pow.readback")
            return np.asarray(dev)

        self.depth = max(1, depth)
        self.lanes = max(1, lanes)
        self.should_stop = should_stop
        self.fetch = fetch or default_fetch
        #: per-harvest stall deadline (0 disables the watchdog); a
        #: wedged transfer raises SlabStallError out of run(), which
        #: the dispatcher treats as a tier failure and requeues the
        #: batch to the next ladder tier
        self.stall_timeout = max(stall_timeout or 0.0, 0.0)
        #: reusable guard workers: with several devices one a device
        #: and one for a first launch beside their fetches, and as many
        #: again, since the workers outlive the solve (``_IDLE_GUARDS``)
        #: and the fetch of a launch that was abandoned unread holds
        #: its worker until that launch ends on the device, which may
        #: be after the next solve has begun; with one device one
        #: worker for both (no fetch is out while the loop launches) —
        #: the guarded path must not pay a thread spawn per harvest, nor
        #: per solve; only a stall abandons them (the wedged thread
        #: keeps the old executor, a fresh one takes over).  None: one
        #: device and no watchdog, everything runs in place
        self._guard_pool = None
        #: lane -> (fetch of that device's oldest launch, its deadline)
        self._fetching: dict = {}
        #: label of this driver's launches in the pipeline counters
        self.kind = kind
        #: (program, static shape) of the launches, if the caller knows:
        #: the first launch of a shape on a device traces and lowers
        #: the kernel (see :meth:`_on_device_thread`)
        self.shape = shape
        self.devices = list(devices) if devices else list(range(self.lanes))
        #: the lanes' states while ``run`` is under way
        self._lanes: list = []
        #: state -> seconds of the latest ``run``, its lanes summed:
        #: together ``lanes`` times ``wall_seconds``
        self.lane_seconds = dict.fromkeys(LANE_STATES, 0.0)
        #: blocking wait of the latest fetch (its ``pow.fetch`` span)
        self.last_wait = 0.0
        self.wall_seconds = 0.0
        self.slabs = 0

    @property
    def _guarded(self) -> bool:
        return self.lanes > 1 or self.stall_timeout > 0

    def _submit(self, fn, *args):
        import concurrent.futures as cf
        import contextvars
        if self._guard_pool is None:
            try:
                self._guard_pool = _IDLE_GUARDS[self.lanes].pop()
            except (KeyError, IndexError):
                self._guard_pool = cf.ThreadPoolExecutor(
                    2 * self.lanes + 1 if self.lanes > 1 else 1,
                    thread_name_prefix="bmtpu-pow-slab-guard")
        return self._guard_pool.submit(contextvars.copy_context().run,
                                       fn, *args)

    def _stalled(self) -> SlabStallError:
        timeout = self.stall_timeout
        STALLS.labels(site="pow.slab").inc()
        # black box: dump the ring while the pre-stall context
        # (launches, breaker flips, chaos fires) is still in it
        from ..observability.flightrec import FLIGHT_RECORDER
        FLIGHT_RECORDER.record("stall", site="pow.slab", timeout=timeout)
        FLIGHT_RECORDER.dump("stall")
        logger.error("pow.slab stalled: harvest exceeded %.1fs; "
                     "abandoning the launch and falling back", timeout)
        self._drop_guards()
        return SlabStallError(
            "pow.slab exceeded %.1fs stall deadline" % timeout)

    def _drop_guards(self, wedged: bool = True) -> None:
        """Leave the workers behind: a ``wedged`` one with its
        executor, those of a solve that is over for the next solve."""
        for fut, _deadline in self._fetching.values():
            # consume whatever a worker eventually produces so its late
            # exception is not reported as never-retrieved
            fut.add_done_callback(lambda f: f.exception())
        self._fetching.clear()
        pool, self._guard_pool = self._guard_pool, None
        if pool is None:
            return
        if wedged:
            pool.shutdown(wait=False)
        else:
            _IDLE_GUARDS.setdefault(self.lanes, []).append(pool)

    def _first_in(self, queues):
        """Take the launch that comes in first among each device's
        oldest: ``(tag, host_result)``.  One device is fetched as it
        always was: in place with the watchdog off, else on the guard
        worker with ``stall_timeout`` seconds to come in.  With several
        each oldest launch has a fetch running on a guard worker, and
        one that is not in ``stall_timeout`` seconds after its fetch
        began is a stall."""
        import concurrent.futures as cf
        with trace("pow.fetch") as span:
            if self.lanes == 1:
                lane, host = 0, self._on_device_thread(
                    self.fetch, queues[0][0][2], timeout=self.stall_timeout)
            else:
                for k, q in enumerate(queues):
                    if q and k not in self._fetching:
                        self._fetching[k] = (
                            self._submit(self.fetch, q[0][2]),
                            time.monotonic() + self.stall_timeout)
                due = min(d for _f, d in self._fetching.values())
                cf.wait([f for f, _d in self._fetching.values()],
                        max(due - time.monotonic(), 0)
                        if self.stall_timeout else None,
                        return_when=cf.FIRST_COMPLETED)
                # of those that are in, the one launched first
                lane = min((k for k, (f, _d) in self._fetching.items()
                            if f.done()),
                           key=lambda k: queues[k][0][0], default=None)
                if lane is None:
                    raise self._stalled()
                host = self._fetching.pop(lane)[0].result()
            span.attrs["device"] = lane
        self.last_wait = span.duration
        DEVICE_WAIT.observe(span.duration)
        tag = queues[lane].popleft()[1]
        if not queues[lane]:
            # nothing queued behind it: the device has nothing to run
            # until this loop has harvested and come round to the lane
            self._lanes[lane].enter("turn")
        return tag, host

    def _on_device_thread(self, fn, *args, timeout=None):
        """``fn(*args)`` on one of this driver's worker threads, the
        caller's span context with it; not back in ``timeout`` seconds
        (0 or None: no deadline) it is a stall.  With one device and
        the watchdog off (``stall_timeout`` 0) it is called in place.

        One device's fetches run there so that a wedged transfer can be
        left behind.  So does the first launch of a shape on a device,
        with no deadline (a cold compile is inside it): it traces and
        lowers the kernel, and how long CPython 3.12 takes over that
        depends on how deep the calling thread's frames already are —
        every call that crosses a 16 KiB boundary of the thread's frame
        stack frees and maps a chunk, and a trace that hovers there
        took 2.5 times as long on the chip (PERF.md section 6, PR 29).
        From a worker's own shallow stack the answer does not depend on
        what called the solve."""
        if not self._guarded:
            return fn(*args)
        import concurrent.futures as cf
        fut = self._submit(fn, *args)
        try:
            return fut.result(timeout or None)
        except cf.TimeoutError:
            # consume whatever the wedged worker eventually produces so
            # its late exception is not reported as never-retrieved
            fut.add_done_callback(lambda f: f.exception())
            raise self._stalled() from None

    def run(self, next_launch, harvest, done=None, load=None) -> None:
        queues = [deque() for _ in range(self.lanes)]
        t_start = time.monotonic()
        per = len(self.devices) // self.lanes
        self._lanes = [_Lane(k, self.devices[k * per:(k + 1) * per], load,
                             t_start) for k in range(self.lanes)]
        try:
            while True:
                inflight = sum(map(len, queues))
                if done is not None and done():
                    # every result is in: any remaining in-flight slab
                    # is pure speculation — abandon it unfetched (the
                    # device finishes it in the background) instead of
                    # paying a blocking readback for nothing
                    if inflight:
                        ABANDONED_LAUNCHES.labels(kind=self.kind).inc(
                            inflight)
                    break
                if self.should_stop is not None and self.should_stop():
                    # drain what is already in flight — a pending slab
                    # may hold the answer the caller checkpoints on
                    while any(queues):
                        harvest(*self._first_in(queues))
                    raise PowInterrupted("pipelined PoW interrupted")
                # the devices with room for a launch, the one with the
                # fewest in flight asked first (one that has run out
                # before any other), then the one with the least to do:
                # what has arrived since goes to the chip that needs it
                room = [k for k, q in enumerate(queues)
                        if len(q) < self.depth]
                while room:
                    lane = room[0] if len(room) == 1 else min(
                        room, key=lambda k: (len(queues[k]),
                                             load(k) if load else 0))
                    if (self.shape, lane) in _TRACED_SHAPES:
                        nxt = next_launch(lane)
                    else:
                        nxt = self._on_device_thread(next_launch, lane)
                    if nxt is None:
                        room.remove(lane)
                        if not queues[lane]:
                            self._lanes[lane].enter("starved")
                        continue
                    if self.shape is not None:
                        _TRACED_SHAPES.add((self.shape, lane))
                    self.slabs += 1
                    queues[lane].append((self.slabs, *nxt))
                    if len(queues[lane]) == 1:
                        self._lanes[lane].enter("inflight")
                    if len(queues[lane]) >= self.depth:
                        room.remove(lane)
                    inflight += 1
                    LAUNCHES.labels(kind=self.kind).inc()
                    for k in range(lane * per, (lane + 1) * per):
                        # bounded by the host's device count
                        DEVICE_LAUNCHES.labels(device="%d" % k).inc()  # bmlint: allow(metric-labels)
                    PIPELINE_DEPTH.set(inflight)
                    _flight("slab_launch", n=self.slabs,
                            inflight=inflight)
                if not inflight:
                    break
                DISPATCH_AHEAD.observe(inflight)
                tag, host = self._first_in(queues)
                PIPELINE_DEPTH.set(inflight - 1)
                _flight("slab_harvest",
                        wait_ms=round(self.last_wait * 1e3, 2),
                        inflight=inflight - 1)
                harvest(tag, host)
        finally:
            PIPELINE_DEPTH.set(0)
            # after a stall the workers are gone already
            self._drop_guards(wedged=False)
            t_end = time.monotonic()
            left = [ln.leave(t_end) for ln in self._lanes]
            self.lane_seconds = {state: sum(s[state] for s in left)
                                 for state in LANE_STATES}
            self.wall_seconds = max(t_end - t_start, 1e-9)
            DEVICE_BUSY.set(self.busy_ratio)

    @property
    def busy_ratio(self) -> float:
        """The share of the latest ``run``'s lane time with a launch in
        flight."""
        return self.lane_seconds["inflight"] / (
            len(self.devices) * self.wall_seconds) \
            if self.wall_seconds else 0.0


class _Lane:
    """Why one device has or has not something to run, while
    ``_PipelineDriver.run`` is under way.  ``inflight``: from a launch
    appended to the lane's empty queue to the read that empties it.
    ``turn``: from that read (and from ``run``'s start) until the host
    loop has launched for the lane again or found nothing for it.
    ``starved``: from a turn that found nothing to launch with the
    queue empty, every object of the device solved, until the lane's
    next launch or ``run``'s end.  In a ``batched`` solve over several
    devices such a turn first takes a nonce-range copy of another
    lane's unresolved object and launches that
    (``solve_batch_pipelined``), so a lane is starved only while no
    other lane has an object left to copy, or no group of its own has
    every launch read.

    ``turn`` and ``starved`` are intervals in the profiler's trace,
    with the device's id, the lane and the lane's live slots when they
    opened (``inflight`` is their absence inside a solve); every
    state's seconds are credited to
    ``pow_pipeline_lane_seconds_total`` on the host's clock when the
    lane leaves it, so one ``run`` credits its devices times its wall
    time whatever ends it.  A lane whose launches are one program over
    several devices opens an interval and credits the seconds for each
    of them.
    """

    __slots__ = ("lane", "devices", "load", "state", "since", "seconds",
                 "_interval")

    def __init__(self, lane: int, devices, load, now: float):
        #: the ids of the devices a launch of the lane runs on: one, or
        #: all that one program spans
        self.lane, self.devices, self.load = lane, tuple(devices), load
        #: the open interval of each device (None: none is open)
        self.state, self.since, self._interval = None, now, None
        self.seconds = dict.fromkeys(LANE_STATES, 0.0)
        self.enter("turn")

    def enter(self, state, now=None) -> None:
        """The lane is in ``state`` from now on (None: no longer
        driven); a lane that is there already stays as it is."""
        if state == self.state:
            return
        if self._interval is not None:
            end = [iv.close().end for iv in self._interval][0]
            now = end if now is None else now
            self._interval = None
        if state in ("turn", "starved"):
            attrs = dict(lane=self.lane,
                         live=self.load(self.lane) if self.load else 0)
            self._interval = tuple(
                interval("pow.lane.turn", device=device, **attrs)
                if state == "turn"
                else interval("pow.lane.starved", device=device, **attrs)
                for device in self.devices)
            start = [iv.open().start for iv in self._interval][0]
            now = start if now is None else now
        elif now is None:
            now = time.monotonic()
        if self.state is not None:
            self.seconds[self.state] += (now - self.since) \
                * len(self.devices)
            for device in self.devices:
                # bounded by the host's device count
                LANE_SECONDS.labels(  # bmlint: allow(metric-labels)
                    device="%d" % device, state=self.state).inc(
                        now - self.since)
            self.since = now
        self.state = state

    def leave(self, now: float) -> dict:
        """``run`` is over at ``now``: what is open is closed; the
        lane's seconds by state."""
        self.enter(None, now)
        return self.seconds


# ---------------------------------------------------------------------------
# the pipelined solve (production entry)
# ---------------------------------------------------------------------------


def _split64(value: int):
    """A 64-bit value as the ``(hi, lo)`` uint32 pair the kernels take."""
    return (value >> 32) & 0xFFFFFFFF, value & 0xFFFFFFFF


@functools.lru_cache(maxsize=256)
def _pair_on_device(value: int, device):
    """``value`` as the pair the kernels take, put on ``device`` (None:
    JAX's default) once and kept there.  For the base of a lone
    object's launch: an object begins at nonce 0 unless it is resumed,
    so lane ``k``'s ``n``-th launch begins where it began for the last
    object, and of a launch's three operands the base is the one that
    need not cross again.  A transfer costs the host 0.11 to 0.28 ms
    on a v5e however it is made (PERF.md section 6, PR 44); a base that
    is not here yet (a resumed object, an object many launches long)
    costs that once."""
    import numpy as np

    return jax.device_put(np.array(_split64(value), dtype=np.uint32),
                          device)


def _hash_words(initial_hash: bytes):
    """The eight 64-bit words of an initial hash as ``(hi, lo)`` pairs."""
    return [_split64(int.from_bytes(initial_hash[j:j + 8], "big"))
            for j in range(0, 64, 8)]


def _checked_nonce(nonce: int, initial_hash: bytes, target: int) -> int:
    """The hashlib re-check of a nonce the device reported as a winner."""
    check = double_sha512(nonce.to_bytes(8, "big") + initial_hash)
    if int.from_bytes(check[:8], "big") > target:
        raise ArithmeticError("accelerator returned an invalid PoW nonce")
    return nonce


class _LaunchGroup:
    """Host state for one launch-wide slab group (``width`` slots, of
    which those not given an object are pad).  A group lives on one
    device: ``device`` is where its arrays are put and so where its
    launches run (None: wherever JAX puts an array by default).  Its
    words go there when it is laid out, unless ``words_on_device`` is
    False: a lone object's ride its launches."""

    __slots__ = ("idx", "words", "ih_words", "stale", "targets", "t_arr",
                 "bases", "trials", "done", "copy", "unread", "width",
                 "unbatched", "device")

    def __init__(self, items, idx, width, starts=None, unbatched=False,
                 device=None, words_on_device=True):
        import numpy as np

        self.device = device

        pad = width - len(idx)
        ihs = [items[i][0] for i in idx] + [b"\x00" * 64] * pad
        self.targets = ([items[i][1] & _MASK64 for i in idx]
                        + [_ALWAYS_HIT] * pad)
        self.words = np.array([_hash_words(ih) for ih in ihs],
                              dtype=np.uint32)
        # ``pallas_search`` takes its one object's words without the
        # leading object axis
        self.unbatched = unbatched
        #: the device copy of ``words`` is behind the host's
        self.stale = True
        if idx and words_on_device:
            # a group of pad slots alone is never launched: its words
            # go to the device with its first refill; a lone object's
            # cross in its launches (``words_on_device`` False)
            self.device_words()
        self.t_arr = np.array([_split64(t) for t in self.targets],
                              dtype=np.uint32)
        #: the item each slot searches for (None: a pad slot)
        self.idx = list(idx) + [None] * pad
        self.width = width
        # resumable PoW: each object's search starts at its journaled
        # checkpoint offset instead of 0 (pad slots stay at 0)
        self.bases = ([(starts[i] if starts else 0) & _MASK64
                       for i in idx] + [0] * pad)
        self.trials = [0] * width
        self.done = [i is None for i in self.idx]
        #: the slot searches a nonce-range copy of another lane's object
        self.copy = [False] * width
        #: launches dispatched and not yet harvested
        self.unread = 0

    @property
    def finished(self) -> bool:
        return all(self.done)

    def live(self) -> int:
        return sum(1 for d in self.done if not d)

    def live_targets(self):
        return (t for t, d in zip(self.targets, self.done) if not d)

    def device_words(self):
        """The initial hashes as the kernels take them, sent to the
        device again only after a refill."""
        if self.stale:
            self.ih_words = jax.device_put(
                self.words[0] if self.unbatched else self.words,
                self.device)
            self.stale = False
        return self.ih_words

    def refill(self, k: int, i: int, initial_hash: bytes, target: int,
               base: int, copy: bool = False) -> None:
        """Slot ``k``, solved or pad, takes item ``i`` (``copy``: a
        range of it that another lane's slot does not search).  Only
        between launches that have all been read: an unread launch
        still answers for the slot's last object."""
        self.idx[k] = i
        self.words[k] = _hash_words(initial_hash)
        self.stale = True
        self.targets[k] = target & _MASK64
        self.t_arr[k] = _split64(self.targets[k])
        self.bases[k] = base & _MASK64
        self.trials[k] = 0
        self.done[k] = False
        self.copy[k] = copy

    def retire(self, k: int) -> None:
        """Slot ``k`` has nothing left to search: pad semantics, an
        always-hit step in the group's next launch and idle after."""
        self.done[k] = True
        self.t_arr[k] = (0xFFFFFFFF, 0xFFFFFFFF)


def _copy_base(start: int, nth: int, lanes: int) -> int:
    """Where the copy of an object on the ``nth`` lane after its own
    (counted round the ``lanes``, so 1 to ``lanes - 1``) begins its
    search: the object's own start plus ``nth`` shares of the nonce
    space, 2**62 apart on four chips.  An object at network difficulty
    needs 1e7 to 1e9 trials and its own range begins at ``start``, so
    ranges 2**64 / ``lanes`` apart never meet; if they ever did, a
    range would be searched twice and nothing would be wrong."""
    return (start + nth * ((1 << 64) // lanes)) & _MASK64


def _pow2_at_least(n: int, cap: int) -> int:
    p = 1
    while p < n and p < cap:
        p *= 2
    return min(p, cap)


def _one_program(impl: str, devices) -> bool:
    """Whether several ``devices`` search a lone object as ONE program
    whose parts stop at the first hit (``sha512_ici.ici_search``, or
    its XLA equivalent :func:`_ici_search_xla`).  They do, but for the
    Mosaic kernel on the tests' virtual CPU devices, which keep a
    launch a lane: the TPU interpreter cannot read a semaphore on the
    ``cpu`` backend, so that program cannot run there."""
    return len(devices) > 1 and (
        impl != "pallas"
        or getattr(devices[0], "platform", "cpu") != "cpu")


def _slab_rows(out, found):
    """``pallas_search``'s output (a hit flag and a nonce a grid step)
    as the one ``[hit_step + 1, nonce_hi, nonce_lo]`` row the other
    kernels return; the nonces are pulled only after a hit."""
    import numpy as np

    rows = np.zeros((1, 3), np.uint32)
    step = int(found.argmax())
    if found[step]:
        rows[0] = (step + 1, *np.asarray(out[1])[step])
    return rows


def solve_batch_pipelined(items, *, rows: int = DEFAULT_ROWS,
                          unroll: int = 1, depth: int = 2,
                          impl: str | None = None,
                          interpret: bool = False,
                          plan: BatchPlan | None = None,
                          stats: dict | None = None,
                          should_stop: Callable[[], bool] | None = None,
                          start_nonces=None, progress=None,
                          stall_timeout: float = 0.0,
                          on_solved=None, feed=None, expect: int = 0,
                          devices=None):
    """Solve ``[(initial_hash, target), ...]`` — one object or a queue
    — through the dispatch-ahead driver.  Returns ``[(nonce, trials),
    ...]`` aligned with ``items`` (then with what ``feed`` brought, in
    the order it came); raises :class:`PowInterrupted` on shutdown.

    The plan (see :func:`plan_batch`) names the kernel and its shape;
    every mode then runs the same loop: launch groups of ``width``
    objects, up to ``depth`` launches in flight (one for
    ``single-sync``), the oldest read back while the newer run; a
    group's next launch goes ahead of its unread ones only where
    :func:`worth_speculating` says so.  Every
    returned nonce is host re-verified.  Per-object ``trials`` credit
    the grid steps the object's own search really ran (a search leaves
    its launch at its first hit; an object searched in several slots,
    below, the sum over them up to the win), for every mode;
    ``stats["executed_trials"]`` (optional dict) estimates total device
    hashing including straggler and pad waste — the two diverge
    exactly where packing removes waste.

    The solve as a stream: ``on_solved(i, (nonce, trials))`` is called
    from the harvest that found item ``i``'s nonce, once the nonce has
    passed the hashlib re-check, in hit order and before the solve
    returns.  ``feed(room)`` returns at most ``room`` new requests
    ``(initial_hash, target, start_nonce)`` that have arrived since it
    was last asked; a ``batched`` solve asks before a group's launch,
    when all of the group's launches have been read, and gives each
    done slot of that group one of them (item numbers go on from
    ``len(items)``).  The plan is made once, at the start, of what is
    there and of what the caller says is announced: ``expect`` is the
    number of objects the solve is laid out for.  With ``expect`` above
    ``len(items)`` (a sweep's solve that starts at its first member)
    the mode is that of a queue of ``expect`` objects, the groups are
    those ``expect`` objects would fill (``ceil(expect / 64)`` of 64
    slots, :data:`MIN_BATCH_GROUPS` for at most 64), the objects that
    are there are dealt over them and every other slot starts as a pad
    slot for ``feed`` to fill; a group with no live slot is not
    launched, and takes arrivals in when its turn comes.  Without
    ``expect`` nothing is announced: a solve that starts with one
    object stays ``slab`` and asks nobody.  The solve ends when every
    slot is done and ``feed`` has nothing, whoever may still be
    missing.

    ``devices`` places the solve: the launch groups are dealt over
    them in turn, a group's arrays and launches stay on its device,
    each device has its own ``depth`` launches in flight and its own
    round-robin over its groups, and a freed slot takes what ``feed``
    brings on the chip that freed it.  A queue is laid out as at least
    :data:`MIN_BATCH_GROUPS` groups a device, so that no device needs
    a launch dispatched ahead of an unread one to stay busy.  Without
    ``devices`` there is one lane, on JAX's default device.

    An object's own nonce range is searched on one chip.  A chip of a
    ``batched`` solve that has run out (no live slot, nothing from
    ``feed``) takes a COPY of a straggler, one object a turn: from the
    lane with the most unresolved objects the one that the fewest
    lanes search (the hardest of those), into a done slot of a group of
    its own, at a base no other holder searches (:func:`_copy_base`).
    The search is memoryless, so a trial there is worth as much as one
    in the object's own range.  The first slot to hit resolves the
    object (``on_solved`` once, the nonce re-verified as any other);
    every other slot of it becomes a pad slot, and what its launches
    in flight still search is computed and not needed.  One a turn and
    only with nothing live, because four chips that copy greedily are
    four copies of one queue (PERF.md section 6, PR 42).

    ONE object on several ``devices`` (mode ``slab``) is laid out the
    same way from the start: a group of one slot on every device, lane
    ``k``'s at :func:`_copy_base` ``(start, k, lanes)``, all launched in
    the first turn.  A lone object's lay-out, on one lane or several,
    is host arrays: a lane's operands cross in its launches, the words
    and the target riding the jit call and the base on its device
    already (:func:`_pair_on_device`), the same call in every round.
    The first harvest with a hit resolves the object
    and the solve returns; what the other lanes' launches still search
    is abandoned unread.  The speculation rule counts the unread
    launches of every lane: none is dispatched ahead while those in
    flight are likely to end the object.  ``progress`` and a resumed
    ``start_nonces`` are lane 0's, the object's own range.

    Where the plan says ``one_program`` (:func:`_one_program`: the
    chips of an accelerator, and the XLA equivalent anywhere) those
    lanes are ONE program, ``sha512_ici.ici_search``: one group whose slots
    are the lanes' ranges, one driver lane that spans the devices, the
    operands one array riding the call, a launch as long as one chip's
    (512 steps each).  Its kernels read a flag at every grid step that
    the chip whose step hits raises on the others over ICI, so a solve
    is one launch, one fetch and one harvest, nothing runs on after
    the win, and every lane's row says how many steps it ran and why
    it left: ``executed_trials`` and ``pow_pipeline_executed_trials_total``
    count every lane's steps, the needed trials the winner's up to its
    nonce and the others' up to the winner's step,
    ``pow_pipeline_lone_lanes_total`` how each lane left and
    ``pow_pipeline_lone_cancel_lag_steps`` the steps a cancelled lane
    ran past the winner's.  Of two lanes that hit in one step the host
    takes the first; an object that outlasts a launch has its next one
    dispatched ahead by the same rule.

    Resilience hooks (docs/resilience.md): ``start_nonces`` resumes
    each object from a checkpointed offset; ``progress(i, next)`` is
    invoked at every harvest with the end of the slab range just
    proven miss-free for item ``i`` (safe resume point — speculative
    dispatch-ahead never moves a checkpoint before its slab is
    harvested, and a copy's slot never moves one: it proves nothing
    about the range a resumed search would skip); on ``should_stop``
    what is in flight is harvested first, and an answer found there is
    returned; ``stall_timeout > 0`` bounds each harvest's blocking
    device wait.
    """
    import numpy as np

    t_entry = time.monotonic()
    items = list(items)
    n = len(items)
    if n == 0:
        return []
    if impl is None:
        impl = default_impl()
    expect = max(expect, n)
    devices = list(devices) if devices else [None]
    if plan is None:
        with trace("pow.plan", objects=n, expect=expect) as span:
            plan = plan_batch(items, rows=rows, unroll=unroll,
                              expect=expect, lanes=len(devices),
                              one_program=_one_program(impl, devices))
            span.attrs.update(mode=plan.mode, chunks=plan.chunks)
    PIPELINE_MODE.labels(mode=plan.mode).inc()
    mode, pack, chunks = plan.mode, plan.pack, plan.chunks
    kind = _KIND[mode]
    pallas = impl == "pallas"
    if mode != "batched":
        feed = None             # only a queue of whole tiles takes in
    if mode == "single-sync":
        devices = devices[:1]   # one small launch at a time: one lane
    #: one object shared out over the devices, a slot of it on each
    lone = mode == "slab" and len(devices) > 1
    #: its slots are ONE group and a launch ONE program over the
    #: devices, which stops every slot at the first hit
    spanned = lone and plan.one_program

    # the launch geometry of each mode, and the jitted program it
    # launches with the static-shape key that decides compile-vs-cache
    # (mirrors each kernel's static_argnames) for device telemetry
    width = 1
    if mode == "packed":
        # one launch carries groups*pack objects on the leading grid
        # axis — the storm's launch-overhead amortization
        width = pack * _pow2_at_least(-(-n // pack), PACKED_GROUPS_MAX)
    elif mode == "batched":
        width = BATCH_OBJS
        if pallas:
            rows, unroll = min(rows, BATCH_ROWS), BATCH_UNROLL
        else:
            chunks = min(chunks, XLA_BATCH_CHUNKS)
    elif mode == "slab":
        unroll = DEFAULT_UNROLL if pallas else unroll
    else:
        depth = 1
    step_trials = (rows // pack) * LANE_COLS * unroll
    slab_trials = step_trials * chunks          # per object per launch
    if not pallas:
        tele_prog, tele_key = "packed_search_xla", (
            step_trials, chunks) + ((len(devices),) if spanned else ())
    elif spanned:
        tele_prog, tele_key = "ici_slab", (rows, chunks, unroll,
                                           interpret, len(devices))
    elif mode == "slab":
        tele_prog, tele_key = "pallas_slab", (rows, chunks, unroll,
                                              interpret)
    elif mode == "batched":
        tele_prog, tele_key = "batch_search", (rows, chunks, unroll,
                                               interpret)
    else:
        tele_prog, tele_key = "packed_search", (rows, chunks, pack,
                                                unroll, interpret)
    # the packed Mosaic kernel donates its base/target input buffers,
    # which is why they are made anew for every launch
    donated = pallas and mode in ("packed", "single-sync")
    unbatched = pallas and mode == "slab" and not spanned

    # what each group starts with: ``width`` objects of the plan's
    # order; or, for a queue that would fill fewer groups than
    # MIN_BATCH_GROUPS a device or one laid out for announced company,
    # the order dealt evenly over the groups that queue would fill, at
    # least that many
    count, least = -(-expect // width), MIN_BATCH_GROUPS * len(devices)
    if mode == "batched" and (expect > n or count < least):
        count = max(least, count)
        per = -(-n // count)
        shares = [plan.order[s:s + per]
                  for s in range(0, per * count, per)]
    elif spanned:
        width = len(devices)
        shares = [plan.order * width]
    elif lone:
        shares = [plan.order] * len(devices)
    else:
        shares = [plan.order[s:s + width] for s in range(0, n, width)]
    #: where each object's own range begins
    starts = list(start_nonces) if start_nonces else [0] * n
    with trace("pow.groups", objects=n, width=width,
               devices=len(devices)):
        # a lone object's lay-out is host arrays: nothing of it crosses
        # to a device before its lanes' launches do
        groups = [_LaunchGroup(items, share, width, starts=start_nonces,
                               unbatched=unbatched,
                               device=devices[j % len(devices)],
                               words_on_device=mode != "slab")
                  for j, share in enumerate(shares)]
        if lone:
            # slot ``k`` of the object: a group's one, or the one
            # group's ``k``-th
            for k in range(1, len(devices)):
                g, slot = (groups[0], k) if spanned else (groups[k], 0)
                g.bases[slot] = _copy_base(starts[0], k, len(devices))
                g.copy[slot] = True
    #: the groups of each lane (a device; or all of them, where a launch
    #: is one program over them), and where its round-robin stands
    lanes = [groups] if spanned else [
        groups[k::len(devices)] for k in range(len(devices))]
    rr = [0] * len(lanes)
    results: list = [None] * n
    executed = {"trials": 0, "copies": 0}
    #: lane -> when its first launch had returned
    head: dict = {}
    #: unresolved item -> {lane: (group, slot)} of the slots that search
    #: it: its own first, then its copies, at most one a lane
    held: dict = {}
    for j, g in enumerate(groups):
        for k, i in enumerate(g.idx):
            if i is not None:
                held.setdefault(i, {})[k if spanned
                                       else j % len(lanes)] = (g, k)
    #: whether a lane that has run out has another lane to copy from
    may_copy = mode == "batched" and len(lanes) > 1

    def load(lane) -> int:
        return sum(g.live() for g in lanes[lane])

    def take_in(g, lane) -> int:
        """Give the done slots of ``g``, whose launches have all been
        read, objects that have arrived since ``feed`` was last asked;
        how many it took."""
        free = [k for k in range(g.width) if g.done[k]]
        arrived = feed(len(free)) if free else ()
        for k, (initial_hash, target, start) in zip(free, arrived):
            items.append((initial_hash, target))
            results.append(None)
            starts.append(start)
            held[len(items) - 1] = {lane: (g, k)}
            g.refill(k, len(items) - 1, initial_hash, target, start)
        if arrived:
            REFILLS.labels(kind=kind).inc(len(arrived))
        return len(arrived)

    def take_copy(lane):
        """``lane`` has no live slot and ``feed`` had nothing for it:
        a done slot of one of its groups whose launches have all been
        read takes a copy of ONE object, from the lane with the most
        unresolved objects the one that the fewest lanes search, of
        those the hardest.  No live slot means ``lane`` holds none of
        them yet.  The group to launch, or None."""
        g = next((g for g in lanes[lane] if not g.unread), None)
        if g is None or not held:
            return None
        of_lane = [[i for i, where in held.items() if other in where]
                   for other in range(len(lanes))]
        i = min(max(of_lane, key=len),
                key=lambda i: (len(held[i]), items[i][1] & _MASK64))
        own = next(iter(held[i]))
        k = g.done.index(True)
        g.refill(k, i, *items[i],
                 _copy_base(starts[i], (lane - own) % len(lanes),
                            len(lanes)), copy=True)
        held[i][lane] = (g, k)
        executed["copies"] += 1
        return g

    def speculate(mine):
        # THE speculation rule, for every mode: with no fresh group
        # left on a device, dispatch the next launch of one of its
        # groups ahead of its unread ones only while those are unlikely
        # to finish it (a lone ack is never speculated on, 64 objects in
        # mid sweep always are); if they do finish it, run() counts
        # this one abandoned
        for g in mine:
            if g.finished:
                continue
            # a lone object is searched by every lane's unread launches
            unread = sum(h.unread * h.width for h in groups) if lone \
                else g.unread
            ahead = worth_speculating(slab_trials * unread,
                                      g.targets[:1] if spanned
                                      else g.live_targets())
            SPECULATION.labels(
                kind=kind,
                decision="launched" if ahead else "withheld").inc()
            if ahead:
                return g
        return None

    def next_launch(lane):
        mine = lanes[lane]
        cand, refilled, copied = None, 0, 0
        # round-robin over the device's groups without an in-flight
        # slab: one with a done slot takes in what has arrived, an
        # unfinished one is launched
        for off in range(len(mine)):
            g = mine[(rr[lane] + off) % len(mine)]
            if g.unread:
                continue
            if feed is not None:
                refilled = take_in(g, lane)
            if not g.finished:
                cand = g
                rr[lane] = (rr[lane] + off + 1) % len(mine)
                break
        speculative = cand is None
        if speculative and may_copy and not load(lane):
            # run out: search on for a chip that has not
            cand, speculative = take_copy(lane), False
            copied = int(cand is not None)
        elif speculative:
            # decided in a call that has returned before the kernel is
            # called: none of it lies under a kernel's trace
            cand = speculate(mine)
        if cand is None:
            return None
        live = cand.live()
        SLOTS.labels(kind=kind, state="live").inc(live)
        SLOTS.labels(kind=kind, state="idle").inc(cand.width - live)
        if mode == "packed":
            # pack statistics describe lane sharing, which only the
            # packed kernel does — the other modes must not dilute
            # them (docs/observability.md semantics)
            PACK_SIZE.observe(live)
            PACK_OCCUPANCY.set(live / cand.width)
        if unbatched:
            # a lone object's operands cross in the launch itself: its
            # words and its target, new with every solve, ride the jit
            # call as numpy, and the base, on the lane's device
            # already, says which device that is.  Nothing else moves,
            # in the first round or a later one
            ih_words = cand.words[0]
            base = _pair_on_device(cand.bases[0], cand.device)
        elif not spanned:       # whose operands are one array, below
            ih_words = cand.device_words()
        with trace("pow.launch", program=tele_prog, chunks=chunks,
                   live=live, speculative=speculative,
                   refilled=refilled, copied=copied,
                   device=lane) as span:
            # the kernels are called from this frame, not through a
            # helper: on the chip a process's first call, when it
            # traced and lowered pallas_search, took 2.5 times as long
            # from one frame further down (PERF.md section 6, PR 29).
            # Since PR 48 only a machine's FIRST start traces here: the
            # export of core/programcache.py, under a frame with a
            # chunk of its own, so it no longer matters how deep this
            # one lies (tools/first_solve_faults.py drives it); a later
            # start loads the program and traces nothing.  The pin
            # still serves the calls that trace live (an export that
            # failed, the XLA stand-in)
            bases = np.array([_split64(b) for b in cand.bases],
                             dtype=np.uint32)
            if spanned:
                # ONE array, a row a device (the words, that device's
                # base, the target), rides the call as numpy: a
                # transfer a device
                operands = np.concatenate(
                    [cand.words.reshape(cand.width, 16), bases,
                     cand.t_arr], axis=1)
                if pallas:
                    out = sha512_ici.ici_search(
                        operands, devices, rows=rows, chunks=chunks,
                        unroll=unroll, interpret=interpret)
                else:
                    out = _ici_search_xla(operands, lanes=step_trials,
                                          chunks=chunks)
            elif unbatched:
                out = sha512_pallas.pallas_search(
                    ih_words, base, cand.t_arr[0], rows=rows,
                    chunks=chunks, unroll=unroll, interpret=interpret)
            elif not pallas:
                out = _packed_search_xla(
                    ih_words, jax.device_put(bases, cand.device),
                    jax.device_put(cand.t_arr, cand.device),
                    lanes=step_trials, chunks=chunks)
            elif mode == "batched":
                out = sha512_pallas.pallas_batch_search(
                    ih_words, jax.device_put(bases, cand.device),
                    jax.device_put(cand.t_arr, cand.device), rows=rows,
                    chunks=chunks, unroll=unroll, interpret=interpret)
            else:
                out = pallas_packed_search(
                    ih_words, jax.device_put(bases, cand.device),
                    jax.device_put(cand.t_arr, cand.device), rows=rows,
                    chunks=chunks, pack=pack, unroll=unroll,
                    interpret=interpret)
        cand.unread += 1
        head.setdefault(lane, span.end)
        for k in range(cand.width):
            if not cand.done[k]:
                cand.bases[k] = (cand.bases[k] + slab_trials) & _MASK64
        # snapshot of each object's post-slab offset: the safe resume
        # point to checkpoint once THIS slab harvests miss-free (the
        # live ``bases`` may already include speculative launches)
        tag = (cand, lane, span.start, span.end, list(cand.bases), out)
        # what the driver blocks on: pallas_search's hit flags
        return tag, (out[0] if unbatched else out)

    def harvest(tag, host):
        with trace("pow.harvest", device=tag[1]) as span:
            _harvest(tag, host, span.start)

    def _harvest_spanned(g, rows_out, end_bases) -> int:
        """A launch of the one program over the devices: every lane's
        row says how many steps it ran and why it left, so what each
        computed is known, the losers' too.  The trials it needed."""
        ran = [int(r[sha512_ici.STEPS]) for r in rows_out]
        executed["trials"] += sum(ran) * step_trials
        hits = [k for k in range(g.width) if rows_out[k, sha512_ici.HIT]]
        # two lanes that hit in one step both report: the first step's,
        # then the first lane's
        win = min(hits, key=lambda k: int(rows_out[k, sha512_ici.HIT]),
                  default=None)
        step1 = int(rows_out[win, sha512_ici.HIT]) if hits else 0
        # a launch dispatched ahead and read after the one before it
        # had hit: computed, needed by nobody, and no second result
        late = g.finished
        for k, n in enumerate(ran):
            outcome = "won" if k == win and not late else _LONE_LEFT.get(
                int(rows_out[k, sha512_ici.WHY]), "ran_out")
            LONE_LANES.labels(outcome=outcome).inc()
            if outcome == "cancelled":
                LONE_CANCEL_LAG.observe(max(n - step1, 0))
            if not late:
                g.trials[k] += n * step_trials
        if late:
            return 0
        i = g.idx[0]
        if not hits:
            # nobody raised the flag: every lane ran its whole slab
            if progress is not None:
                progress(i, end_bases[0])
            return sum(ran) * step_trials
        nonce = _checked_nonce(
            (int(rows_out[win, sha512_ici.NONCE_HI]) << 32)
            | int(rows_out[win, sha512_ici.NONCE_LO]),
            items[i][0], g.targets[win])
        del held[i]
        results[i] = (nonce, sum(g.trials))
        for k in range(g.width):
            g.retire(k)
        # bounded by the host's device count
        LONE_WINS.labels(lane="%d" % win).inc()  # bmlint: allow(metric-labels)
        if on_solved is not None:
            on_solved(i, results[i])
        # the winner needed its steps up to the nonce, every other lane
        # what it searched while the object was unresolved
        return min((nonce - end_bases[win] + slab_trials + 1) & _MASK64,
                   step1 * step_trials) + sum(
                       min(n, step1) * step_trials
                       for k, n in enumerate(ran) if k != win)

    def _harvest(tag, host, t_h):
        g, _lane, t0, t1, end_bases, out = tag
        rows_out = _slab_rows(out, host) if unbatched else host
        g.unread -= 1
        before = executed["trials"]
        # the one program's lanes report for themselves; else slot by
        # slot
        needed = _harvest_spanned(g, rows_out, end_bases) if spanned else 0
        for k in () if spanned else range(g.width):
            step1 = int(rows_out[k, 0])
            if g.done[k]:
                # a pad or solved slot's one always-hit step; or what a
                # slot searched in this launch after another launch, or
                # another slot of its object, had resolved it: computed,
                # needed by nobody, and no second result
                executed["trials"] += (step1 or chunks) * step_trials
                continue
            i = g.idx[k]
            if step1:
                g.trials[k] += step1 * step_trials
                executed["trials"] += step1 * step_trials
                nonce = _checked_nonce(
                    (int(rows_out[k, 1]) << 32) | int(rows_out[k, 2]),
                    items[i][0], g.targets[k])
                # this launch searched on from end_bases[k] - slab_trials;
                # the nonce lies in the step that reported it
                needed += min(
                    (nonce - end_bases[k] + slab_trials + 1) & _MASK64,
                    step1 * step_trials)
                # first hit wins: every slot that searches the object,
                # on whichever lane, is retired with this one
                slots = held.pop(i).values()
                results[i] = (nonce, sum(h.trials[s] for h, s in slots))
                for h, s in slots:
                    h.retire(s)
                    if h.copy[s] and not lone:
                        COPIES.labels(
                            kind=kind, outcome="won" if h is g
                            else "cancelled").inc()
                if lone:
                    # bounded by the host's device count
                    LONE_WINS.labels(lane="%d" % _lane).inc()  # bmlint: allow(metric-labels)
                if on_solved is not None:
                    on_solved(i, results[i])
            else:
                g.trials[k] += slab_trials
                executed["trials"] += slab_trials
                needed += slab_trials
                if progress is not None and not g.copy[k]:
                    # this slab proved [prev, end_bases[k]) miss-free:
                    # a resumed search may safely start there
                    progress(i, end_bases[k])
        ran = executed["trials"] - before
        EXECUTED_TRIALS.labels(kind=kind).inc(ran)
        NEEDED_TRIALS.labels(kind=kind).inc(needed)
        record_launch(tele_prog, key=tele_key, dispatch_seconds=t1 - t0,
                      # the driver fetched this slab just before calling us
                      wait_seconds=driver.last_wait, span=(t0, t_h),
                      items=ran, bytes_in=16 * g.width,
                      bytes_out=12 * g.width,
                      bytes_donated=16 * g.width if donated else 0)

    def done():
        """Every slot is done, and the queue has nobody for a group
        whose launches have all been read."""
        if not all(g.finished for g in groups):
            return False
        return feed is None or not any(
            take_in(g, j % len(lanes)) for j, g in enumerate(groups)
            if not g.unread)

    driver = _PipelineDriver(depth=depth, lanes=len(lanes),
                             should_stop=should_stop,
                             stall_timeout=stall_timeout, kind=kind,
                             shape=(tele_prog, tele_key),
                             devices=[getattr(d, "id", k)
                                      for k, d in enumerate(devices)])
    try:
        driver.run(next_launch, harvest, done=done, load=load)
    except PowInterrupted:
        if any(r is None for r in results):
            raise
    if mode == "slab" and len(head) == len(lanes):
        # bounded by the host's device count
        LONE_HEAD.labels(lanes="%d" % len(devices)).observe(  # bmlint: allow(metric-labels)
            max(head.values()) - t_entry)
    if stats is not None:
        stats.update(
            mode=mode, pack=pack, width=width, chunks=chunks,
            groups=len(groups), devices=len(devices),
            launches=driver.slabs, copies=executed["copies"],
            executed_trials=executed["trials"],
            credited_trials=sum(r[1] for r in results),
            wall_seconds=driver.wall_seconds,
            device_busy_ratio=driver.busy_ratio)
    return results


def pipeline_snapshot() -> dict:
    """Pipeline gauges for clientStatus / bench (one JSON-able dict)."""
    return {
        "deviceBusyRatio": round(
            REGISTRY.sample("pow_pipeline_device_busy_ratio"), 4),
        "depth": REGISTRY.sample("pow_pipeline_depth"),
        "packOccupancy": round(
            REGISTRY.sample("pow_pack_occupancy_ratio"), 4),
    }
