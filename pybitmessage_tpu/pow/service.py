"""Async PoW front-end: coalesces concurrent solves into one batch.

The reference worker solves strictly one object at a time
(src/class_singleWorker.py:1274-1276).  Here every concurrently pending
solve joins one solve: requests are queued, a short coalescing window
lets the rest of a send sweep arrive, and the whole batch goes through
:meth:`PowDispatcher.solve_batch` — on an accelerator the pipeline's
launch groups, dealt over its chips (SURVEY §6: grid = nonce-lanes x
objects).

A solve is a stream (docs/pow_pipeline.md): each object's future
resolves when its own nonce has been found and re-checked, not when
the batch it came in with is through, and a solve that is running
takes the requests that arrive meanwhile into the slots that have come
free, up to :data:`SOLVE_SLOTS` objects at a time; what is beyond waits
in the queue.

``window`` is the LONGEST a queued object waits for company, not a
fixed delay (the latency/batching tradeoff called out in SURVEY §7:
dynamic batch assembly with padding, no recompilation per batch size
thanks to the object-axis padding in ``sharded_solve_batch``).  A send
sweep announces its member tasks (:meth:`PowService.announce`) before
any of them runs; a member is *outstanding* while it is announced and
not blocked inside :meth:`PowService.solve`, and is withdrawn when its
task ends, however it ends.  A request with no announced company is
not held at all.  What closes the window while somebody is missing
depends on the solver, asked at every window:

- a solver that streams now (it names ``on_solved``, ``feed`` and
  ``expect``, and its ``streams(items, expect)`` says that a solve of
  what the queue holds would take late arrivals in: ``PowDispatcher``
  on an accelerator, of one chip or several, with its pipeline
  healthy, for objects at network difficulty) holds nothing back.  The window closes at the
  first arrival (``first_arrival``), the solve is told how many
  objects to lay itself out for (``expect``: what the queue held plus
  the members outstanding, at most :data:`SOLVE_SLOTS`), and the rest
  of the sweep enters through ``feed`` while the chip already
  searches: a sweep of 256 broadcasts is still one solve, begun while
  255 of them are being signed and encrypted.  A solve that runs out
  of live slots with members still missing ends as any solve does;
  the next arrival opens the next window at once;
- any other solver (the CPU ladder, a CPU mesh's XLA rung, the
  farm, a wrapper that only passes ``**kwargs`` through) cannot take a late
  member in, so the window closes when nobody outstanding is missing
  (``all_arrived``) or after ``window`` seconds (``timeout``),
  whichever is first: a sweep of 256 when its last member arrives.

A lone send's ack and message are each dispatched on arrival either
way: nobody is outstanding when they ask.

Resilience (ISSUE 3, docs/resilience.md):

- a dispatcher failure REQUEUES what the solve had not resolved, with
  exponential backoff, instead of dropping it — a transient tier
  failure never loses a queued object and never resolves one twice;
  only ``max_attempts`` consecutive failures surface the error to the
  caller (and the job stays journaled);
- with a :class:`~pybitmessage_tpu.resilience.journal.PowJournal`
  attached, every request is journaled before it is queued, search
  progress is checkpointed as slabs harvest, and an object's row is
  deleted when its future resolves — queued/in-flight objects survive
  a process crash and a resumed solve continues from its checkpointed
  nonce offset.
"""

from __future__ import annotations

import asyncio
import contextvars
import itertools
import logging
import time
from collections import deque
from collections.abc import Iterable
from dataclasses import dataclass, field

from ..observability import (DEFAULT_SIZE_BUCKETS, REGISTRY, current_span,
                             set_batch, trace)
from ..observability.flightrec import record as _flight
from ..observability.lifecycle import LIFECYCLE
from ..ops.pow_search import PowInterrupted
from ..resilience import RetryPolicy
from ..resilience.policy import ERRORS

logger = logging.getLogger("pybitmessage_tpu.pow")

BATCH_SIZE = REGISTRY.histogram(
    "pow_batch_size",
    "Objects one solve_batch took in all told: the coalescing window's "
    "occupancy, plus what a streamed solve took into freed slots",
    buckets=DEFAULT_SIZE_BUCKETS)
QUEUE_WAIT = REGISTRY.histogram(
    "pow_queue_wait_seconds",
    "Time a solve request waited in the coalescing queue before its "
    "batch launched")
QUEUE_DEPTH = REGISTRY.gauge(
    "pow_queue_depth", "Solve requests currently queued or coalescing")
BATCHES = REGISTRY.counter(
    "pow_batches_total", "Coalesced solve_batch launches")
SOLVED = REGISTRY.counter(
    "pow_solved_total", "Solve requests completed through the service")
WINDOW_CLOSED = REGISTRY.counter(
    "pow_window_closed_total",
    "Coalescing windows closed, by what closed them: every announced "
    "member of the sweep had arrived (all_arrived), the solver streams "
    "and the solve began with members still missing (first_arrival), or "
    "the window ran out with a member still missing (timeout)",
    ("reason",))
RESOLVE_LAG = REGISTRY.histogram(
    "pow_resolve_lag_seconds",
    "From the harvest that found an object's nonce (solving thread) to "
    "its future resolved on the event loop")
REQUEUED = REGISTRY.counter(
    "pow_requeue_total",
    "Solve requests put back on the queue after a dispatcher failure "
    "or interrupt — the no-object-loss path", ("reason",))

#: sequence numbers of coalesced batches, process-wide: the ``batch``
#: attribute that ties a request's ``worker.pow`` span to the spans of
#: the ``solve_batch`` that served it
_BATCH_SEQ = itertools.count(1)

#: objects one solve begins with, and is laid out for, at most: on one
#: chip the four launch groups of 64 that the pipeline keeps two
#: launches in flight over (``chan_storm_256``); what is beyond waits
#: in the queue for a slot to come free
SOLVE_SLOTS = 256

#: default coalescing window in seconds, the longest a request waits
#: for announced company; overridable per node via the
#: ``powbatchwindow`` setting (core/config.py)
DEFAULT_WINDOW = 0.05


@dataclass
class _Request:
    initial_hash: bytes
    target: int
    future: asyncio.Future
    enqueued: float
    job_id: int | None = None
    start_nonce: int = 0
    attempts: int = 0
    #: monotonic time of the last journal checkpoint (write throttle)
    last_checkpoint: float = field(default=0.0)
    #: wire trace id this job belongs to (hex prefix in flight events;
    #: the future solver-farm protocol carries it on submit/requeue so
    #: a job's path through a remote farm stays one causal trace)
    trace_id: bytes = b""
    #: sequence number of the batch that last took this request
    batch: int = 0


def _unresolved(taken: list) -> list:
    return [req for req in taken if not req.future.done()]


class _RequestQueue:
    """Solve requests waiting for a slot.  Put and awaited on the event
    loop; taken there (a new solve's first batch) or on the solving
    thread (a running solve's refills): a deque, whose ends are atomic,
    and an event for the loop to sleep on."""

    def __init__(self):
        self._items: deque = deque()
        self._arrived = asyncio.Event()

    def qsize(self) -> int:
        return len(self._items)

    def put_nowait(self, req) -> None:
        self._items.append(req)
        self._arrived.set()

    async def get(self):
        while True:
            got = self.take(1)
            if got:
                return got[0]
            # no await between the empty take and the wait, and puts
            # come from this loop alone: none is slept through
            self._arrived.clear()
            await self._arrived.wait()

    def take(self, room: int) -> list:
        """At most ``room`` requests, oldest first; from any thread."""
        out = []
        while len(out) < room:
            try:
                out.append(self._items.popleft())
            except IndexError:
                break
        return out


class PowService:
    """Owns a background task that drains solve requests in batches."""

    #: minimum seconds between journal checkpoint writes per request
    CHECKPOINT_INTERVAL = 0.2

    def __init__(self, dispatcher, *, shutdown: asyncio.Event | None = None,
                 window: float | None = None, journal=None,
                 max_attempts: int = 3, retry: RetryPolicy | None = None):
        self.dispatcher = dispatcher
        self.shutdown = shutdown or asyncio.Event()
        self.window = DEFAULT_WINDOW if window is None else window
        self.journal = journal
        self.max_attempts = max(1, max_attempts)
        #: backoff between requeued batches (async sleeps in _run)
        self.retry = retry or RetryPolicy(attempts=self.max_attempts,
                                          base_delay=0.2, max_delay=5.0)
        #: journal writes run inline on the event loop, so their retry
        #: budget is µs-scale sqlite work + at most ~60 ms of backoff —
        #: NEVER the batch policy above (whose sleeps would stall all
        #: network/API I/O while a broken journal thrashes)
        self._journal_retry = RetryPolicy(attempts=3, base_delay=0.01,
                                          max_delay=0.05, jitter=0.0)
        self.queue = _RequestQueue()
        #: announced sweep members not blocked inside :meth:`solve`:
        #: the company a taken request may still wait for
        self._outstanding: set[asyncio.Task] = set()
        #: set while ``_outstanding`` is empty; what ``_run`` waits on
        self._all_arrived = asyncio.Event()
        self._all_arrived.set()
        self._task: asyncio.Task | None = None
        # injected solvers may predate the resumable-PoW kwargs or the
        # stream's — detect once and degrade to the plain call shape.
        # The stream's hooks go to a solver that names them.  One that
        # only passes ``**kwargs`` through may edit what comes back (the
        # benchmark's ``SpoiledNonces`` does, and its test holds that
        # the edit decides): its answer is its return value, until the
        # wrapper wraps ``on_solved`` too (PERF.md section 7)
        import inspect
        try:
            params = inspect.signature(dispatcher.solve_batch).parameters
            self._resumable = ("start_nonces" in params or any(
                p.kind == p.VAR_KEYWORD for p in params.values()))
            self._streams = "on_solved" in params and "feed" in params
            #: the solver can be laid out for company still to come,
            #: and says per window whether it would take it in
            self._lays_out = (self._streams and "expect" in params
                              and callable(getattr(dispatcher, "streams",
                                                   None)))
        except (TypeError, ValueError):
            self._resumable = self._streams = self._lays_out = False
        # batch/solve bookkeeping lives ONLY in the registry counters;
        # per-instance views subtract the construction-time baseline so
        # a fresh service still reports its own counts
        self._batches_base = BATCHES.value
        self._solved_base = SOLVED.value

    @property
    def batches(self) -> int:
        """Coalesced launches through THIS service instance."""
        return int(BATCHES.value - self._batches_base)

    @property
    def solved(self) -> int:
        """Requests completed through THIS service instance."""
        return int(SOLVED.value - self._solved_base)

    def start(self) -> asyncio.Task:
        self._task = asyncio.create_task(self._run())
        return self._task

    async def stop(self) -> None:
        if self._task:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass

    # -- journal plumbing ----------------------------------------------------

    def _journal_call(self, fn, site: str):
        """Run one journal write, absorbing transient failures with a
        bounded retry; a persistently broken journal degrades to
        un-journaled operation instead of failing the solve."""
        if self.journal is None:
            return None
        try:
            return self._journal_retry.call(fn, site=site)
        except Exception:
            ERRORS.labels(site=site).inc()
            logger.exception("PoW journal write failed (%s); continuing "
                             "without journal durability", site)
            return None

    def _journal_all(self, batch, op: str, method: str) -> None:
        """One journal write for the journaled requests of ``batch``,
        under one ``pow.queue.journal`` span."""
        jobs = [req.job_id for req in batch if req.job_id is not None]
        if self.journal is None or not jobs:
            return
        write = getattr(self.journal, method)
        with trace("pow.queue.journal", op=op):
            self._journal_call(lambda: write(*jobs),
                               site="pow.journal." + op)

    def _checkpoint(self, req: _Request, next_nonce: int) -> None:
        """Progress hook from the dispatcher (executor thread)."""
        req.start_nonce = max(req.start_nonce, next_nonce)
        if self.journal is None or req.job_id is None:
            return
        now = time.monotonic()
        if now - req.last_checkpoint < self.CHECKPOINT_INTERVAL:
            return
        req.last_checkpoint = now
        try:
            self.journal.checkpoint(req.job_id, next_nonce)
        except Exception:
            ERRORS.labels(site="pow.journal.checkpoint").inc()
            logger.debug("journal checkpoint failed for job %s",
                         req.job_id, exc_info=True)

    # -- API -----------------------------------------------------------------

    def announce(self, members: Iterable[asyncio.Task]) -> None:
        """A sweep names the tasks that will each call :meth:`solve`
        (once or twice), before any of them runs.  For a solver that
        streams they are the company a solve is laid out for when it
        begins at the first of them (``expect``); for any other the
        window closes when the last of them has arrived instead of on
        the timer.  A member is withdrawn when its task ends, however
        it ends (result, exception, cancellation before its first
        step)."""
        for task in members:
            self._outstanding.add(task)
            task.add_done_callback(self._withdraw)
        self._company_changed()

    def _withdraw(self, task: asyncio.Task) -> None:
        self._outstanding.discard(task)
        self._company_changed()

    def _company_changed(self) -> None:
        if self._outstanding:
            self._all_arrived.clear()
        else:
            self._all_arrived.set()

    async def _await_company(self) -> str:
        """The rule of a solver that cannot take a late member in (PR
        28's, and the only one before a solve could stream): hold a
        taken request until no announced member is missing
        (``all_arrived``) or ``window`` has run out (``timeout``);
        returns which it was.  A solver that streams is not held at
        all: :meth:`_form_batch` closes its window at the first
        arrival."""
        if self._all_arrived.is_set():
            return "all_arrived"
        if self.window <= 0:
            return "timeout"
        try:
            await asyncio.wait_for(self._all_arrived.wait(), self.window)
        except asyncio.TimeoutError:
            return "timeout"
        return "all_arrived"

    async def _form_batch(self, first: _Request):
        """The batch a solve begins with, the number of objects it is
        to be laid out for, and what closed the window over ``first``.
        With an announced member missing, a solver that streams begins
        with what is there, laid out for the missing too (each asks
        once more at least, or ends and leaves a pad slot), and takes
        them in as they come; any other solver waits for them."""
        batch = [first]
        if self._lays_out and not self._all_arrived.is_set():
            batch += self.queue.take(SOLVE_SLOTS - 1)
            expect = min(len(batch) + len(self._outstanding), SOLVE_SLOTS)
            if self.dispatcher.streams(
                    [(r.initial_hash, r.target) for r in batch], expect):
                return batch, expect, "first_arrival"
        closed = await self._await_company()
        batch += self.queue.take(SOLVE_SLOTS - len(batch))
        return batch, len(batch), closed

    async def solve(self, initial_hash: bytes, target: int):
        """Queue one solve; returns (nonce, trials) when its batch lands."""
        fut = asyncio.get_running_loop().create_future()
        req = _Request(initial_hash, target, fut, time.monotonic())
        journaled = self._journal_call(
            lambda: self.journal.add(initial_hash, target),
            site="pow.journal.add")
        if journaled is not None:
            req.job_id, req.start_nonce = journaled
            if req.start_nonce:
                logger.info("resuming journaled PoW job %d from nonce "
                            "offset %d", req.job_id, req.start_nonce)
        # lifecycle: locally-generated objects enter the timeline via
        # their pre-nonce initial hash (the inventory hash only exists
        # after the winning nonce is prepended)
        LIFECYCLE.record(initial_hash, "pow_queued")
        # the job joins (or opens) the object's wire trace: submit and
        # every requeue carry the id, so a job bounced between
        # processes remains one causal trace
        ctx = LIFECYCLE.trace_ctx_for(initial_hash)
        if ctx is not None:
            req.trace_id = ctx.trace_id
        self.queue.put_nowait(req)
        QUEUE_DEPTH.set(self.queue.qsize())
        # an announced member has arrived: it is nobody's missing
        # company until its result is back and it may ask again
        me = asyncio.current_task()
        member = me in self._outstanding
        if member:
            self._withdraw(me)
        try:
            result = await fut
        finally:
            if member:
                self._outstanding.add(me)
                self._company_changed()
        waited = current_span()     # the caller's span: worker.pow
        if waited is not None:
            waited.attrs["batch"] = req.batch
        return result

    # -- drain loop ----------------------------------------------------------

    async def _run(self) -> None:
        while True:
            first = await self.queue.get()
            seq = next(_BATCH_SEQ)
            # this task's spans, and through the executor hop below
            # every span of the solve, carry the batch's number
            set_batch(seq)
            with trace("pow.queue.window") as window:
                batch, expect, closed = await self._form_batch(first)
                window.attrs["objects"] = len(batch)
                window.attrs["expect"] = expect
                window.attrs["closed"] = closed
            WINDOW_CLOSED.labels(reason=closed).inc()
            #: every request the solve has taken in, by its item number
            taken: list[_Request] = []
            self._take_in(taken, batch, seq, window.end)
            items = [(r.initial_hash, r.target) for r in batch]
            starts = [r.start_nonce for r in batch]
            loop = asyncio.get_running_loop()
            cancelled = False

            def progress(i, next_nonce):
                self._checkpoint(taken[i], next_nonce)

            #: hits the loop has yet to resolve, and whether it has
            #: been woken for them: a harvest's hits share one wake
            found: deque = deque()
            woken = [False]

            def resolve_found():
                # the flag falls before the drain, so a hit that finds
                # it raised was appended before the drain began
                woken[0] = False
                while found:
                    self._resolve(*found.popleft())

            def on_solved(i, result):
                # solving thread: the future is the loop's to resolve.
                # A lone object leaves nobody behind (a solve that starts
                # alone, with no company announced, takes nobody in): the
                # solve's return resolves it, as ever, once the solve's
                # books are closed
                if expect == 1 and len(taken) == 1:
                    return
                found.append((taken[i], result, time.monotonic()))
                if woken[0]:
                    return
                woken[0] = True
                try:
                    loop.call_soon_threadsafe(resolve_found)
                except RuntimeError:        # the loop has been closed
                    raise PowInterrupted("event loop closed") from None

            def feed(room):
                # solving thread: what has arrived since the last launch
                # (nothing more once this task has been cancelled: the
                # thread may outlive it)
                arrived = [] if cancelled else self.queue.take(room)
                if arrived:
                    self._take_in(taken, arrived, seq, time.monotonic())
                return [(r.initial_hash, r.target, r.start_nonce)
                        for r in arrived]

            kwargs = {"should_stop": self.shutdown.is_set}
            if self._resumable:
                kwargs.update(start_nonces=starts, progress=progress)
            if self._streams:
                kwargs.update(on_solved=on_solved, feed=feed)
            if self._lays_out:
                kwargs.update(expect=expect)
            results = ()
            try:
                # run_in_executor does not carry contextvars: copy the
                # context so the solve's spans keep batch and parent
                results = await loop.run_in_executor(
                    None, contextvars.copy_context().run,
                    lambda: self.dispatcher.solve_batch(items, **kwargs))
            except asyncio.CancelledError:
                cancelled = True
                self._settle_interrupted(_unresolved(taken))
                raise
            except PowInterrupted:
                # shutdown-driven: jobs stay journaled for the next
                # process; the futures cancel so callers unwind
                self._settle_interrupted(_unresolved(taken))
                continue
            except Exception as exc:
                await self._requeue_failed(_unresolved(taken), exc)
                continue
            finally:
                BATCH_SIZE.observe(len(taken))
            BATCHES.inc()
            if len(taken) > 1:
                logger.info("batched PoW: %d objects in one solve (%s)",
                            len(taken), self.dispatcher.last_backend)
            # a solver that knows nothing of ``on_solved`` answers here
            for req, res in zip(taken, results):
                self._resolve(req, res)

    def _take_in(self, taken: list, reqs: list, seq: int,
                 now: float) -> None:
        """``reqs`` join solve ``seq``: the first batch on the loop, a
        refill on the solving thread."""
        for req in reqs:
            req.batch = seq
            QUEUE_WAIT.observe(now - req.enqueued)
        QUEUE_DEPTH.set(self.queue.qsize())
        self._journal_all(reqs, "inflight", "mark_inflight")
        taken.extend(reqs)

    def _resolve(self, req: _Request, result,
                 found: float | None = None) -> None:
        """One object's nonce is known and re-checked: its journal row
        is complete and its future resolves, whatever the rest of its
        solve is still doing.  ``found`` is when the harvest had it."""
        if req.future.done():
            return
        SOLVED.inc()
        self._journal_all((req,), "complete", "complete")
        LIFECYCLE.record(req.initial_hash, "pow_solved")
        if found is not None:
            RESOLVE_LAG.observe(max(time.monotonic() - found, 0.0))
        req.future.set_result(result)

    @staticmethod
    def _trace_ids(batch: list[_Request]) -> list[str]:
        """Short trace-id prefixes for flight events (bounded)."""
        return [r.trace_id.hex()[:8] for r in batch[:8] if r.trace_id]

    def _settle_interrupted(self, batch: list[_Request]) -> None:
        REQUEUED.labels(reason="interrupt").inc(len(batch))
        _flight("pow_requeue", reason="interrupt", n=len(batch),
                traces=self._trace_ids(batch))
        for req in batch:
            if req.job_id is not None:
                self._journal_call(
                    lambda j=req.job_id: self.journal.requeue(j),
                    site="pow.journal.requeue")
            if not req.future.done():
                req.future.cancel()

    async def _requeue_failed(self, batch: list[_Request],
                              exc: Exception) -> None:
        """A dispatcher failure must never lose a queued object: every
        request goes back on the queue (with backoff) until it exceeds
        ``max_attempts``; exhausted requests surface the error to the
        caller but STAY journaled for the next process."""
        survivors = []
        for req in batch:
            req.attempts += 1
            if req.job_id is not None:
                self._journal_call(
                    lambda j=req.job_id: self.journal.requeue(j),
                    site="pow.journal.requeue")
            if req.attempts >= self.max_attempts:
                REQUEUED.labels(reason="exhausted").inc()
                logger.error(
                    "PoW solve failed after %d attempts; surfacing the "
                    "error to the caller (job stays journaled)",
                    req.attempts)
                if not req.future.done():
                    req.future.set_exception(exc)
            else:
                survivors.append(req)
        if not survivors:
            return
        REQUEUED.labels(reason="failure").inc(len(survivors))
        _flight("pow_requeue", reason="failure", n=len(survivors),
                error=repr(exc)[:120],
                traces=self._trace_ids(survivors))
        attempt = min(r.attempts for r in survivors) - 1
        pause = self.retry.delay(attempt)
        logger.warning(
            "dispatcher failed (%r); requeueing %d solve(s), attempt "
            "%d/%d after %.2fs backoff", exc, len(survivors),
            attempt + 2, self.max_attempts, pause)
        try:
            await asyncio.sleep(pause)
        except asyncio.CancelledError:
            self._settle_interrupted(survivors)
            raise
        for req in survivors:
            self.queue.put_nowait(req)
        QUEUE_DEPTH.set(self.queue.qsize())
