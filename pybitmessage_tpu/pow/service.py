"""Async PoW front-end: coalesces concurrent solves into one batch.

The reference worker solves strictly one object at a time
(src/class_singleWorker.py:1274-1276).  Here every concurrently pending
solve joins a single pod-wide launch: requests are queued, a short
coalescing window lets the rest of a send sweep arrive, and the whole
batch goes through :meth:`PowDispatcher.solve_batch` — objects
data-parallel over the mesh's object axis, each nonce range partitioned
over the remaining chips (SURVEY §6: grid = nonce-lanes x objects).

``window`` is the LONGEST a queued object waits for company, not a
fixed delay (the latency/batching tradeoff called out in SURVEY §7:
dynamic batch assembly with padding, no recompilation per batch size
thanks to the object-axis padding in ``sharded_solve_batch``).  A send
sweep announces its member tasks (:meth:`PowService.announce`) before
any of them runs; a member is *outstanding* while it is announced and
not blocked inside :meth:`PowService.solve`, and is withdrawn when its
task ends, however it ends.  The window closes as soon as nobody
outstanding is missing, or after ``window`` seconds, whichever is
first: a lone send's ack and message are each dispatched on arrival,
a sweep of 256 when its last member arrives.  A request with no
announced company is not held at all.

Resilience (ISSUE 3, docs/resilience.md):

- a dispatcher failure REQUEUES the in-flight batch with exponential
  backoff instead of dropping it — a transient tier failure never
  loses a queued object; only ``max_attempts`` consecutive failures
  surface the error to the caller (and the job stays journaled);
- with a :class:`~pybitmessage_tpu.resilience.journal.PowJournal`
  attached, every request is journaled before it is queued, search
  progress is checkpointed as slabs harvest, and completion deletes
  the row — queued/in-flight objects survive a process crash and a
  resumed solve continues from its checkpointed nonce offset.
"""

from __future__ import annotations

import asyncio
import contextvars
import itertools
import logging
import time
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
    "Objects coalesced into one solve_batch launch (window occupancy)",
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
    "member of the sweep had arrived (all_arrived) or the window ran "
    "out with a member still missing (timeout)", ("reason",))
REQUEUED = REGISTRY.counter(
    "pow_requeue_total",
    "Solve requests put back on the queue after a dispatcher failure "
    "or interrupt — the no-object-loss path", ("reason",))

#: sequence numbers of coalesced batches, process-wide: the ``batch``
#: attribute that ties a request's ``worker.pow`` span to the spans of
#: the ``solve_batch`` that served it
_BATCH_SEQ = itertools.count(1)

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
        self.queue: asyncio.Queue = asyncio.Queue()
        #: announced sweep members not blocked inside :meth:`solve`:
        #: the company a taken request may still wait for
        self._outstanding: set[asyncio.Task] = set()
        #: set while ``_outstanding`` is empty; what ``_run`` waits on
        self._all_arrived = asyncio.Event()
        self._all_arrived.set()
        self._task: asyncio.Task | None = None
        # injected solvers may predate the resumable-PoW kwargs —
        # detect once and degrade to the plain call shape
        import inspect
        try:
            params = inspect.signature(dispatcher.solve_batch).parameters
            self._resumable = ("start_nonces" in params or any(
                p.kind == p.VAR_KEYWORD for p in params.values()))
        except (TypeError, ValueError):
            self._resumable = False
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

    def _journal_each(self, batch, op: str, method: str) -> None:
        """One journal write per journaled request of ``batch``, all
        under one ``pow.queue.journal`` span."""
        if self.journal is None:
            return
        write = getattr(self.journal, method)
        with trace("pow.queue.journal", op=op):
            for req in batch:
                if req.job_id is not None:
                    self._journal_call(lambda j=req.job_id: write(j),
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
        (once or twice), before any of them runs: the window then
        closes when the last of them has arrived instead of on the
        timer.  A member is withdrawn when its task ends, however it
        ends (result, exception, cancellation before its first step)."""
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
        """Hold a taken request until no announced member is missing
        or ``window`` has run out; returns which it was."""
        if self._all_arrived.is_set():
            return "all_arrived"
        if self.window <= 0:
            return "timeout"
        try:
            await asyncio.wait_for(self._all_arrived.wait(), self.window)
        except asyncio.TimeoutError:
            return "timeout"
        return "all_arrived"

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
        await self.queue.put(req)
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
                closed = await self._await_company()
                batch = [first]
                while not self.queue.empty():
                    batch.append(self.queue.get_nowait())
                window.attrs["objects"] = len(batch)
                window.attrs["closed"] = closed
            WINDOW_CLOSED.labels(reason=closed).inc()
            for req in batch:
                req.batch = seq
                QUEUE_WAIT.observe(window.end - req.enqueued)
            BATCH_SIZE.observe(len(batch))
            QUEUE_DEPTH.set(self.queue.qsize())
            items = [(r.initial_hash, r.target) for r in batch]
            starts = [r.start_nonce for r in batch]
            self._journal_each(batch, "inflight", "mark_inflight")

            def progress(i, next_nonce, _batch=batch):
                self._checkpoint(_batch[i], next_nonce)

            kwargs = {"should_stop": self.shutdown.is_set}
            if self._resumable:
                kwargs.update(start_nonces=starts, progress=progress)
            loop = asyncio.get_running_loop()
            try:
                # run_in_executor does not carry contextvars: copy the
                # context so the solve's spans keep batch and parent
                results = await loop.run_in_executor(
                    None, contextvars.copy_context().run,
                    lambda: self.dispatcher.solve_batch(items, **kwargs))
            except asyncio.CancelledError:
                self._settle_interrupted(batch)
                raise
            except PowInterrupted:
                # shutdown-driven: jobs stay journaled for the next
                # process; the futures cancel so callers unwind
                self._settle_interrupted(batch)
                continue
            except Exception as exc:
                await self._requeue_failed(batch, exc)
                continue
            BATCHES.inc()
            SOLVED.inc(len(batch))
            if len(batch) > 1:
                logger.info("batched PoW: %d objects in one launch (%s)",
                            len(batch), self.dispatcher.last_backend)
            self._journal_each(batch, "complete", "complete")
            for req, res in zip(batch, results):
                LIFECYCLE.record(req.initial_hash, "pow_solved")
                if not req.future.done():
                    req.future.set_result(res)

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
