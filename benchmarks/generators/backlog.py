"""Backlog sender: one client whose outbox is kept full — a bot or a
gateway with more queued sends than the node can have in flight.

``sweep`` submits sends until ``backlog`` of them are outstanding
(submitted and in no final state), then returns once at least
``report`` more have reached a final state, with every record that did
so during the call.  The first call submits the whole backlog together;
every later call tops it up by what the last one returned.  A send in
a failed state, or outstanding for longer than ``LOST_AFTER`` seconds,
comes back with ``t_done`` None, so a lost send fails the run and is
not left in the backlog unseen.  A call in which no send ends for
``STALLED_AFTER`` seconds raises: the outbox is not being drained as a
stream, and the run ends there, non-zero, instead of going on for
minutes.  What the node solved while the harness
stood between two calls is no call's.  The harness holds the event loop
there (it waits for the device and reads the launch log), so nothing
is published meanwhile, but the device searches on and the loop, once
free, publishes a second's hits at a stroke: the first call of a
measured window would be credited them.  So a call that begins more
than ``POLL_SECONDS`` after the last one returned lets the node catch
up for one poll, and a pass then takes out what has ended; the call's
line counts them.

Parameters (a traffic file under ``traffic/``):

``send``          ``message`` (to the deployment's recipient) or
                  ``broadcast`` (from its chan identity)
``backlog``       sends outstanding throughout
``report``        sends that reach a final state before a call returns
``body_bytes``    ``[[weight, low, high], ...]``: the size mix
``warm_verify_batches``, ``warm_quiet_sweeps``, ``warm_max_sweeps``
                  as for ``closed_loop``

Every seed sends the same set of sizes, in another order: the sizes of
one backlog are the mix's quantiles, shuffled by the seed, and shuffled
again each time they have all been sent.

Statuses are read in one pass over the sent table, at most every
``POLL_SECONDS``: one query for the rows whose status changed since the
pass before (``lastactiontime`` is stamped in whole seconds, so a pass
reads from the second the last one began in).  Reading each of a
thousand handles by itself would cost a table scan a handle.  Each call
prints what its passes cost, as a share of the call's length.
"""

from __future__ import annotations

import asyncio
import string
import time

from benchmarks.generators.closed_loop import (FAILED_STATES, SENT_STATES,
                                               Generator as ClosedLoop,
                                               Sent, changed, sweep_sizes)

#: seconds between two passes over the sent table
POLL_SECONDS = 0.1
#: seconds a send may be outstanding before the run gives it up
LOST_AFTER = 300.0
#: seconds a call may wait with no send ending before the run is given
#: up.  A node that drains the outbox as a stream ends its first send
#: 15-20 s after the outbox filled (compiling included) and one every
#: few seconds from then on; one that resolves nothing until a whole
#: sweep of the outbox has solved ends its first after 100-110 s and
#: needs 7.6 minutes a run (PERF.md, PR 32), more than a run is given.
STALLED_AFTER = 60.0


class Generator:
    def __init__(self, params: dict, rng):
        self.params = params
        self.rng = rng
        self.kind = params["send"]
        if self.kind not in SENT_STATES:
            raise ValueError("send must be message or broadcast")
        self.backlog = int(params["backlog"])
        self.report = int(params["report"])
        self._sizes = sweep_sizes(params["body_bytes"], self.backlog)
        self._left: list[int] = []      # sizes of this shuffle not yet sent
        self._outstanding: dict[bytes, Sent] = {}
        self._n = 0
        #: wall-clock second the last pass began in (0: none yet)
        self._read_from = 0
        #: when the last call returned (None: none has)
        self._t_returned: float | None = None

    # the incoming-PoW check is warmed as the closed loop warms it
    warm_receive_shapes = ClosedLoop.warm_receive_shapes

    def _body(self) -> str:
        if not self._left:
            self._left = list(self._sizes)
            self.rng.shuffle(self._left)
        letters = string.ascii_letters + string.digits + "    "
        return "".join(self.rng.choices(letters, k=self._left.pop()))

    async def _submit(self, dep, tag: str) -> None:
        node = dep.sender
        ttl = dep.config["ttl"]
        self._n += 1
        subject = "%s-%d" % (tag, self._n)
        body = self._body()
        t = time.monotonic()
        if self.kind == "message":
            handle = await node.send_message(
                dep.to_address, dep.from_address, subject, body, ttl=ttl)
        else:
            handle = await node.send_broadcast(
                dep.from_address, subject, body, ttl=ttl)
        self._outstanding[bytes(handle)] = Sent(subject, body, handle, t)

    def _ended(self, dep) -> list[Sent]:
        """One pass: the outstanding sends that have reached a final or
        a failed state, or have been outstanding too long."""
        read_from, self._read_from = self._read_from, int(time.time())
        out = []
        for handle, status in changed(dep.sender, read_from):
            rec = self._outstanding.get(handle)
            if rec is None:
                continue
            rec.status = status
            if status in SENT_STATES[self.kind]:
                rec.t_done = time.monotonic()
            elif status not in FAILED_STATES:
                continue
            out.append(self._outstanding.pop(rec.handle))
        lost = time.monotonic() - LOST_AFTER
        for rec in [r for r in self._outstanding.values()
                    if r.t_submit < lost]:
            out.append(self._outstanding.pop(rec.handle))
        return out

    async def sweep(self, dep, tag: str) -> list[Sent]:
        """Top the outbox up, then wait for ``report`` sends to end."""
        import jax.profiler as prof
        t_call = time.monotonic()
        # what was solved while nobody called; a failed or lost send is
        # returned all the same
        early = []
        if (self._t_returned is not None
                and t_call - self._t_returned > POLL_SECONDS):
            await asyncio.sleep(POLL_SECONDS)
            early = self._ended(dep)
        ended = [rec for rec in early if rec.t_done is None]
        between = len(early) - len(ended)
        with prof.TraceAnnotation("bench.submit"):
            while len(self._outstanding) < self.backlog:
                await self._submit(dep, tag)
        passes, cost = 0, 0.0
        t_moved, still = time.monotonic(), 0.0
        with prof.TraceAnnotation("bench.wait_published"):
            while len(ended) < self.report and self._outstanding:
                await asyncio.sleep(POLL_SECONDS)
                t = time.perf_counter()
                more = self._ended(dep)
                passes += 1
                cost += time.perf_counter() - t
                still = max(still, time.monotonic() - t_moved)
                if more:
                    ended.extend(more)
                    t_moved = time.monotonic()
                elif still > STALLED_AFTER:
                    raise RuntimeError(
                        "%s: no send of %d outstanding has ended for "
                        "%.0f s: the outbox is not drained as a stream"
                        % (tag, len(self._outstanding), STALLED_AFTER))
        took = max(time.monotonic() - t_call, 1e-9)
        print("[backlog] %s: %d ended, %d more between calls, %d "
              "outstanding, %.1fs, at most %.1fs with none ending; %d "
              "status passes took %.1f ms, %.2f%% of the call"
              % (tag, len(ended), between, len(self._outstanding), took,
                 still, passes, cost * 1e3, 100.0 * cost / took),
              flush=True)
        self._t_returned = time.monotonic()
        return ended


def make(params: dict, rng) -> Generator:
    return Generator(params, rng)
