"""Closed-loop sender: one client that submits a sweep of sends
together and submits the next sweep when every send of the last one
has been published — a sender waits.

Parameters (a traffic file under ``traffic/``):

``send``          ``message`` (to the deployment's recipient) or
                  ``broadcast`` (from its chan identity)
``sweep``         sends submitted together
``body_bytes``    ``[[weight, low, high], ...]``: the size mix
``warm_verify_batches``  batch sizes of incoming PoW checks to warm on
                  the sender (its acks come back in such batches)
``warm_quiet_sweeps`` / ``warm_max_sweeps``  warm-up ends after this
                  many consecutive sweeps without a compilation
``warm_lone_sends``  (optional, 0) sends made one at a time after the
                  first warm-up sweep, for a cell whose sweeps now and
                  then leave an object alone (see ``send_alone``)

Every seed sends the same set of sizes, in another order: the sizes of
a sweep are the mix's quantiles, shuffled by the seed.

Every send is timed by itself (``t_submit`` before its call,
``t_done`` from the poll that first sees its final status).  A sweep of
one polls its one handle every 2 ms.  A sweep of more polls every 20 ms
with ONE pass over the sent table (``changed``: the rows whose status
was stamped since the pass before), and every pending send the pass
finds published gets that pass's time: watching the head of the line
alone would give each send the time of the slowest before it.
"""

from __future__ import annotations

import asyncio
import string
import time
from dataclasses import dataclass

SENT_STATES = {"message": ("msgsent", "ackreceived",
                           "msgsentnoackexpected"),
               "broadcast": ("broadcastsent",)}
FAILED_STATES = ("badkey", "toodifficult", "notfound")
#: seconds one sweep may take before the run gives up
SWEEP_TIMEOUT = 300.0
#: seconds between two polls: of the one handle of a sweep of one, and
#: between two passes over a sweep of more
POLL_ONE, POLL_SWEEP = 0.002, 0.02
CHANGED = "SELECT ackdata, status FROM sent WHERE lastactiontime >= ?"


@dataclass
class Sent:
    subject: str
    body: str
    handle: bytes
    t_submit: float
    t_done: float | None = None
    status: str = ""


def changed(node, read_from: int) -> list[tuple[bytes, str]]:
    """One pass: ``(handle, status)`` of the sent rows stamped in the
    wall-clock second ``read_from`` or later.  ``lastactiontime`` is in
    whole seconds, so a caller reads from the second its last pass
    began in and meets a row of that second again."""
    return [(bytes(handle), status) for handle, status
            in node.db.query(CHANGED, (read_from,))]


def sweep_sizes(mix, n: int) -> list[int]:
    """``n`` sizes at the quantiles of the mix ``[[weight, lo, hi]]``."""
    total = sum(w for w, _lo, _hi in mix)
    sizes = []
    for i in range(n):
        u = (i + 0.5) / n * total
        acc = 0.0
        for w, lo, hi in mix:
            if u <= acc + w or (w, lo, hi) == tuple(mix[-1]):
                frac = min(max((u - acc) / w, 0.0), 1.0)
                sizes.append(int(round(lo + frac * (hi - lo))))
                break
            acc += w
    return sizes


class Generator:
    def __init__(self, params: dict, rng):
        self.params = params
        self.rng = rng
        self.kind = params["send"]
        if self.kind not in SENT_STATES:
            raise ValueError("send must be message or broadcast")
        self.sweep_size = int(params["sweep"])
        self._sizes = sweep_sizes(params["body_bytes"], self.sweep_size)
        self._n = 0
        #: wall-clock second the last pass began in (0: none yet)
        self._read_from = 0

    def _bodies(self) -> list[str]:
        sizes = list(self._sizes)
        self.rng.shuffle(sizes)
        letters = string.ascii_letters + string.digits + "    "
        return ["".join(self.rng.choices(letters, k=size))
                for size in sizes]

    async def sweep(self, dep, tag: str) -> list[Sent]:
        """Submit one sweep and wait until all of it is published."""
        import jax.profiler as prof
        node = dep.sender
        ttl = dep.config["ttl"]
        bodies = self._bodies()
        sent: list[Sent] = []
        with prof.TraceAnnotation("bench.submit"):
            for body in bodies:
                self._n += 1
                subject = "%s-%d" % (tag, self._n)
                t = time.monotonic()
                if self.kind == "message":
                    handle = await node.send_message(
                        dep.to_address, dep.from_address, subject, body,
                        ttl=ttl)
                else:
                    handle = await node.send_broadcast(
                        dep.from_address, subject, body, ttl=ttl)
                sent.append(Sent(subject, body, handle, t))
        with prof.TraceAnnotation("bench.wait_published"):
            if self.sweep_size == 1:
                await self._wait_one(node, sent[0])
            else:
                await self._wait_each(node, sent)
        return sent

    async def _wait_one(self, node, rec: Sent) -> None:
        """A sweep of one: its handle's status every ``POLL_ONE``."""
        deadline = time.monotonic() + SWEEP_TIMEOUT
        while True:
            rec.status = node.message_status(rec.handle)
            if rec.status in SENT_STATES[self.kind]:
                rec.t_done = time.monotonic()
                return
            if (rec.status in FAILED_STATES
                    or time.monotonic() > deadline):
                return
            await asyncio.sleep(POLL_ONE)

    async def _wait_each(self, node, sent: list[Sent]) -> None:
        """A sweep of more: one pass every ``POLL_SWEEP`` stamps every
        send it first sees published; a failed one leaves unstamped."""
        deadline = time.monotonic() + SWEEP_TIMEOUT
        pending = {bytes(rec.handle): rec for rec in sent}
        while pending and time.monotonic() <= deadline:
            read_from, self._read_from = self._read_from, int(time.time())
            # a second further back than the last pass began in: the
            # program stamps a row before it writes it, and a write
            # that waits for the table's lock across a second's end
            # and a pass would never be read
            for handle, status in changed(node, read_from - 1):
                rec = pending.get(handle)
                if rec is None:
                    continue
                rec.status = status
                if status in SENT_STATES[self.kind]:
                    rec.t_done = time.monotonic()
                elif status not in FAILED_STATES:
                    continue
                del pending[handle]
            if pending:
                await asyncio.sleep(POLL_SWEEP)

    async def warm_receive_shapes(self, dep) -> None:
        """Once, after the first warm-up sweep: what the window will
        meet and a whole sweep need not have run.  The lone object's
        program where the traffic file asks for it, and the sender's
        incoming-PoW check at each batch size its returning acks can
        arrive in."""
        await send_alone(self, dep)
        sizes = self.params.get("warm_verify_batches") or []
        if not sizes:
            return
        inv = dep.sender.inventory
        objs = [bytes(inv[h].payload) for h in inv.hashes()]
        if not objs:
            return
        verifier = dep.sender.pow_verifier
        for n in sizes:
            batch = [objs[i % len(objs)] for i in range(n)]
            await asyncio.gather(*(verifier.check(o) for o in batch))


async def send_alone(gen, dep) -> None:
    """``warm_lone_sends`` sends of ``gen``'s kind, each submitted when
    the one before is published, so that each of its objects asks for
    its proof of work with nobody beside it.  A sweep's last straggler
    can be such an object, laid out for nobody, and the pipeline gives
    it another shape than a queue's: ``pallas_search`` on every chip
    (plan mode ``slab`` on lanes), which no whole sweep need ever
    launch.  Left to chance its first use, and with it its tracing
    and lowering, falls inside the measured window in most runs
    (PERF.md, PR 38; 24 s for the partition's program then, seconds
    for the pipeline's since PR 43); asked for here it is set-up."""
    lone = Generator(dict(gen.params, sweep=1), gen.rng)
    for k in range(int(gen.params.get("warm_lone_sends") or 0)):
        sent = await lone.sweep(dep, "lone%d" % k)
        if any(s.t_done is None for s in sent):
            raise RuntimeError("a lone warm-up send did not publish: %s"
                               % sorted({s.status for s in sent}))


def make(params: dict, rng) -> Generator:
    return Generator(params, rng)
