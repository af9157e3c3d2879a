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

Every seed sends the same set of sizes, in another order: the sizes of
a sweep are the mix's quantiles, shuffled by the seed.
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


@dataclass
class Sent:
    subject: str
    body: str
    handle: bytes
    t_submit: float
    t_done: float | None = None
    status: str = ""


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
        done_states = SENT_STATES[self.kind]
        # a single send is timed, so it is polled closely; a sweep
        # resolves as a whole, so one pending send is watched
        poll = 0.002 if self.sweep_size == 1 else 0.02
        deadline = time.monotonic() + SWEEP_TIMEOUT
        pending = list(sent)
        with prof.TraceAnnotation("bench.wait_published"):
            while pending:
                head = pending[0]
                head.status = node.message_status(head.handle)
                if head.status in done_states:
                    head.t_done = time.monotonic()
                    pending.pop(0)
                    continue
                if head.status in FAILED_STATES:
                    pending.pop(0)
                    continue
                if time.monotonic() > deadline:
                    break
                await asyncio.sleep(poll)
        return sent

    async def warm_receive_shapes(self, dep) -> None:
        """Run the sender's incoming-PoW check once at each batch size
        its returning acks can arrive in."""
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


def make(params: dict, rng) -> Generator:
    return Generator(params, rng)
