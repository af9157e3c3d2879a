"""Rolling admission in ``SendWorker`` (ISSUE 32), on the CPU.

A pair of nodes at test difficulty (the benchmark's own deployment
builder) whose sender is given a solver that streams: it resolves what
it holds one object at a time, asks ``feed`` before each, and can hold
one object back.  Held here: a send queued while 64 are in flight gets
its ack's nonce before the 64th message is published — the sender does
not hold it for the sweep's end, the service hands it to the running
solve — and sends in flight never exceed ``MAX_IN_FLIGHT`` while a
longer outbox still drains to its end — also when one command stands
for all of it, as after a restart or when a contact's key arrives, and
for broadcasts, whose rows keep their queued status while in flight.
"""

import asyncio
import json
import pathlib
import sys
import threading
import time
from collections import deque

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks.deployments import build, wait_for  # noqa: E402
from pybitmessage_tpu.observability import TRACER  # noqa: E402
from pybitmessage_tpu.ops.pow_search import PowInterrupted  # noqa: E402
from pybitmessage_tpu.pow.dispatcher import python_solve  # noqa: E402
from pybitmessage_tpu.workers import sender as sender_module  # noqa: E402

DONE = ("msgsent", "ackreceived")
#: at ntpb 10 / extra 10 an ack (54 B, TTL a week) needs about 6,500
#: trials and the shortest message here about 25,000
IS_ACK = 2 ** 64 // 12000


def _config() -> dict:
    cfg = json.loads((REPO / "benchmarks" / "configs"
                      / "sender_default.json").read_text())
    cfg.update(test_mode=True, ntpb=10, extra=10)
    return cfg


class Streaming:
    """A solver whose solve is a stream, oldest object first.  With
    ``hold_a_message`` the first message it comes to is set aside until
    ``release``."""

    last_backend = "fake"

    def __init__(self, hold_a_message: bool = False):
        self.hold = hold_a_message
        self.parked: list[int] = []
        self.release = threading.Event()
        self.resolved: list[str] = []       # "ack" | "msg", in order
        #: what the solve that holds the message back resolved beside it
        self.beside_parked: list[str] = []
        self.probe = lambda: None

    def solve_batch(self, items, *, should_stop=None, start_nonces=None,
                    progress=None, on_solved=None, feed=None):
        items = list(items)
        results = [None] * len(items)
        todo = deque(range(len(items)))
        while todo or self.parked:
            if should_stop is not None and should_stop():
                raise PowInterrupted("stopped")
            for ih, target, _start in feed(256):
                items.append((ih, target))
                results.append(None)
                todo.append(len(items) - 1)
            self.probe()
            if not todo:
                if self.release.is_set():
                    todo.extend(self.parked)
                    self.parked.clear()
                else:
                    time.sleep(0.002)
                continue
            i = todo.popleft()
            kind = "ack" if items[i][1] > IS_ACK else "msg"
            if self.hold and kind == "msg":
                self.hold = False
                self.parked.append(i)
                continue
            results[i] = python_solve(*items[i])
            self.resolved.append(kind)
            if self.parked:
                self.beside_parked.append(kind)
            on_solved(i, results[i])
        return results


async def _send(dep, n: int, tag: str) -> list[bytes]:
    return [await dep.sender.send_message(
        dep.to_address, dep.from_address, "%s-%d" % (tag, i), "x" * 200,
        ttl=dep.config["ttl"]) for i in range(n)]


def _published(dep, handles) -> int:
    return sum(dep.sender.message_status(h) in DONE for h in handles)


@pytest.mark.asyncio
async def test_a_send_queued_behind_64_is_not_held_for_their_end():
    solver = Streaming(hold_a_message=True)
    dep = await build(_config(), solver)
    try:
        first = await _send(dep, 64, "first")
        assert await wait_for(
            lambda: _published(dep, first) == 63 and solver.parked, 120)
        # 63 are out and the 64th message is still being searched for
        acks0 = solver.beside_parked.count("ack")
        late = await _send(dep, 1, "late")
        # the late send gets its ack's nonce from the solve that is
        # still searching for that message: the sender did not hold it
        # for the sweep's end, the running solve took it in
        assert await wait_for(
            lambda: solver.beside_parked.count("ack") == acks0 + 1, 60)
        assert solver.resolved.count("ack") == 65
        assert _published(dep, first) == 63 and solver.parked
        solver.release.set()
        assert await wait_for(
            lambda: _published(dep, first + late) == 65, 120)
    finally:
        solver.release.set()
        await dep.stop()
    assert solver.resolved.count("msg") == 65


@pytest.mark.asyncio
async def test_sends_in_flight_never_exceed_the_constant(monkeypatch):
    monkeypatch.setattr(sender_module, "MAX_IN_FLIGHT", 4)
    solver = Streaming()
    dep = await build(_config(), solver)
    seen = []
    solver.probe = lambda: seen.append(len(dep.sender.sender._in_flight))
    try:
        sweeps0 = len(TRACER.recent(10000, name="sender.sweep"))
        handles = await _send(dep, 11, "many")
        # the outbox drains to its end, four at a time
        assert await wait_for(
            lambda: _published(dep, handles) == 11, 120), (
            [dep.sender.message_status(h) for h in handles],
            dep.sender.sender._in_flight, dep.sender.sender._held,
            dep.sender.pow_service.queue.qsize(), solver.resolved)
        assert not dep.sender.sender._in_flight
    finally:
        await dep.stop()
    assert seen and max(seen) == 4
    sweeps = TRACER.recent(10000, name="sender.sweep")[sweeps0:]
    mine = [s for s in sweeps if s.attrs.get("kind") == "message"]
    assert sum(s.attrs["objects"] for s in mine) == 11
    assert all(0 < s.attrs["in_flight"] <= 4 for s in mine)
    # the first sweep filled the room; each later one took what a send
    # that ended had left free
    assert mine[0].attrs["objects"] == 4


async def _queue_rows(dep, kind: str, n: int) -> list[bytes]:
    """``n`` queued rows of ``kind`` whose commands go nowhere: what a
    restart finds in the sent table."""
    worker = dep.sender.sender
    real, worker.queue = worker.queue, asyncio.Queue()
    try:
        if kind == "message":
            return await _send(dep, n, "outbox")
        return [await dep.sender.send_broadcast(
            dep.from_address, "outbox-%d" % i, "x" * 200,
            ttl=dep.config["ttl"]) for i in range(n)]
    finally:
        worker.queue = real


@pytest.mark.asyncio
@pytest.mark.parametrize("kind, command, done", [
    ("message", "sendmessage", DONE),
    ("broadcast", "sendbroadcast", ("broadcastsent",)),
])
async def test_one_command_drains_an_outbox_longer_than_the_constant(
        monkeypatch, kind, command, done):
    monkeypatch.setattr(sender_module, "MAX_IN_FLIGHT", 4)
    solver = Streaming()
    dep = await build(_config(), solver)
    worker = dep.sender.sender
    seen = []
    solver.probe = lambda: seen.append(len(worker._in_flight))
    try:
        handles = await _queue_rows(dep, kind, 11)
        assert not worker._in_flight and worker.queue.empty()
        # the one command SendWorker.start() sends, and the processor
        # when a pubkey arrives
        worker.queue.put_nowait((command,))
        assert await wait_for(
            lambda: all(dep.sender.message_status(h) in done
                        for h in handles), 120), (
            [dep.sender.message_status(h) for h in handles],
            worker._in_flight, worker._held)
        assert not worker._in_flight and not worker._held
    finally:
        await dep.stop()
    assert seen and max(seen) == 4
