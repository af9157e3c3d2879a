"""What closes ``PowService``'s coalescing window (no device).

A sweep announces its member tasks; the window closes when nobody who
could still join is missing, with ``window`` kept as the upper bound.
A fake dispatcher records the batches it is handed.
"""

import asyncio
import contextlib
import time

import pytest

from pybitmessage_tpu.observability import REGISTRY, TRACER
from pybitmessage_tpu.pow import PowService
from pybitmessage_tpu.pow.service import QUEUE_WAIT
from pybitmessage_tpu.workers.sender import SendWorker

TARGET = 1 << 60
#: a window no test may ever sit out: anything it closes took the timer
LONG = 5.0


class FakeDispatcher:
    """Solves nothing; keeps the size of every batch it was handed."""

    last_backend = "fake"

    def __init__(self):
        self.batches: list[int] = []

    def solve_batch(self, items, should_stop=None):
        self.batches.append(len(items))
        return [(7, 1)] * len(items)


def _hash(i: int) -> bytes:
    return bytes([i % 256]) * 64


def _closed(reason: str) -> float:
    return REGISTRY.sample("pow_window_closed_total", {"reason": reason})


def _window_spans() -> list:
    return TRACER.recent(1000, name="pow.queue.window")


@contextlib.asynccontextmanager
async def _service(window: float):
    """A started service over a fake dispatcher, stopped on exit."""
    dispatcher = FakeDispatcher()
    service = PowService(dispatcher, window=window)
    service.start()
    try:
        yield service, dispatcher
    finally:
        await service.stop()


def _sweep(service, members):
    """Announce coroutines as a sweep does: tasks first, then run."""
    tasks = [asyncio.ensure_future(m) for m in members]
    service.announce(tasks)
    return tasks


@pytest.mark.asyncio
async def test_lone_unannounced_request_is_not_held():
    async with _service(LONG) as (service, dispatcher):
        all0, out0, waits0 = (_closed("all_arrived"), _closed("timeout"),
                              QUEUE_WAIT.sum)
        TRACER.clear()
        t0 = time.monotonic()
        assert await service.solve(_hash(1), TARGET) == (7, 1)
        assert time.monotonic() - t0 < 1.0
        assert dispatcher.batches == [1]
        # the counter grows by reason, the wait histogram sees about 0
        assert _closed("all_arrived") == all0 + 1
        assert _closed("timeout") == out0
        assert QUEUE_WAIT.sum - waits0 < 0.5
        (span,) = _window_spans()
        assert span.attrs["closed"] == "all_arrived"
        assert span.attrs["objects"] == 1


@pytest.mark.asyncio
async def test_staggered_members_form_one_batch_closed_by_the_last():
    async def member(i):
        await asyncio.sleep(0.02 * i)
        return await service.solve(_hash(i), TARGET)

    async with _service(LONG) as (service, dispatcher):
        TRACER.clear()
        t0 = time.monotonic()
        results = await asyncio.gather(
            *_sweep(service, (member(i) for i in range(5))))
        assert time.monotonic() - t0 < 1.0
        assert results == [(7, 1)] * 5
        assert dispatcher.batches == [5]
        (span,) = _window_spans()
        assert span.attrs["closed"] == "all_arrived"
        assert span.attrs["objects"] == 5


@pytest.mark.asyncio
async def test_missing_member_leaves_the_batch_to_the_timer():
    late = asyncio.Event()

    async def member(i):
        if i == 2:
            await late.wait()
        return await service.solve(_hash(i), TARGET)

    async with _service(0.1) as (service, dispatcher):
        out0 = _closed("timeout")
        TRACER.clear()
        t0 = time.monotonic()
        tasks = _sweep(service, (member(i) for i in range(3)))
        await asyncio.gather(*tasks[:2])
        assert time.monotonic() - t0 >= 0.09, "closed before the timer"
        assert dispatcher.batches == [2]
        assert _closed("timeout") == out0 + 1
        # the straggler forms the next batch itself, and is not held:
        # the two that are back are outstanding only until they end
        late.set()
        t1 = time.monotonic()
        assert await tasks[2] == (7, 1)
        assert time.monotonic() - t1 < 0.09
        assert dispatcher.batches == [2, 1]
        assert [s.attrs["closed"] for s in _window_spans()] == \
            ["timeout", "all_arrived"]


@pytest.mark.parametrize("ending", ["return", "exception", "cancellation",
                                    "cancelled_before_start"])
@pytest.mark.asyncio
async def test_member_that_ends_without_solving_releases_the_window(ending):
    gate = asyncio.Event()

    async def quitter():
        if ending != "cancelled_before_start":
            await gate.wait()
        if ending == "exception":
            raise RuntimeError("badkey")
        if ending == "cancellation":
            await asyncio.sleep(60)

    async def member():
        return await service.solve(_hash(1), TARGET)

    async with _service(LONG) as (service, dispatcher):
        t0 = time.monotonic()
        solving, quitting = _sweep(service, (member(), quitter()))
        if ending == "cancelled_before_start":
            quitting.cancel()       # its coroutine never takes a step
        else:
            await asyncio.sleep(0.05)
            assert not solving.done(), "dispatched with a member missing"
        gate.set()
        if ending == "cancellation":
            await asyncio.sleep(0)
            quitting.cancel()
        assert await solving == (7, 1)
        assert time.monotonic() - t0 < 1.0
        assert dispatcher.batches == [1]
        await asyncio.gather(quitting, return_exceptions=True)
        assert not service._outstanding


@pytest.mark.asyncio
async def test_members_that_solve_twice_give_two_batches():
    async def member(i):
        await asyncio.sleep(0.01 * i)
        ack = await service.solve(_hash(i), TARGET)
        await asyncio.sleep(0.01 * (4 - i))
        return ack, await service.solve(_hash(100 + i), TARGET)

    async with _service(LONG) as (service, dispatcher):
        t0 = time.monotonic()
        results = await asyncio.gather(
            *_sweep(service, (member(i) for i in range(4))))
        assert time.monotonic() - t0 < 1.0
        assert results == [((7, 1), (7, 1))] * 4
        assert dispatcher.batches == [4, 4]
        assert service.solved == 8
        await asyncio.sleep(0)
        assert not service._outstanding


@pytest.mark.parametrize("window, closed, least, most",
                         [(LONG, "all_arrived", 0.05, 1.0),
                          (0.1, "timeout", 0.09, 1.0)])
@pytest.mark.asyncio
async def test_unannounced_request_waits_for_members_or_the_timer(
        window, closed, least, most):
    arrive = asyncio.Event()

    async def member():
        await arrive.wait()
        return await service.solve(_hash(2), TARGET)

    async with _service(window) as (service, dispatcher):
        TRACER.clear()
        (task,) = _sweep(service, [member()])
        t0 = time.monotonic()
        stranger = asyncio.ensure_future(service.solve(_hash(1), TARGET))
        await asyncio.sleep(0.06)
        if closed == "all_arrived":
            assert not stranger.done(), "did not wait for the member"
            arrive.set()
        assert await stranger == (7, 1)
        assert least <= time.monotonic() - t0 < most
        arrive.set()
        assert await task == (7, 1)
        assert dispatcher.batches == \
            ([2] if closed == "all_arrived" else [1, 1])
        assert _window_spans()[0].attrs["closed"] == closed


@pytest.mark.asyncio
async def test_zero_window_never_waits_for_a_missing_member():
    """``powbatchwindow`` 0 keeps its meaning: launch immediately."""
    async def member():
        await asyncio.sleep(60)

    async with _service(0.0) as (service, dispatcher):
        (task,) = _sweep(service, [member()])
        assert await service.solve(_hash(1), TARGET) == (7, 1)
        assert _window_spans()[-1].attrs["closed"] == "timeout"
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)


def _failing_second(solve):
    """Three sends of which the second fails before it asks for PoW."""
    async def send(i):
        if i == 1:
            raise ValueError("send failed")
        return await solve(i)
    return (send(i) for i in range(3))


@pytest.mark.asyncio
async def test_send_worker_sweep_announces_its_members():
    """``SendWorker._gather_sweep``: every send is announced before any
    runs, and withdrawn however it ends."""
    seen = []

    async def solve(i):
        seen.append(len(service._outstanding))
        return await service.solve(_hash(i), TARGET)

    worker = SendWorker.__new__(SendWorker)
    async with _service(LONG) as (service, dispatcher):
        worker.pow_service = service
        t0 = time.monotonic()
        results = await worker._gather_sweep(_failing_second(solve))
        assert time.monotonic() - t0 < 1.0
        assert results[0] == results[2] == (7, 1)
        assert isinstance(results[1], ValueError)
        # all three were announced before the first ran; the one that
        # failed did not hold the other two's batch
        assert seen[0] == 3
        assert dispatcher.batches == [2]
        await asyncio.sleep(0)
        assert not service._outstanding


@pytest.mark.asyncio
async def test_send_worker_without_a_service_announces_nothing():
    async def solve(i):
        return i

    worker = SendWorker.__new__(SendWorker)
    worker.pow_service = None
    results = await worker._gather_sweep(_failing_second(solve))
    assert results[0] == 0 and results[2] == 2
    assert isinstance(results[1], ValueError)
