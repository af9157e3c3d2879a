"""What closes ``PowService``'s coalescing window (no device).

A sweep announces its member tasks; the window closes when nobody who
could still join is missing, with ``window`` kept as the upper bound.
A fake dispatcher records the batches it is handed.

A solver that streams, and says so, holds nothing back (ISSUE 33): the
window closes at the first arrival, the solve is told how many objects
to ``expect`` and takes the rest of the sweep in through ``feed``.
"""

import asyncio
import contextlib
import threading
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


# -- a solver that streams, and says so (ISSUE 33) ----------------------


class StreamingFake:
    """Names the stream's hooks and ``expect``, and says whether it
    streams now.  A solve resolves what it starts with, then takes in
    what ``feed`` has until it holds ``expect`` objects or has waited
    ``patience`` seconds for nobody (the real pipeline ends when every
    slot is done and ``feed`` has nothing: ``patience`` stands for the
    search the objects it holds would still be in)."""

    last_backend = "fake"

    def __init__(self, *, on: bool = True, patience: float = 2.0):
        self.on = on
        self.patience = patience
        self.asked = 0
        #: the threads on which ``feed`` brought somebody
        self.fed_on: list = []
        #: per solve: (objects it started with, expect, objects in all)
        self.solves: list[tuple] = []

    def streams(self, items, expect) -> bool:
        assert 1 <= len(items) <= expect
        self.asked += 1
        return self.on

    def solve_batch(self, items, *, should_stop=None, start_nonces=None,
                    progress=None, on_solved=None, feed=None, expect=0):
        results = []

        def resolve(new):
            for _ in new:
                results.append((7, 1))
                on_solved(len(results) - 1, results[-1])

        resolve(items)
        waited = time.monotonic()
        while len(results) < expect and \
                time.monotonic() - waited < self.patience:
            arrived = feed(expect - len(results))
            if arrived:
                self.fed_on.append(threading.current_thread())
                resolve(arrived)
                waited = time.monotonic()
            else:
                time.sleep(0.002)
        self.solves.append((len(items), expect, len(results)))
        return results


@contextlib.asynccontextmanager
async def _streaming(window: float = LONG, **kwargs):
    dispatcher = StreamingFake(**kwargs)
    service = PowService(dispatcher, window=window)
    service.start()
    try:
        yield service, dispatcher
    finally:
        await service.stop()


async def _ended(dispatcher, n: int) -> list:
    """The solves so far, once ``n`` have returned: a future resolves
    from its harvest, before its solve has ended."""
    while len(dispatcher.solves) < n:
        await asyncio.sleep(0.005)
    return dispatcher.solves


def _batch_sizes():
    (_values, child), = REGISTRY.get("pow_batch_size").children()
    total, count = child.snapshot()[1:]
    return total, count


@pytest.mark.parametrize("n", [2, 5, 40])
@pytest.mark.asyncio
async def test_a_streaming_solver_begins_the_sweep_at_its_first_member(n):
    answered = asyncio.Event()

    async def member(i):
        if i:
            # the rest arrive staggered, and only once the first has
            # its answer: it was not held for any of them
            await answered.wait()
            await asyncio.sleep(0.005 * i)
        result = await service.solve(_hash(i), TARGET)
        answered.set()
        return result

    async with _streaming() as (service, dispatcher):
        first0, all0 = _closed("first_arrival"), _closed("all_arrived")
        total0, count0 = _batch_sizes()
        TRACER.clear()
        t0 = time.monotonic()
        results = await asyncio.gather(
            *_sweep(service, (member(i) for i in range(n))))
        assert time.monotonic() - t0 < 2.0
        assert results == [(7, 1)] * n
        # ONE solve, begun with the first member and laid out for all
        assert await _ended(dispatcher, 1) == [(1, n, n)]
        (span,) = _window_spans()
        assert span.attrs["closed"] == "first_arrival"
        assert span.attrs["objects"] == 1
        assert span.attrs["expect"] == n
        assert _closed("first_arrival") == first0 + 1
        assert _closed("all_arrived") == all0
        # the service counts a batch at its solve's own end, a turn of
        # the loop or two after the solve's thread has returned
        for _ in range(200):
            total, count = _batch_sizes()
            if count > count0:
                break
            await asyncio.sleep(0.01)
        assert (count - count0, total - total0) == (1, n)
        assert service.solved == n          # each future once
        await asyncio.sleep(0)
        assert not service._outstanding


@pytest.mark.parametrize("ending", ["return", "exception"])
@pytest.mark.asyncio
async def test_a_member_that_never_asks_leaves_a_pad_slot(ending):
    """The solve was laid out for four and ends with three: the
    quitter's slot stayed a pad slot, and nobody waited for the timer."""
    answered = asyncio.Event()

    async def member(i):
        if i:
            await answered.wait()       # the solve is running by then
        if i == 2:
            if ending == "exception":
                raise RuntimeError("badkey")
            return None
        result = await service.solve(_hash(i), TARGET)
        answered.set()
        return result

    async with _streaming(patience=0.2) as (service, dispatcher):
        t0 = time.monotonic()
        results = await asyncio.gather(
            *_sweep(service, (member(i) for i in range(4))),
            return_exceptions=True)
        assert time.monotonic() - t0 < 1.0
        assert [r for i, r in enumerate(results) if i != 2] == [(7, 1)] * 3
        assert await _ended(dispatcher, 1) == [(1, 4, 3)]
        assert service.solved == 3
        assert not service._outstanding


@pytest.mark.asyncio
async def test_a_solve_that_runs_dry_is_followed_by_a_second():
    """Two members come long after the solve has run out of objects:
    it ends as any solve does, and the next arrival opens the next
    window at once.  Nothing is lost, held for the timer or resolved
    twice."""
    late, fourth = asyncio.Event(), asyncio.Event()

    async def member(i):
        if i >= 3:
            # one after the other, long after the first three
            await (late if i == 3 else fourth).wait()
        result = await service.solve(_hash(i), TARGET)
        if i == 3:
            fourth.set()
        return result

    async with _streaming(patience=0.05) as (service, dispatcher):
        TRACER.clear()
        tasks = _sweep(service, (member(i) for i in range(5)))
        assert await asyncio.gather(*tasks[:3]) == [(7, 1)] * 3
        assert await _ended(dispatcher, 1) == [(3, 5, 3)]
        dispatcher.patience = 2.0       # the second searches on
        late.set()
        t1 = time.monotonic()
        assert await asyncio.gather(*tasks[3:]) == [(7, 1)] * 2
        assert time.monotonic() - t1 < 1.0
        # the three that are back have ended: the second solve was laid
        # out for the two that were missing
        assert (await _ended(dispatcher, 2))[1:] == [(1, 2, 2)]
        assert [(s.attrs["closed"], s.attrs["objects"], s.attrs["expect"])
                for s in _window_spans()] == \
            [("first_arrival", 3, 5), ("first_arrival", 1, 2)]
        assert service.solved == 5


@pytest.mark.asyncio
async def test_a_sweep_longer_than_a_solve_is_laid_out_for_its_slots():
    from pybitmessage_tpu.pow.service import SOLVE_SLOTS
    n = SOLVE_SLOTS + 44
    gate = asyncio.Event()

    async def member(i):
        if i:
            await gate.wait()
        return await service.solve(_hash(i), TARGET)

    async with _streaming() as (service, dispatcher):
        tasks = _sweep(service, (member(i) for i in range(n)))
        assert await tasks[0] == (7, 1)
        gate.set()
        assert await asyncio.gather(*tasks) == [(7, 1)] * n
        while service.batches < 2:          # the last solve's own end
            await asyncio.sleep(0.01)
        assert dispatcher.solves[0] == (1, SOLVE_SLOTS, SOLVE_SLOTS)
        assert sum(total for _s, _e, total in dispatcher.solves) == n
        assert service.solved == n


@pytest.mark.parametrize("window, closed, batches",
                         [(LONG, "all_arrived", [(5, 5, 5)]),
                          (0.05, "timeout", [(1, 1, 1), (4, 4, 4)])])
@pytest.mark.asyncio
async def test_a_solver_that_does_not_stream_now_keeps_the_old_rule(
        window, closed, batches):
    """``streams()`` is asked at every window with a member missing; a
    no (the CPU ladder, a pod, an open breaker) is PR 28's rule."""
    async def member(i):
        await asyncio.sleep(0.3 if i else 0.0)
        return await service.solve(_hash(i), TARGET)

    async with _streaming(window, on=False) as (service, dispatcher):
        first0 = _closed("first_arrival")
        TRACER.clear()
        results = await asyncio.gather(
            *_sweep(service, (member(i) for i in range(5))))
        assert results == [(7, 1)] * 5
        assert await _ended(dispatcher, len(batches)) == batches
        assert dispatcher.asked >= 1
        span = _window_spans()[0]
        assert span.attrs["closed"] == closed
        assert span.attrs["expect"] == span.attrs["objects"]
        assert _closed("first_arrival") == first0


@pytest.mark.asyncio
async def test_a_lone_request_never_closes_first_arrival():
    """Nobody is outstanding when a lone send asks (``single_send``):
    the solver is not even asked, and the solve is laid out for one."""
    async def member():
        ack = await service.solve(_hash(1), TARGET)
        return ack, await service.solve(_hash(2), TARGET)

    async with _streaming() as (service, dispatcher):
        first0 = _closed("first_arrival")
        (task,) = _sweep(service, [member()])
        assert await task == ((7, 1), (7, 1))
        assert await service.solve(_hash(3), TARGET) == (7, 1)
        assert await _ended(dispatcher, 3) == [(1, 1, 1)] * 3
        assert dispatcher.asked == 0
        assert _closed("first_arrival") == first0


@pytest.mark.asyncio
async def test_a_late_member_is_fed_on_the_solving_thread():
    """What arrives after the window has closed reaches the solve
    through ``feed``, from the solving thread, and is journaled in
    flight as the first batch was."""
    from pybitmessage_tpu.resilience.journal import PowJournal
    answered = asyncio.Event()

    async def member(i):
        if i:
            await answered.wait()       # the solve is running by then
        result = await service.solve(_hash(i), TARGET)
        answered.set()
        return result

    dispatcher, journal = StreamingFake(), PowJournal()
    service = PowService(dispatcher, window=LONG, journal=journal)
    service.start()
    try:
        results = await asyncio.gather(
            *_sweep(service, (member(i) for i in range(3))))
        assert await _ended(dispatcher, 1) == [(1, 3, 3)]
    finally:
        await service.stop()
    assert results == [(7, 1)] * 3
    assert dispatcher.fed_on
    assert threading.main_thread() not in dispatcher.fed_on
    assert journal.pending_count() == 0
