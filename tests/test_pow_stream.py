"""The solve as a stream (ISSUE 32; docs/pow_pipeline.md), on the CPU.

Objects leave a solve when their own nonce has been found and
re-checked (``on_solved``), and a ``batched`` solve takes queued
requests into its done slots before a group's next launch (``feed``).
Held here, at test difficulty through the XLA stand-in or with
scripted kernels: every object of a streamed solve resolves exactly
once with a nonce the plain reference accepts; ``on_solved`` fires in
hit order and before the solve returns; a queue of at most one launch
is laid out as two groups and nothing is dispatched ahead; a solve
that starts alone asks nobody; a rung that fails hands on only what
is unresolved, in the dispatcher and in ``PowService``; an interrupt
leaves the rest journaled; the ladder's counters move inside one
solve; the new series and attributes move.
"""

import asyncio
import hashlib
import pathlib
import sys
import threading
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import reference  # noqa: E402
from pybitmessage_tpu.observability import REGISTRY, TRACER  # noqa: E402
from pybitmessage_tpu.ops import sha512_pallas  # noqa: E402
from pybitmessage_tpu.ops.pow_search import PowInterrupted  # noqa: E402
from pybitmessage_tpu.pow import PowService, pipeline  # noqa: E402
from pybitmessage_tpu.pow.dispatcher import PowDispatcher  # noqa: E402
from pybitmessage_tpu.pow.service import SOLVE_SLOTS  # noqa: E402
from pybitmessage_tpu.resilience.journal import PowJournal  # noqa: E402
from pybitmessage_tpu.workers import sender  # noqa: E402

#: a tile of 8 rows and 4 chunks: 4,096 trials an object a launch
ROWS, CHUNKS = 8, 4
#: expected trials of a test object: one to three launches
EASY = 6000


def _items(tag: str, n: int, expected: int = EASY):
    return [(hashlib.sha512(b"%s %d" % (tag.encode(), i)).digest(),
             2 ** 64 // expected) for i in range(n)]


def _batched(n: int) -> pipeline.BatchPlan:
    """The plan of a queue at network difficulty, for objects easy
    enough to solve here (which ``plan_batch`` would pack)."""
    return pipeline.BatchPlan("batched", 1, CHUNKS, list(range(n)))


def _stream(items, fed=(), *, feeds=1, **kwargs):
    """Solve ``items`` through the XLA stand-in with ``fed`` handed
    over in ``feeds`` parts as the solve asks; returns the results, the
    ``on_solved`` calls and how often ``feed`` was asked."""
    per = -(-len(fed) // feeds) or 1
    waiting = [list(fed[k:k + per]) for k in range(0, len(fed), per)]
    calls, asked = [], []

    def feed(room):
        asked.append(room)
        part = waiting.pop(0) if waiting else []
        out, rest = part[:room], part[room:]
        if rest:
            waiting.insert(0, rest)
        return [(ih, target, 0) for ih, target in out]

    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="xla", plan=_batched(len(items)),
        on_solved=lambda i, r: calls.append((i, r)), feed=feed, **kwargs)
    return results, calls, asked


def _grown(name: str, labels: dict, before: float) -> float:
    return REGISTRY.sample(name, labels) - before


# -- every object once, with a nonce the reference accepts --------------


@pytest.mark.parametrize("n, m, feeds", [
    (5, 3, 1), (2, 20, 3), (12, 12, 4), (70, 10, 2), (3, 0, 1)])
def test_a_streamed_solve_resolves_each_object_exactly_once(n, m, feeds):
    items, fed = _items("first", n), _items("fed", m)
    refills0 = REGISTRY.sample("pow_pipeline_refills_total",
                               {"kind": "batch"})
    results, calls, asked = _stream(items, fed, feeds=feeds)
    everything = items + fed
    assert len(results) == n + m
    # the fed objects follow the first in the order they came
    for (ih, target), (nonce, trials) in zip(everything, results):
        assert reference.trial_value(nonce.to_bytes(8, "big"), ih) \
            <= target
        assert trials > 0 and trials % (ROWS * 128) == 0
    assert sorted(i for i, _r in calls) == list(range(n + m))
    assert all(results[i] == r for i, r in calls)
    assert asked, "a batched solve asks before a group's launch"
    assert _grown("pow_pipeline_refills_total", {"kind": "batch"},
                  refills0) == m


# -- scripted kernels: who hits in which launch -------------------------


class Script:
    """Stands where ``pallas_batch_search`` is: launch ``k`` reports a
    hit at grid step 1 for the items ``hits[k]`` names, and a miss for
    every other live slot.  Slots are told apart by their hash words,
    so the layout is the pipeline's own."""

    def __init__(self, items, hits, monkeypatch):
        self.index = {
            np.array(pipeline._hash_words(ih), np.uint32).tobytes(): i
            for i, (ih, _t) in enumerate(items)}
        self.hits = [set(h) for h in hits]
        self.live = []              # per launch: the live items it held
        monkeypatch.setattr(sha512_pallas, "pallas_batch_search", self)
        monkeypatch.setattr(pipeline, "_checked_nonce",
                            lambda nonce, initial_hash, target: nonce)

    def __call__(self, ih_words, bases, targets, rows, chunks, unroll,
                 interpret):
        words, targets = np.asarray(ih_words), np.asarray(targets)
        hits = self.hits[len(self.live)] if len(self.live) < len(
            self.hits) else None
        out = np.zeros((len(words), 3), np.uint32)
        live = []
        for k in range(len(words)):
            if tuple(targets[k]) == (2 ** 32 - 1,) * 2:
                out[k] = (1, 0, 0)          # pad or solved: always hits
                continue
            i = self.index[words[k].tobytes()]
            live.append(i)
            if hits is None or i in hits:
                out[k] = (1, 0, i)
        self.live.append(live)
        return out


def _scripted(items, script, **kwargs):
    calls = []
    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="pallas", plan=_batched(len(items)),
        on_solved=lambda i, r: calls.append(i), **kwargs)
    return results, calls, script


def test_on_solved_fires_in_hit_order_before_the_solve_returns(
        monkeypatch):
    items = _items("order", 6)
    # two groups of three: (0, 1, 2) and (3, 4, 5), launched in turn
    script = Script(items, [{2}, {4}, {0}, {5, 3}, {1}], monkeypatch)
    returned = []
    results, calls, _ = _scripted(items, script)
    returned.append(list(calls))
    assert calls == [2, 4, 0, 3, 5, 1]
    assert returned[0] == calls and all(r is not None for r in results)
    # the nonce the harvest checked is the one handed on
    assert [results[i][0] for i in calls] == calls


@pytest.mark.parametrize("n", [2, 7, 33, 64])
def test_a_queue_of_one_launch_is_two_groups_and_nothing_goes_ahead(
        n, monkeypatch):
    # a launch covers 16,384 trials an object here: as hard, against
    # it, as an ack against the real launch
    items = _items("two", n, expected=20000)
    # each launch finishes the first half of what it holds: the groups
    # drain side by side, as real ones do
    half = -(-n // 2)
    shares = [list(range(half)), list(range(half, n))]
    hits, left = [], [list(s) for s in shares]
    while any(left):
        for g in (0, 1):
            if left[g]:
                done = left[g][:-(-len(left[g]) // 2)]
                hits.append(set(done))
                left[g] = left[g][len(done):]
    launched0 = REGISTRY.sample("pow_pipeline_speculation_total",
                                {"kind": "batch", "decision": "launched"})
    abandoned0 = REGISTRY.sample("pow_pipeline_abandoned_launches_total",
                                 {"kind": "batch"})
    stats = {}
    script = Script(items, hits, monkeypatch)
    results, calls, _ = _scripted(items, script, stats=stats)
    assert stats["mode"] == "batched" and stats["width"] == 64
    # the first two launches hold the two halves, each in the kernel's
    # one 64-wide shape
    assert [sorted(live) for live in script.live[:2]] == shares
    assert all(len(live) <= 32 for live in script.live)
    assert sorted(calls) == list(range(n))
    assert _grown("pow_pipeline_speculation_total",
                  {"kind": "batch", "decision": "launched"},
                  launched0) == 0
    assert _grown("pow_pipeline_abandoned_launches_total",
                  {"kind": "batch"}, abandoned0) == 0


def test_more_than_one_launch_keeps_whole_groups(monkeypatch):
    items = _items("whole", 65, expected=20000)
    script = Script(items, [], monkeypatch)     # everything hits at once
    _scripted(items, script)
    assert [len(live) for live in script.live] == [64, 1]


def test_a_refill_waits_for_the_group_to_be_read(monkeypatch):
    """A slot is refilled only between launches that have all been
    read: the launch in flight still answers for its last object."""
    items = _items("gen", 64, expected=10 ** 7)
    fed = _items("late", 4, expected=10 ** 7)
    everything = items + fed
    # group 0 finishes at once, group 1 never by itself: with 32 live
    # its next launch goes ahead of the unread one, and the newcomers
    # go to group 0, whose launches have all been read
    script = Script(everything, [set(range(32)), set(), set(), set()],
                    monkeypatch)
    waiting = [list(fed)]
    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="pallas", plan=_batched(64),
        feed=lambda room: [(ih, t, 0) for ih, t in
                           (waiting.pop() if waiting else [])][:room])
    assert len(results) == 68 and all(r is not None for r in results)
    # the newcomers were launched together, in a group of their own
    assert [64, 65, 66, 67] in [sorted(live) for live in script.live]


# -- a solve that starts alone ------------------------------------------


def test_a_solve_that_starts_alone_plans_slab_and_asks_nobody():
    (item,) = _items("alone", 1, expected=20000)
    asked, calls, stats = [], [], {}
    results = pipeline.solve_batch_pipelined(
        [item], rows=ROWS, impl="xla", stats=stats,
        on_solved=lambda i, r: calls.append((i, r)),
        feed=lambda room: asked.append(room) or [])
    assert stats["mode"] == "slab"
    assert asked == []
    assert calls == [(0, results[0])]
    assert reference.trial_value(results[0][0].to_bytes(8, "big"),
                                 item[0]) <= item[1]


def test_the_dispatcher_hands_a_lone_object_no_feed(monkeypatch):
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: False)
    monkeypatch.setattr(PowDispatcher, "_device_count", lambda self: 1)
    (item,) = _items("lone ladder", 1, expected=3000)
    asked, calls = [], []
    d = PowDispatcher(use_native=False)
    results = d.solve_batch([item], on_solved=lambda i, r: calls.append(i),
                            feed=lambda room: asked.append(room) or [])
    assert calls == [0] and asked == [] and len(results) == 1


# -- a rung that fails part of the way ----------------------------------


@pytest.mark.parametrize("k", [0, 3, 7])
def test_a_failed_rung_hands_on_only_what_is_unresolved(k, monkeypatch):
    """The pipeline rung resolves ``k`` of eight and raises: the rungs
    below solve the other ``8 - k``, and ``on_solved`` has each once."""
    monkeypatch.setattr(PowDispatcher, "_batch_topology",
                        lambda self: (1, True))
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: False)
    monkeypatch.setattr(PowDispatcher, "_device_count", lambda self: 1)
    items = _items("rung %d" % k, 8, expected=2000)

    def broken(batch, *, on_solved, **_kw):
        for i in range(k):
            on_solved(i, (1000 + i, 4096))
        raise RuntimeError("the device fell over")

    monkeypatch.setattr(pipeline, "solve_batch_pipelined", broken)
    d = PowDispatcher(use_native=False)
    trials0 = REGISTRY.sample("pow_trials_total",
                              {"backend": "tpu-pallas-batch"})
    calls = []
    results = d.solve_batch(items, on_solved=lambda i, r: calls.append(i))
    assert sorted(calls) == list(range(8))
    assert calls[:k] == list(range(k))
    assert [r[0] for r in results[:k]] == [1000 + i for i in range(k)]
    for (ih, target), (nonce, _t) in list(zip(items, results))[k:]:
        assert reference.trial_value(nonce.to_bytes(8, "big"), ih) \
            <= target
    # what the failed rung resolved is credited to it, once
    assert _grown("pow_trials_total", {"backend": "tpu-pallas-batch"},
                  trials0) == k * 4096
    assert d.breakers["tpu-pallas"].state == "open"


class PartialDispatcher:
    """Resolves the first ``k`` items of its first call through
    ``on_solved`` and then fails as ``error``; later calls solve all."""

    last_backend = "fake"

    def __init__(self, k: int, error: Exception):
        self.k, self.error = k, error
        self.calls: list[list] = []

    def solve_batch(self, items, *, should_stop=None, start_nonces=None,
                    progress=None, on_solved=None, feed=None):
        self.calls.append([ih[0] for ih, _t in items])
        results = [(ih[0], 1) for ih, _t in items]
        if len(self.calls) == 1:
            for i in range(self.k):
                on_solved(i, results[i])
            raise self.error
        return results


def _hash(i: int) -> bytes:
    return bytes([i]) * 64


@pytest.mark.asyncio
@pytest.mark.parametrize("k", [0, 2, 5])
async def test_the_service_requeues_only_what_is_unresolved(k):
    from pybitmessage_tpu.resilience import RetryPolicy
    dispatcher = PartialDispatcher(k, RuntimeError("rung failed"))
    journal = PowJournal()
    service = PowService(dispatcher, window=0.0, journal=journal,
                         retry=RetryPolicy(attempts=3, base_delay=0.01,
                                           max_delay=0.01, jitter=0.0))
    requeued0 = REGISTRY.sample("pow_requeue_total", {"reason": "failure"})
    solved0 = service.solved
    service.start()
    try:
        tasks = [asyncio.ensure_future(service.solve(_hash(i), 1 << 60))
                 for i in range(6)]
        service.announce(tasks)
        results = await asyncio.gather(*tasks)
    finally:
        await service.stop()
    assert results == [(i, 1) for i in range(6)]
    assert dispatcher.calls == [list(range(6)), list(range(k, 6))]
    assert _grown("pow_requeue_total", {"reason": "failure"},
                  requeued0) == 6 - k
    assert service.solved - solved0 == 6        # none twice
    assert journal.pending_count() == 0


@pytest.mark.asyncio
async def test_an_interrupt_resolves_what_was_found_and_journals_the_rest():
    dispatcher = PartialDispatcher(2, PowInterrupted("shutting down"))
    journal = PowJournal()
    service = PowService(dispatcher, window=0.0, journal=journal)
    service.start()
    try:
        tasks = [asyncio.ensure_future(service.solve(_hash(i), 1 << 60))
                 for i in range(5)]
        service.announce(tasks)
        results = await asyncio.gather(*tasks, return_exceptions=True)
    finally:
        await service.stop()
    assert results[:2] == [(0, 1), (1, 1)]
    assert all(isinstance(r, asyncio.CancelledError) for r in results[2:])
    assert len(dispatcher.calls) == 1           # nothing was retried
    # complete rows are gone; the rest wait, queued, for the next process
    left = journal.pending()
    assert sorted(job.initial_hash[0] for job in left) == [2, 3, 4]
    assert {job.status for job in left} == {"queued"}


def test_should_stop_mid_stream_returns_what_was_in_flight(monkeypatch):
    items = _items("stop", 6, expected=10 ** 7)
    # the first launch of each group finds one; then the node stops
    script = Script(items, [{0}, {3}, set(), set()], monkeypatch)
    calls = []
    stop = threading.Event()

    def on_solved(i, result):
        calls.append(i)
        stop.set()

    with pytest.raises(PowInterrupted):
        pipeline.solve_batch_pipelined(
            items, rows=ROWS, impl="pallas", plan=_batched(6),
            on_solved=on_solved, should_stop=stop.is_set)
    # both launches in flight at the stop were read before it was obeyed
    assert calls == [0, 3]


# -- the ladder's counters inside one solve -----------------------------


def test_attempts_and_trials_grow_between_two_refills_of_one_solve(
        monkeypatch):
    monkeypatch.setattr(PowDispatcher, "_batch_topology",
                        lambda self: (1, True))
    monkeypatch.setattr(pipeline, "PACK_CHOICES", ())
    monkeypatch.setattr(pipeline, "DEFAULT_BATCH_CHUNKS", CHUNKS)
    monkeypatch.setitem(pipeline.solve_batch_pipelined.__kwdefaults__,
                        "rows", ROWS)
    items = _items("ladder", 6)
    parts = [_items("refill a", 3), _items("refill b", 3)]
    label = {"backend": "tpu-pallas-batch"}
    seen = []

    def feed(room):
        if not parts or not any(seen_solved):
            return []
        seen.append((REGISTRY.sample("pow_attempts_total", label),
                     REGISTRY.sample("pow_trials_total", label)))
        return [(ih, t, 0) for ih, t in parts.pop(0)][:room]

    seen_solved = []
    d = PowDispatcher(use_native=False)
    attempts0 = REGISTRY.sample("pow_attempts_total", label)
    results = d.solve_batch(items, feed=feed,
                            on_solved=lambda i, r: seen_solved.append(i))
    assert len(results) == 12 and sorted(seen_solved) == list(range(12))
    (a1, t1), (a2, t2) = seen
    # the second refill saw the first counted as an attempt, and the
    # objects resolved in between credited
    assert a2 == a1 + 1 and t2 > t1
    # the solve's start and its two refills
    assert _grown("pow_attempts_total", label, attempts0) == 3
    assert d.last_backend == "tpu-pallas-batch"


# -- the new series and attributes --------------------------------------


def test_the_slot_counters_and_the_launch_attribute_move():
    live0 = REGISTRY.sample("pow_pipeline_slots_total",
                            {"kind": "batch", "state": "live"})
    idle0 = REGISTRY.sample("pow_pipeline_slots_total",
                            {"kind": "batch", "state": "idle"})
    launches0 = REGISTRY.sample("pow_pipeline_launches_total",
                                {"kind": "batch"})
    _stream(_items("slots", 4), _items("slots fed", 5))
    launches = _grown("pow_pipeline_launches_total", {"kind": "batch"},
                      launches0)
    live = _grown("pow_pipeline_slots_total",
                  {"kind": "batch", "state": "live"}, live0)
    idle = _grown("pow_pipeline_slots_total",
                  {"kind": "batch", "state": "idle"}, idle0)
    assert live > 0 and live + idle == 64 * launches
    spans = TRACER.recent(int(launches), name="pow.launch")
    assert sum(s.attrs["refilled"] for s in spans) == 5
    assert all(s.attrs["live"] <= 64 for s in spans)


@pytest.mark.parametrize("n, m", [(4, 5), (40, 0), (70, 12)])
def test_needed_trials_are_credited_a_harvest_and_sum_to_the_nonces(n, m):
    needed0 = REGISTRY.sample("pow_pipeline_needed_trials_total",
                              {"kind": "batch"})
    executed0 = REGISTRY.sample("pow_pipeline_executed_trials_total",
                                {"kind": "batch"})
    results, _calls, _asked = _stream(_items("needed", n),
                                      _items("needed fed", m))
    needed = _grown("pow_pipeline_needed_trials_total", {"kind": "batch"},
                    needed0)
    # every search began at nonce 0: it needed its nonce and the trials
    # below it, whichever launches they fell in
    assert needed == sum(nonce + 1 for nonce, _trials in results)
    assert needed < _grown("pow_pipeline_executed_trials_total",
                           {"kind": "batch"}, executed0)


class StreamingDispatcher:
    """Resolves what it is given, then waits for ``late`` and resolves
    what ``feed`` has for it: one solve that takes in."""

    last_backend = "fake"

    def __init__(self):
        self.late = threading.Event()
        self.sizes: list[int] = []

    def solve_batch(self, items, *, should_stop=None, start_nonces=None,
                    progress=None, on_solved=None, feed=None):
        results = [(ih[0], 1) for ih, _t in items]
        for i, r in enumerate(results):
            on_solved(i, r)
        assert self.late.wait(5)
        for ih, _t, _start in feed(8):
            results.append((ih[0], 1))
            on_solved(len(results) - 1, results[-1])
        self.sizes.append(len(results))
        return results


def _histogram(name: str):
    (_values, child), = REGISTRY.get(name).children()
    return child.snapshot()[1:]         # sum, count


@pytest.mark.asyncio
async def test_the_resolve_lag_is_observed_once_an_object():
    lag0 = _histogram("pow_resolve_lag_seconds")[1]
    sum0, n0 = _histogram("pow_batch_size")
    dispatcher = StreamingDispatcher()
    service = PowService(dispatcher, window=0.0)
    service.start()
    try:
        first = [asyncio.ensure_future(service.solve(_hash(i), 1 << 60))
                 for i in range(3)]
        assert await asyncio.gather(*first) == [(i, 1) for i in range(3)]
        # the solve is still running: these two arrive while it is
        late = [asyncio.ensure_future(service.solve(_hash(i), 1 << 60))
                for i in (3, 4)]
        await asyncio.sleep(0)
        dispatcher.late.set()
        assert await asyncio.gather(*late) == [(3, 1), (4, 1)]
    finally:
        await service.stop()
    assert dispatcher.sizes == [5]
    assert _histogram("pow_resolve_lag_seconds")[1] - lag0 == 5
    # one solve took in five objects all told
    total, n = _histogram("pow_batch_size")
    assert (n - n0, total - sum0) == (1, 5)


@pytest.mark.asyncio
async def test_the_hits_of_one_harvest_wake_the_loop_once():
    """Thirty objects resolved from the solving thread while the loop
    is busy: one ``call_soon_threadsafe`` carries them all, and each
    future still resolves with its own result."""
    started, go = threading.Event(), threading.Event()

    class Burst:
        last_backend = "fake"

        def solve_batch(self, items, *, should_stop=None,
                        start_nonces=None, progress=None, on_solved=None,
                        feed=None):
            results = [(ih[0], 1) for ih, _t in items]
            started.set()
            assert go.wait(5)
            for i, r in enumerate(results):
                on_solved(i, r)
            return results

    service = PowService(Burst(), window=0.0)
    loop = asyncio.get_running_loop()
    wakes = []
    real = loop.call_soon_threadsafe

    def counted(callback, *args, **kwargs):
        wakes.append(getattr(callback, "__name__", ""))
        return real(callback, *args, **kwargs)

    loop.call_soon_threadsafe = counted
    service.start()
    try:
        futures = [asyncio.ensure_future(service.solve(_hash(i), 1 << 60))
                   for i in range(30)]
        while not started.is_set():
            await asyncio.sleep(0.001)
        go.set()
        time.sleep(0.2)         # the loop is busy while all thirty hit
        assert await asyncio.gather(*futures) == [
            (i, 1) for i in range(30)]
    finally:
        loop.call_soon_threadsafe = real
        await service.stop()
    assert wakes.count("resolve_found") == 1


def test_the_slots_of_a_solve_are_the_senders_in_flight():
    assert SOLVE_SLOTS == sender.MAX_IN_FLIGHT \
        == 4 * sha512_pallas.BATCH_OBJS
