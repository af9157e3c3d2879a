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

A solve laid out for announced company (ISSUE 33): ``expect`` plans a
lone first member as the queue it belongs to, lays out the groups that
queue would fill, starts the missing members' slots as pad slots and
deals arrivals evenly; the dispatcher says whether it streams, and
passes ``expect`` to the one rung that does.

A solve placed over several chips (ISSUE 37): the launch groups are
dealt over the devices the solve is given, and nothing else changes —
the same nonces and trials as on one device, every device launching,
a freed slot refilled on the chip that freed it, no wait on a chip
that has run out; the dispatcher offers a queue the pipeline on any
accelerator, and a lone object the nonce-range partition.
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


# -- a solve laid out for the company that is announced -----------------

#: a target at network difficulty (a 300-byte broadcast: 8.2e6 trials)
NETWORK = 2 ** 64 // 8_200_000


@pytest.mark.parametrize("there, expect, mode", [
    (1, 0, "slab"), (1, 1, "slab"), (1, 2, "batched"), (1, 200, "batched"),
    (3, 256, "batched"), (5, 2, "batched"), (5, 0, "batched")])
def test_the_plan_is_that_of_the_queue_announced(there, expect, mode):
    items = [(bytes([i]) * 64, NETWORK) for i in range(there)]
    plan = pipeline.plan_batch(items, expect=expect)
    assert plan.mode == mode
    # the order names what is there, never a member still to come
    assert sorted(plan.order) == list(range(there))


def test_the_pack_rule_still_reads_the_targets_that_are_there():
    (item,) = _items("tiny", 1, expected=1000)
    assert pipeline.plan_batch([item]).mode == "single-sync"
    plan = pipeline.plan_batch([item], expect=200)
    assert (plan.mode, plan.pack, plan.order) == ("packed", 16, [0])


def _real_plan(monkeypatch):
    """``plan_batch`` itself plans test objects as it plans objects at
    network difficulty: no packing, the test tile."""
    monkeypatch.setattr(pipeline, "PACK_CHOICES", ())
    monkeypatch.setattr(pipeline, "DEFAULT_BATCH_CHUNKS", CHUNKS)


@pytest.mark.parametrize("there, expect, groups, feeds", [
    (1, 200, 4, 7), (1, 40, 2, 3), (3, 256, 4, 5), (5, 64, 2, 1),
    (10, 130, 3, 4), (2, 65, 2, 2)])
def test_a_solve_laid_out_for_company_takes_it_in(there, expect, groups,
                                                  feeds, monkeypatch):
    """The solve starts with ``there`` objects, laid out for ``expect``:
    mode ``batched`` in the groups ``expect`` objects would fill, the
    rest through ``feed``; results aligned with the order of arrival
    and hashlib-valid; no launch without a live slot."""
    _real_plan(monkeypatch)
    items = _items("first %d" % expect, there, expected=3000)
    fed = _items("company %d" % expect, expect - there, expected=3000)
    per = -(-len(fed) // feeds)
    waiting = [fed[k:k + per] for k in range(0, len(fed), per)]
    calls, stats = [], {}
    label = {"kind": "batch"}
    launches0 = REGISTRY.sample("pow_pipeline_launches_total", label)
    slots0 = [REGISTRY.sample("pow_pipeline_slots_total",
                              dict(label, state=state))
              for state in ("live", "idle")]
    refills0 = REGISTRY.sample("pow_pipeline_refills_total", label)
    TRACER.clear()

    def feed(room):
        part = waiting.pop(0) if waiting else []
        out, rest = part[:room], part[room:]
        if rest:
            waiting.insert(0, rest)
        return [(ih, target, 0) for ih, target in out]

    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="xla", stats=stats, expect=expect,
        on_solved=lambda i, r: calls.append((i, r)), feed=feed)
    assert (stats["mode"], stats["groups"], stats["width"]) == \
        ("batched", groups, 64)
    assert len(results) == expect
    for (ih, target), (nonce, trials) in zip(items + fed, results):
        assert hashlib.sha512(hashlib.sha512(
            nonce.to_bytes(8, "big") + ih).digest()).digest()[:8] \
            <= target.to_bytes(8, "big")
        assert trials > 0
    assert sorted(i for i, _r in calls) == list(range(expect))
    assert _grown("pow_pipeline_refills_total", label,
                  refills0) == expect - there
    # a group with no live slot was never launched
    launches = int(_grown("pow_pipeline_launches_total", label, launches0))
    spans = TRACER.recent(launches, name="pow.launch")
    assert len(spans) == launches and all(
        s.attrs["live"] >= 1 for s in spans)
    live, idle = (REGISTRY.sample("pow_pipeline_slots_total",
                                  dict(label, state=state)) - before
                  for state, before in zip(("live", "idle"), slots0))
    assert live >= launches and live + idle == 64 * launches
    (plan_span,) = TRACER.recent(10, name="pow.plan")
    assert (plan_span.attrs["objects"], plan_span.attrs["expect"],
            plan_span.attrs["mode"]) == (there, expect, "batched")


@pytest.mark.parametrize("there, expect, full", [
    (6, 256, [64, 64, 64, 64]), (1, 130, [64, 64, 2])])
def test_a_sweep_that_arrives_before_anybody_hits_fills_whole_groups(
        there, expect, full, monkeypatch):
    """Everybody arrives before anybody hits: a group takes what has
    arrived into every free slot when its turn comes, so the storm's
    256 still search as four groups of 64."""
    everything = _items("full %d" % expect, expect, expected=10 ** 7)
    items, fed = everything[:there], everything[there:]
    # nobody hits until every group has been launched full; then all do
    hits = [set()] * (3 * len(full))
    script = Script(everything, hits, monkeypatch)
    waiting = list(fed)

    def feed(room):
        out = waiting[:room]
        del waiting[:room]
        return [(ih, t, 0) for ih, t in out]

    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="pallas", plan=_batched(there), feed=feed,
        expect=expect)
    assert len(results) == expect and all(r is not None for r in results)
    assert not waiting
    assert sorted(len(live) for live in script.live[:len(full)]) == \
        sorted(full)
    assert all(script.live), "a launch held no live object"


def test_a_solve_that_runs_dry_with_members_missing_ends(monkeypatch):
    """Nobody comes: the solve ends when what it holds is solved, the
    pad slots of the missing never launched alone."""
    _real_plan(monkeypatch)
    items = _items("dry", 2)
    asked, stats = [], {}
    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="xla", stats=stats, expect=10,
        feed=lambda room: asked.append(room) or [])
    assert len(results) == 2 and all(r is not None for r in results)
    assert stats["groups"] == 2 and asked


@pytest.mark.parametrize("there, expect", [(5, 0), (5, 3), (5, 5),
                                            (70, 70), (65, 0)])
def test_without_company_the_layout_is_what_it_was(there, expect,
                                                   monkeypatch):
    everything = _items("as ever", there, expected=20000)
    script = Script(everything, [], monkeypatch)    # all hit at once
    asked = []
    pipeline.solve_batch_pipelined(
        everything, rows=ROWS, impl="pallas", plan=_batched(there),
        feed=lambda room: asked.append(room) or [], expect=expect)
    want = ([-(-there // 2), there // 2] if there <= 64
            else [64, there - 64])
    assert [len(live) for live in script.live[:2]] == want
    # and a group is offered every free slot it has
    assert max(asked) == 64


@pytest.mark.parametrize("topology, tpu, breaker, farm, streams", [
    ((1, True), True, "closed", None, True),
    ((1, False), True, "closed", None, False),      # the CPU ladder
    ((4, True), True, "closed", None, True),        # four chips
    ((4, False), True, "closed", None, False),      # a CPU mesh
    ((0, False), True, "closed", None, False),      # the probe failed
    ((1, True), False, "closed", None, False),
    ((1, True), True, "open", None, False),
    ((1, True), True, "closed", "up", False),       # the farm leads
    ((1, True), True, "closed", "down", True)])
def test_the_dispatcher_says_whether_a_queue_would_stream(
        topology, tpu, breaker, farm, streams, monkeypatch):
    monkeypatch.setattr(PowDispatcher, "_batch_topology",
                        lambda self: topology)
    d = PowDispatcher(use_tpu=tpu, use_native=False)
    if breaker == "open":
        d.breakers["tpu-pallas"].record_failure()
    if farm is not None:
        class Farm:
            class breaker:
                available = staticmethod(lambda: farm == "up")
        d.attach_farm(Farm())
    items = [(b"\x01" * 64, NETWORK)]
    assert d.streams(items, 200) is streams
    # asking consumes nothing: the answer stands
    assert d.streams(items, 200) is streams
    # a queue of tiny objects is packed, and a packed solve holds who
    # it starts with
    assert d.streams(_items("tiny", 1, expected=1000), 200) is False


@pytest.mark.parametrize("topology, expect, rung", [
    ((1, True), 200, "pipeline"), ((1, True), 0, "ladder"),
    ((1, True), 1, "ladder"), ((1, False), 200, "ladder"),
    ((4, True), 200, "pipeline"), ((4, False), 200, "ladder")])
def test_a_lone_item_with_company_is_offered_the_streaming_rung(
        topology, expect, rung, monkeypatch):
    monkeypatch.setattr(PowDispatcher, "_batch_topology",
                        lambda self: topology)
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: False)
    monkeypatch.setattr(PowDispatcher, "_device_count", lambda self: 1)
    _real_plan(monkeypatch)
    (item,) = _items("lone %s %d" % (topology, expect), 1, expected=3000)
    seen = []

    def recorded(items, *, expect, feed, on_solved, **_kw):
        seen.append((len(items), expect, feed is not None))
        return [python_solve(ih, t) for ih, t in items]

    from pybitmessage_tpu.pow.dispatcher import python_solve
    monkeypatch.setattr(pipeline, "solve_batch_pipelined", recorded)
    d = PowDispatcher(use_native=False)
    TRACER.clear()
    calls = []
    (result,) = d.solve_batch([item], expect=expect,
                              on_solved=lambda i, r: calls.append(i),
                              feed=lambda room: [])
    assert reference.trial_value(result[0].to_bytes(8, "big"),
                                 item[0]) <= item[1]
    assert calls == [0]
    assert seen == ([(1, 200, True)] if rung == "pipeline" else [])
    assert d.last_backend == ("tpu-pallas-batch" if rung == "pipeline"
                              else "tpu")
    (span,) = TRACER.recent(5, name="pow.solve_batch")
    assert (span.attrs["objects"], span.attrs["expect"]) == \
        (1, max(expect, 1))


@pytest.mark.asyncio
async def test_a_staggered_sweep_is_one_solve_through_the_real_ladder(
        monkeypatch):
    """``PowService`` over ``PowDispatcher`` told it has one chip, the
    XLA stand-in where the kernel would be: twenty members arriving one
    by one are ONE solve that began with the first."""
    monkeypatch.setattr(PowDispatcher, "_batch_topology",
                        lambda self: (1, True))
    _real_plan(monkeypatch)
    monkeypatch.setitem(pipeline.solve_batch_pipelined.__kwdefaults__,
                        "rows", ROWS)
    n = 20
    items = _items("sweep", n, expected=12000)
    service = PowService(PowDispatcher(use_native=False), window=5.0)
    first0 = REGISTRY.sample("pow_window_closed_total",
                             {"reason": "first_arrival"})
    sum0, n0 = _histogram("pow_batch_size")

    async def member(i):
        await asyncio.sleep(0.002 * i)
        return await service.solve(*items[i])

    TRACER.clear()
    service.start()
    try:
        tasks = [asyncio.ensure_future(member(i)) for i in range(n)]
        service.announce(tasks)
        results = await asyncio.gather(*tasks)
    finally:
        await service.stop()
    for (ih, target), (nonce, _trials) in zip(items, results):
        assert reference.trial_value(nonce.to_bytes(8, "big"), ih) \
            <= target
    windows = TRACER.recent(50, name="pow.queue.window")
    assert windows[0].attrs["closed"] == "first_arrival"
    assert windows[0].attrs["objects"] < n
    assert windows[0].attrs["expect"] == n
    plans = TRACER.recent(50, name="pow.plan")
    assert (plans[0].attrs["mode"], plans[0].attrs["expect"]) == \
        ("batched", n)
    # every member was in some solve exactly once, most in the first
    total, count = _histogram("pow_batch_size")
    assert total - sum0 == n
    assert _grown("pow_window_closed_total", {"reason": "first_arrival"},
                  first0) >= 1
    assert service.solved == n


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
        # the last hit resolves its future before the solving thread
        # has booked the solve: wait for what is asserted below
        for _ in range(500):
            if dispatcher.sizes:
                break
            await asyncio.sleep(0.01)
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


@pytest.mark.parametrize("on_disk", [False, True])
def test_the_jobs_a_solve_takes_in_are_marked_by_one_statement(
        on_disk, tmp_path):
    journal = PowJournal(str(tmp_path / "pow.dat") if on_disk
                         else ":memory:")
    jobs = [journal.add(_hash(i), 1 << 60)[0] for i in range(6)]
    statements = []
    journal._conn.set_trace_callback(statements.append)
    journal.mark_inflight(*jobs[:4])
    journal._conn.set_trace_callback(None)
    assert len(statements) == 1 and statements[0].startswith("UPDATE")
    by_id = {job.job_id: job for job in journal.pending()}
    assert [by_id[j].status for j in jobs] == \
        ["inflight"] * 4 + ["queued"] * 2
    assert [by_id[j].attempts for j in jobs] == [1] * 4 + [0] * 2
    # one job alone, as the farm marks them, is the same call
    journal.mark_inflight(jobs[4])
    assert journal.get(jobs[4]).status == "inflight"
    journal.close()


@pytest.mark.asyncio
async def test_a_refill_costs_the_journal_one_write():
    """What ``feed`` hands a running solve is marked in flight by one
    journal call, on the solving thread, before the solve has it."""
    calls = []

    class Counting(PowJournal):
        def mark_inflight(self, *job_ids):
            calls.append((len(job_ids), threading.current_thread()))
            return super().mark_inflight(*job_ids)

    dispatcher, journal = StreamingDispatcher(), Counting()
    service = PowService(dispatcher, window=0.0, journal=journal)
    service.start()
    try:
        first = [asyncio.ensure_future(service.solve(_hash(i), 1 << 60))
                 for i in range(3)]
        await asyncio.gather(*first)
        late = [asyncio.ensure_future(service.solve(_hash(i), 1 << 60))
                for i in (3, 4, 5, 6)]
        await asyncio.sleep(0)
        dispatcher.late.set()
        assert await asyncio.gather(*late) == [(i, 1) for i in (3, 4, 5, 6)]
    finally:
        await service.stop()
    assert [n for n, _thread in calls] == [3, 4]
    assert calls[0][1] is threading.main_thread()
    assert calls[1][1] is not threading.main_thread()
    assert journal.pending_count() == 0


def test_the_slots_of_a_solve_are_the_senders_in_flight():
    assert SOLVE_SLOTS == sender.MAX_IN_FLIGHT \
        == 4 * sha512_pallas.BATCH_OBJS


# -- a solve placed over several chips (ISSUE 37) -----------------------


def _device_launches() -> dict:
    fam = REGISTRY.get("pow_pipeline_device_launches_total")
    return {values[0]: child.value for values, child in fam.children()}


@pytest.mark.parametrize("ndev", [1, 2, 4])
@pytest.mark.parametrize("n, m", [(40, 0), (9, 30), (130, 12)])
def test_a_placed_solve_returns_the_one_device_solves_answers(ndev, n, m):
    """Object for object the ``(nonce, trials)`` of the solve that is
    given no devices, each nonce accepted by the plain reference;
    unless a chip that had run out searched a copy of the object
    (tests/test_pow_copies.py): its misses are credited too, and where
    the copy won the nonce lies in the copy's range."""
    import jax
    items, fed = _items("placed %d" % n, n), _items("placed fed %d" % n, m)
    want, _calls, _asked = _stream(items, fed)
    before = _device_launches()
    stats = {}
    got, calls, _asked = _stream(items, fed, stats=stats,
                                 devices=jax.devices()[:ndev])
    assert stats["devices"] == ndev
    assert stats["groups"] >= pipeline.MIN_BATCH_GROUPS * ndev
    assert len(got) == n + m
    assert ndev > 1 or not stats["copies"]
    if not stats["copies"]:
        assert got == want
    for (nonce, trials), (own, alone) in zip(got, want):
        if nonce == own:
            assert trials >= alone
        else:
            assert stats["copies"] and nonce >= (1 << 64) // ndev
    assert sorted(i for i, _r in calls) == list(range(n + m))
    assert all(got[i] == r for i, r in calls)
    for (ih, target), (nonce, trials) in zip(items + fed, got):
        assert reference.trial_value(nonce.to_bytes(8, "big"), ih) \
            <= target
        assert trials > 0
    grown = {k: v - before.get(k, 0) for k, v in
             _device_launches().items() if v - before.get(k, 0)}
    assert sorted(grown) == [str(k) for k in range(ndev)]
    assert sum(grown.values()) == stats["launches"]


class Placed:
    """Stands where ``pallas_batch_search`` is, for a solve on several
    devices, where the order of the launches is not scripted: item
    ``i`` misses until its ``after[i]``-th launch and hits in that one.
    Keeps, for every launch, the device its arrays were on and the
    live items it held."""

    def __init__(self, items, after, monkeypatch):
        self.index = {
            np.array(pipeline._hash_words(ih), np.uint32).tobytes(): i
            for i, (ih, _t) in enumerate(items)}
        self.left = dict(enumerate(after))
        self.launches = []          # (device, [live items])
        self._lock = threading.Lock()
        monkeypatch.setattr(sha512_pallas, "pallas_batch_search", self)
        monkeypatch.setattr(pipeline, "_checked_nonce",
                            lambda nonce, initial_hash, target: nonce)

    def __call__(self, ih_words, bases, targets, rows, chunks, unroll,
                 interpret):
        (device,) = ih_words.devices()
        assert bases.devices() == targets.devices() == {device}
        words, targets = np.asarray(ih_words), np.asarray(targets)
        out = np.zeros((len(words), 3), np.uint32)
        live = []
        with self._lock:
            for k in range(len(words)):
                if tuple(targets[k]) == (2 ** 32 - 1,) * 2:
                    out[k] = (1, 0, 0)
                    continue
                i = self.index[words[k].tobytes()]
                live.append(i)
                self.left[i] -= 1
                if self.left[i] <= 0:
                    out[k] = (1, 0, i)
            self.launches.append((device, live))
        return out

    def devices_of(self, i) -> set:
        return {dev for dev, live in self.launches if i in live}

    def home_of(self, i):
        """The device of the first launch that held ``i``: its own
        slot's (a copy is taken later, by a chip that has run out)."""
        return next(dev for dev, live in self.launches if i in live)


def test_on_four_devices_a_freed_slot_is_refilled_on_its_own_chip(
        monkeypatch):
    """Eight objects, a group each, two groups a chip.  Object 2 hits
    in its first launch, the others in their third; the queue holds
    one more object, handed only to a group whose own object has
    solved (it asks for 64 slots, the others for 63): the newcomer
    searches on the chip object 2 searched on."""
    import jax
    devices = jax.devices()[:4]
    items = _items("chip", 8, expected=10 ** 7)
    (late,) = _items("chip late", 1, expected=10 ** 7)
    placed = Placed(items + [late], [3, 3, 1, 3, 3, 3, 3, 3, 2],
                    monkeypatch)
    calls, handed = [], []

    def feed(room):
        if room < 64 or handed:
            return []
        # on_solved has fired for the object whose slot this is,
        # before the solve has returned
        handed.append(list(calls))
        return [(late[0], late[1], 0)]

    before = _device_launches()
    stats = {}
    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="pallas", plan=_batched(8), feed=feed,
        on_solved=lambda i, r: calls.append(i), devices=devices,
        stats=stats, stall_timeout=30.0)
    assert len(results) == 9 and all(r is not None for r in results)
    assert (stats["groups"], stats["devices"]) == (8, 4)
    assert handed == [[2]]
    assert sorted(calls) == list(range(9)) and calls[0] == 2
    # the groups are dealt in turn: object j on device j % 4
    for j in range(8):
        assert placed.home_of(j) == devices[j % 4]
    assert placed.home_of(8) == devices[2]
    assert {dev for dev, _live in placed.launches} == set(devices)
    grown = {k: v - before.get(k, 0)
             for k, v in _device_launches().items()}
    assert all(grown[str(k)] >= 2 for k in range(4))
    assert sum(grown.values()) == stats["launches"] \
        == len(placed.launches)


def test_the_driver_is_told_how_many_live_slots_each_chip_has(
        monkeypatch):
    """``load`` is what the driver orders its asking by among chips
    with as many launches in flight (tests/test_pow_pipeline.py holds
    the order): six objects dealt over eight groups on four devices
    are two live slots on chips 0 and 1 and one on 2 and 3,
    and none anywhere once all have solved."""
    import jax
    items = _items("load", 6, expected=10 ** 7)
    Placed(items, [2] * 6, monkeypatch)
    seen = {}
    run = pipeline._PipelineDriver.run

    def spy(self, next_launch, harvest, done=None, load=None):
        seen["before"] = [load(k) for k in range(self.lanes)]
        run(self, next_launch, harvest, done=done, load=load)
        seen["after"] = [load(k) for k in range(self.lanes)]

    monkeypatch.setattr(pipeline._PipelineDriver, "run", spy)
    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="pallas", plan=_batched(6),
        feed=lambda room: [], devices=jax.devices()[:4])
    assert all(r is not None for r in results)
    assert seen == {"before": [2, 2, 1, 1], "after": [0, 0, 0, 0]}


def test_a_device_that_has_run_out_is_not_waited_for(monkeypatch):
    """The two objects of device 0 hit at once and nothing is queued:
    from then on it searches copies of the others' objects, one at a
    time, while theirs take five launches each (a copy's count),
    resolve, and the solve ends."""
    import jax
    devices = jax.devices()[:4]
    items = _items("early", 8, expected=10 ** 7)
    placed = Placed(items, [1 if j % 4 == 0 else 5 for j in range(8)],
                    monkeypatch)
    asked, calls, stats = [], [], {}
    TRACER.clear()
    results = pipeline.solve_batch_pipelined(
        items, rows=ROWS, impl="pallas", plan=_batched(8),
        feed=lambda room: asked.append(room) or [],
        on_solved=lambda i, r: calls.append(i), devices=devices,
        stats=stats)
    assert all(r is not None for r in results)
    assert set(calls[:2]) == {0, 4} and sorted(calls) == list(range(8))
    # what device 0 launched once its own had hit held one copy each
    mine = [live for dev, live in placed.launches if dev == devices[0]]
    assert stats["copies"] >= 1 and len(mine) > 3
    assert all(len(live) == 1 and live[0] not in (0, 4)
               for live in mine[3:])
    # the copies' launches count towards each object's five: some
    # object took fewer than five launches of its own chip
    at_home = [sum(1 for dev, live in placed.launches
                   if j in live and dev == devices[j % 4])
               for j in (1, 2, 3, 5, 6, 7)]
    assert min(at_home) < 5
    # the spans say where each launch and harvest was
    n = len(placed.launches)
    launched = TRACER.recent(n + 1, name="pow.launch")
    assert [devices[s.attrs["device"]] for s in launched] \
        == [dev for dev, _live in placed.launches]
    # the launches left unread at the end have no harvest: every chip
    # searches to the end now, two in flight each
    harvests = TRACER.recent(n + 1, name="pow.harvest")
    assert n - 2 * 4 <= len(harvests) <= n
    assert sorted({s.attrs["device"] for s in harvests}) == [0, 1, 2, 3]
    (groups,) = TRACER.recent(3, name="pow.groups")
    assert groups.attrs["devices"] == 4


@pytest.mark.parametrize("ndev, accel, queue, lone", [
    (4, True, ["tpu-pallas-batch", "tpu-batch"], "tpu-pallas"),
    (4, False, ["tpu-batch"], "tpu-sharded"),
    (1, True, ["tpu-pallas-batch"], "tpu-pallas"),
    (1, False, [], "tpu")])
def test_the_rungs_a_topology_admits(ndev, accel, queue, lone,
                                     monkeypatch):
    """A queue is offered the pipeline on an accelerator however many
    chips it has, with the devices where there are several; a lone
    object goes the same way, on the rung of one chip (PR 43), and the
    XLA partition is left for several devices that are no accelerator."""
    import jax

    import pybitmessage_tpu.parallel as par
    monkeypatch.setattr(PowDispatcher, "_device_count",
                        lambda self: ndev)
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: accel)
    d = PowDispatcher(use_native=False)
    items = [(b"\x01" * 64, NETWORK)] * 3
    rungs = list(d._batch_rungs(items, [0] * 3, None, None, None, None, 3))
    assert [rung.backend for rung, _call in rungs] == queue
    assert d.streams(items, 3) is accel
    seen = {}

    def recorded(items, *, devices=None, **_kw):
        seen["devices"] = devices
        return [(7, 1)] * len(items)

    monkeypatch.setattr(pipeline, "solve_batch_pipelined", recorded)
    if accel:
        assert rungs[0][1]() == [(7, 1)] * 3
        assert seen["devices"] == (jax.devices()[:4] if ndev > 1
                                   else None)
    # a lone object: the pipeline on an accelerator, with its devices
    monkeypatch.setattr(
        par, "sharded_solve", lambda ih, target, mesh, **kw: (11, 1))
    monkeypatch.setattr(par, "pallas_sharded_solve", None)
    from pybitmessage_tpu.ops import pow_search
    monkeypatch.setattr(pow_search, "solve",
                        lambda ih, target, **kw: (11, 1))
    (result,) = d.solve_batch([items[0]])
    assert d.last_backend == lone
    assert result == ((7, 1) if lone == "tpu-pallas" else (11, 1))
    if accel:
        assert seen["devices"] == (jax.devices()[:4] if ndev > 1
                                   else None)
