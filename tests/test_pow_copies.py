"""Nonce-range copies for a chip that has run out (ISSUE 42;
``pow/pipeline.py`` ``solve_batch_pipelined``: ``take_copy``, the
harvest's first-hit-wins, ``_copy_base``).

A lane of a ``batched`` solve over several devices that has no live
slot and gets nothing from ``feed`` takes ONE object of the lane with
the most unresolved objects into a done slot of its own, at a base no
other holder searches.  The first slot to hit resolves the object; the
others become pad slots.  Held here, on the suite's virtual devices:
when a copy is taken and when never, that a resolution is one
whichever slot finds it, that a checkpoint comes from an object's own
range only, what is credited and counted, and that the lane states
still add up.
"""

import collections
import hashlib
import threading
import time

import numpy as np
import pytest

from pybitmessage_tpu.observability import REGISTRY, TRACER
from pybitmessage_tpu.ops import sha512_pallas
from pybitmessage_tpu.pow import pipeline
from pybitmessage_tpu.pow.pipeline import (LANE_STATES, _PipelineDriver,
                                           solve_batch_pipelined)
from pybitmessage_tpu.utils.hashes import double_sha512

ROWS, CHUNKS = 8, 4
#: trials of one step and of one launch of a slot at that geometry
STEP = ROWS * sha512_pallas.LANE_COLS * sha512_pallas.BATCH_UNROLL
SLAB = STEP * CHUNKS
NEVER = 10 ** 9


def _items(tag: str, expected):
    """One object for each of ``expected`` (mean trials)."""
    return [(hashlib.sha512(b"%s %d" % (tag.encode(), i)).digest(),
             2 ** 64 // e) for i, e in enumerate(expected)]


def _plan(n: int) -> pipeline.BatchPlan:
    return pipeline.BatchPlan("batched", 1, CHUNKS, list(range(n)))


def _share(base: int, lanes: int) -> int:
    """Which lane's share of the nonce space ``base`` lies in, counted
    from the object's own (0)."""
    return base // ((1 << 64) // lanes)


def _grown(name: str, before: dict) -> dict:
    now = {values: child.value
           for values, child in REGISTRY.get(name).children()}
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def _family(name: str) -> dict:
    return _grown(name, {})


class Kernel:
    """Stands where ``pallas_batch_search`` is.  ``hits(i, share, nth)``
    says whether the slot that searches item ``i`` in the ``share``-th
    share of the nonce space (0: the object's own range) hits in its
    ``nth`` launch, counted from 1 when the launch is dispatched; a hit
    is at the launch's first step, one past its base.  Keeps, for every
    launch, its device and the live slots' ``(item, base)``; a launch
    takes ``pace`` seconds to dispatch."""

    def __init__(self, items, hits, monkeypatch, lanes=4, pace=0.0):
        self.index = {
            np.array(pipeline._hash_words(ih), np.uint32).tobytes(): i
            for i, (ih, _t) in enumerate(items)}
        self.hits, self.lanes, self.pace = hits, lanes, pace
        self.launches = []
        self.count = collections.Counter()      # (item, share) -> launches
        self.reported = collections.Counter()   # item -> hits reported
        self._lock = threading.Lock()
        monkeypatch.setattr(sha512_pallas, "pallas_batch_search", self)
        monkeypatch.setattr(pipeline, "_checked_nonce",
                            lambda nonce, initial_hash, target: nonce)

    def __call__(self, ih_words, bases, targets, rows, chunks, unroll,
                 interpret):
        (device,) = ih_words.devices()
        words, targets = np.asarray(ih_words), np.asarray(targets)
        bases = np.asarray(bases)
        out = np.zeros((len(words), 3), np.uint32)
        live = []
        if self.pace:
            time.sleep(self.pace)
        with self._lock:
            for k in range(len(words)):
                if tuple(targets[k]) == (2 ** 32 - 1,) * 2:
                    out[k] = (1, 0, 0)      # pad or retired: always hits
                    continue
                i = self.index[words[k].tobytes()]
                base = (int(bases[k, 0]) << 32) | int(bases[k, 1])
                share = _share(base, self.lanes)
                live.append((i, base))
                self.count[i, share] += 1
                if self.hits(i, share, self.count[i, share]):
                    nonce = base + 1
                    out[k] = (1, nonce >> 32, nonce & 0xFFFFFFFF)
                    self.reported[i] += 1
            self.launches.append((device, live))
        return out

    def on(self, device):
        return [live for dev, live in self.launches if dev == device]


def _solve(items, kernel, devices, **kwargs):
    calls, stats = [], {}
    results = solve_batch_pipelined(
        list(items), rows=ROWS, impl="pallas", plan=_plan(len(items)),
        devices=devices, stats=stats, stall_timeout=30.0,
        on_solved=lambda i, r: calls.append(
            (i, r, len(kernel.launches), time.monotonic())),
        **kwargs)
    return results, calls, stats


# -- taking a copy ------------------------------------------------------


def _uneven(monkeypatch, own_after=6, copy_hits=lambda i, nth: False,
            pace=0.0):
    """Eight objects, a group each, two groups a device.  Device 1's
    (1 and 5) and objects 2 and 3 hit in their first launch; 0, 4, 6
    and 7 in the ``own_after``-th of their own range.  Object 4 is the
    hardest."""
    import jax
    devices = jax.devices()[:4]
    items = _items("uneven", [10 ** 7] * 4 + [4 * 10 ** 7] + [10 ** 7] * 3)

    def hits(i, share, nth):
        if share:
            return copy_hits(i, nth)
        return nth >= (1 if i in (1, 5, 2, 3) else own_after)

    return devices, items, Kernel(items, hits, monkeypatch, pace=pace)


def test_a_lane_with_nothing_live_takes_one_copy_a_turn(monkeypatch):
    devices, items, kernel = _uneven(monkeypatch)
    results, calls, stats = _solve(items, kernel, devices)
    assert all(r is not None for r in results)
    assert sorted(i for i, *_ in calls) == list(range(8))
    assert stats["copies"] >= 1
    # a launch that holds a copy holds that one object and nothing else:
    # one a turn, and never on a lane that has a live slot
    copying = [(dev, live) for dev, live in kernel.launches
               if any(_share(base, 4) for _i, base in live)]
    assert copying and all(len(live) == 1 for _dev, live in copying)
    # device 1 ran out first: lane 0 had the most unresolved objects
    # (0 and 4), each searched by one lane; 4 is the harder
    first = next(live[0] for live in kernel.on(devices[1])
                 if live and _share(live[0][1], 4))
    assert first == (4, pipeline._copy_base(0, 1, 4)) == (4, 1 << 62)
    # a copy's range is the taking lane's share after the object's own
    # lane, and no two holders of an object search the same share
    for dev, ((i, base),) in copying:
        assert _share(base, 4) == (devices.index(dev) - i % 4) % 4 != 0
    # every launch of an object's own range is on its own device
    for dev, live in kernel.launches:
        assert all(devices[i % 4] == dev for i, base in live
                   if not _share(base, 4))


@pytest.mark.parametrize("lanes", [2, 4, 8])
def test_the_shares_of_one_object_never_meet(lanes):
    bases = [pipeline._copy_base(12345, nth, lanes)
             for nth in range(lanes)]
    assert bases[0] == 12345
    gaps = sorted((b - a) % (1 << 64)
                  for a in bases for b in bases if a != b)
    # a network object needs 1e7 to 1e9 trials
    assert gaps[0] >= (1 << 64) // lanes > 10 ** 18
    # and the arithmetic is the counter's, modulo 2**64
    assert pipeline._copy_base((1 << 64) - 5, lanes - 1, lanes) \
        == ((1 << 64) - 5 + (lanes - 1) * ((1 << 64) // lanes)) % (1 << 64)


def test_one_lane_never_copies(monkeypatch):
    import jax
    items = _items("one lane", [10 ** 7] * 4)
    kernel = Kernel(items, lambda i, share, nth: nth >= 1 + 3 * (i == 3),
                    monkeypatch, lanes=1)
    for devices in (None, jax.devices()[:1]):
        before = _family("pow_pipeline_copies_total")
        results, _calls, stats = _solve(items, kernel, devices)
        assert all(r is not None for r in results)
        assert stats["copies"] == 0
        assert not _grown("pow_pipeline_copies_total", before)
        kernel.count.clear()
    assert all(not _share(base, 4) for _dev, live in kernel.launches
               for _i, base in live)


def test_a_lane_that_the_queue_has_something_for_does_not_copy(
        monkeypatch):
    """Two devices.  Device 1's object hits at once; the queue hands
    one object a time to whoever asks, five in all: until it is dry no
    lane copies, then the lane that has run out copies object 0."""
    import jax
    devices = jax.devices()[:2]
    items = _items("fed", [10 ** 7] * 2)
    late = _items("fed late", [10 ** 7] * 5)
    kernel = Kernel(items + late,
                    lambda i, share, nth: nth >= (12 if i == 0 else 1),
                    monkeypatch, lanes=2)
    waiting, dry_at = list(late), []

    def feed(room):
        if not waiting or not room:
            return []
        ih, target = waiting.pop(0)
        if not waiting:
            dry_at.append(len(kernel.launches))
        return [(ih, target, 0)]

    before = _family("pow_pipeline_refills_total")
    results, calls, stats = _solve(items, kernel, devices, feed=feed)
    assert len(results) == 7 and all(r is not None for r in results)
    assert sorted(i for i, *_ in calls) == list(range(7))
    copying = [n for n, (_dev, live) in enumerate(kernel.launches)
               if any(_share(base, 2) for _i, base in live)]
    assert copying and min(copying) > dry_at[0]
    assert stats["copies"] >= 1
    # a copy is no refill
    assert _grown("pow_pipeline_refills_total", before) == {("batch",): 5}


# -- first hit wins -----------------------------------------------------


@pytest.mark.parametrize("winner", ["copy", "own", "either"])
def test_first_hit_wins_whichever_slot_it_is_in(winner, monkeypatch):
    """Object 4 is copied by device 1.  ``copy``: only the copy's
    range hits (in its second launch); ``own``: only its own (in its
    fourth); ``either``: both report a hit in every launch from the
    copy's second on, so the slot read second reports a late one."""
    def copy_hits(i, nth):
        # 0, 6 and 7 end through copies as well: theirs hit at once
        return winner != "own" and (i != 4 or nth >= 2)

    devices, items, kernel = _uneven(
        monkeypatch, own_after=4 if winner == "own" else NEVER,
        copy_hits=copy_hits)
    if winner == "either":
        scripted = kernel.hits
        kernel.hits = lambda i, share, nth: scripted(i, share, nth) or (
            i == 4 and kernel.count[4, 1] >= 2)
    before = _family("pow_pipeline_copies_total")
    results, calls, stats = _solve(items, kernel, devices)
    # one resolution an item, in hit order, the result the call's
    assert sorted(i for i, *_ in calls) == list(range(8))
    assert all(results[i] == r for i, r, _n, _t in calls)
    nonce, trials = results[4]
    if winner == "copy":
        assert _share(nonce, 4) == 1
    elif winner == "own":
        assert _share(nonce, 4) == 0
    else:
        assert kernel.reported[4] >= 2      # a late hit changed nothing
    assert trials % STEP == 0 and trials >= STEP
    # every other holder is a pad slot from its next launch on: no
    # launch dispatched after the resolution holds the object live
    for i, _r, launched, _t in calls:
        later = kernel.launches[launched:]
        assert all(i != j for _dev, live in later for j, _b in live)
    # taken = won + cancelled at the solve's end, by outcome
    grown = _grown("pow_pipeline_copies_total", before)
    assert sum(grown.values()) == stats["copies"] >= 1
    assert set(grown) <= {("batch", "won"), ("batch", "cancelled")}
    if winner == "copy":
        assert grown[("batch", "won")] >= 1
    if winner == "own":
        assert set(grown) == {("batch", "cancelled")}


def test_real_hashes_first_hit_wins_and_the_nonce_is_valid():
    """The XLA stand-in on four devices, four objects that hit at once
    and one that takes a few launches: the chips that have run out
    search copies of it, and whichever slot wins, the nonce passes the
    hashlib check and is reported once."""
    import jax
    devices = jax.devices()[:4]
    items = _items("real", [2] * 4 + [30000])
    calls, stats = [], {}
    results = solve_batch_pipelined(
        items, rows=ROWS, impl="xla", plan=_plan(5), devices=devices,
        stats=stats, on_solved=lambda i, r: calls.append(i))
    assert sorted(calls) == list(range(5))
    assert stats["copies"] >= 1 and stats["devices"] == 4
    for (ih, target), (nonce, trials) in zip(items, results):
        digest = double_sha512(nonce.to_bytes(8, "big") + ih)
        assert int.from_bytes(digest[:8], "big") <= target
        assert trials >= STEP
    assert stats["credited_trials"] == sum(r[1] for r in results)


# -- checkpoints, credit, counters --------------------------------------


def test_progress_comes_from_an_objects_own_range_only(monkeypatch):
    """Object 4 starts at a journaled offset; its copy on device 1
    misses in every launch.  Every checkpoint lies in the object's own
    range, a slab past the last, and the copy's misses move none."""
    start = 7 * SLAB
    devices, items, kernel = _uneven(monkeypatch, own_after=5)
    reported = collections.defaultdict(list)
    results, calls, stats = _solve(
        items, kernel, devices, start_nonces=[0] * 4 + [start] + [0] * 3,
        progress=lambda i, nxt: reported[i].append(nxt))
    assert all(r is not None for r in results) and stats["copies"] >= 1
    assert kernel.count[4, 1] >= 2          # the copy was launched
    assert reported[4] == [start + k * SLAB for k in range(1, 5)]
    for i, ends in reported.items():
        assert ends == sorted(ends) and all(
            not _share(e, 4) for e in ends), i
    # the copy began a share of the nonce space past the object's start
    copies_of_4 = [base for _dev, live in kernel.launches
                   for i, base in live if i == 4 and _share(base, 4)]
    assert min(copies_of_4) == pipeline._copy_base(start, 1, 4)


def test_a_solve_resumed_from_the_checkpoint_finds_a_valid_nonce():
    """Real hashes: the solve is stopped once the hard object's own
    range has reported two checkpoints (copies of it are searching
    elsewhere by then); resumed from the last one on one device it
    finds a nonce at or past the checkpoint that passes the check."""
    import jax
    items = _items("resume", [2] * 4 + [10 ** 6])
    ends = []

    def progress(i, nxt):
        if i == 4:
            ends.append(nxt)

    stats = {}
    try:
        first = solve_batch_pipelined(
            items, rows=ROWS, impl="xla", plan=_plan(5), stats=stats,
            devices=jax.devices()[:4], progress=progress,
            should_stop=lambda: len(ends) >= 2)
    except pipeline.PowInterrupted:
        first = None
    assert len(ends) >= 2 and ends == [k * SLAB for k in
                                       range(1, len(ends) + 1)]
    ih, _hard = items[4]
    # resumed at a difficulty this test can afford; the checkpoint
    # says only where the search may begin
    target = 2 ** 64 // 3000
    if first is not None:
        # the luck of a copy: the drain found it before the stop
        assert first[4][0] >> 62 or first[4][0] < ends[-1] + 2 * SLAB
    ((nonce, trials),) = solve_batch_pipelined(
        [(ih, target)], rows=ROWS, impl="xla", plan=_plan(1),
        start_nonces=[ends[-1]])
    assert ends[-1] <= nonce < ends[-1] + trials
    digest = double_sha512(nonce.to_bytes(8, "big") + ih)
    assert int.from_bytes(digest[:8], "big") <= target


def test_credited_trials_are_the_sum_over_the_items_slots(monkeypatch):
    """Object 4's own range never hits; its copy on device 1 hits in
    its third launch.  Credited: every miss read from either slot
    before the win, a slab each, and the winning step."""
    devices, items, kernel = _uneven(
        monkeypatch, own_after=NEVER,
        copy_hits=lambda i, nth: nth >= (3 if i == 4 else 1))
    own_misses = collections.Counter()
    before = {name: _family(name) for name in (
        "pow_pipeline_needed_trials_total",
        "pow_pipeline_executed_trials_total")}
    results, calls, stats = _solve(
        items, kernel, devices,
        progress=lambda i, nxt: own_misses.update([i]))
    nonce, trials = results[4]
    assert _share(nonce, 4) == 1
    assert trials == (own_misses[4] + 2) * SLAB + STEP
    assert stats["credited_trials"] == sum(r[1] for r in results)
    # a miss read before the win was needed, a copy's too; what the
    # losers' launches still searched after it is computed only
    needed = sum(_grown("pow_pipeline_needed_trials_total",
                        before["pow_pipeline_needed_trials_total"])
                 .values())
    executed = sum(_grown("pow_pipeline_executed_trials_total",
                          before["pow_pipeline_executed_trials_total"])
                   .values())
    assert executed == stats["executed_trials"]
    # every hit here is one past its launch's base: two trials needed
    assert needed == sum(r[1] - STEP + 2 for r in results)
    assert needed < stats["credited_trials"] < executed


# -- the lanes' states --------------------------------------------------


def test_a_lane_that_copies_is_not_starved_and_the_states_add_up(
        monkeypatch):
    def lane_seconds():
        return _family("pow_pipeline_lane_seconds_total")

    devices, items, kernel = _uneven(monkeypatch, pace=0.002)
    before = lane_seconds()
    TRACER.clear()
    results, calls, stats = _solve(items, kernel, devices)
    assert all(r is not None for r in results) and stats["copies"] >= 1
    grown = _grown("pow_pipeline_lane_seconds_total", before)
    assert {state for _dev, state in grown} <= set(LANE_STATES)
    assert sum(grown.values()) == pytest.approx(
        4 * stats["wall_seconds"], abs=1e-3)
    for dev in devices:
        assert sum(v for (d, _s), v in grown.items()
                   if d == "%d" % dev.id) \
            == pytest.approx(stats["wall_seconds"], abs=1e-3)
    # device 1 ran out after its first launches and searched copies to
    # the end: while an object was unresolved anywhere it never starved
    last = max(t for *_rest, t in calls)
    starved = [s for s in TRACER.recent(4096)
               if s.name == "pow.lane.starved"]
    assert all(s.start >= last - 1e-3 for s in starved), starved
    assert grown.get(("%d" % devices[1].id, "starved"), 0.0) \
        < 0.1 * stats["wall_seconds"]
    launches = [s for s in TRACER.recent(4096) if s.name == "pow.launch"]
    assert sum(s.attrs["copied"] for s in launches) == stats["copies"]
    assert all(s.attrs["copied"] in (0, 1) and not s.attrs["refilled"]
               for s in launches)


def test_the_driver_closes_starved_at_the_launch_of_a_copy():
    """``_PipelineDriver`` alone, scripted: lane 1 finds nothing until
    lane 0's second launch is read, then a launch for an object that is
    not its own (its load stays what the script says).  Its ``starved``
    interval ends at that launch and the three states add up."""
    TRACER.clear()
    harvested, left, copied_at = [], [5], []

    def next_launch(lane):
        if lane == 1:
            if len(harvested) < 2 or copied_at:
                return None
            copied_at.append(time.monotonic())
            return "tag", "copy"
        if not left[0]:
            return None
        left[0] -= 1
        return "tag", left[0]

    def fetch(dev):
        time.sleep(0.003)
        return dev

    driver = _PipelineDriver(depth=1, lanes=2, fetch=fetch,
                             kind="t_copy_lane", devices=[21, 22])
    driver.run(next_launch, lambda _t, host: harvested.append(host),
               load={0: 2, 1: 0}.get)
    assert "copy" in harvested and len(harvested) == 6
    assert sum(driver.lane_seconds.values()) == pytest.approx(
        2 * driver.wall_seconds, abs=1e-3)
    starved = sorted((s for s in TRACER.recent(256)
                      if s.name == "pow.lane.starved"
                      and s.attrs["device"] == 22), key=lambda s: s.start)
    assert starved[0].end == pytest.approx(copied_at[0], abs=0.05)
    assert starved[0].end >= copied_at[0]
    assert driver.lane_seconds["inflight"] > 6 * 0.003 - 1e-3
