"""One object alone on a host of several chips (ISSUE 43;
``pow/pipeline.py`` ``solve_batch_pipelined`` in mode ``slab`` with
``devices``, ``PowDispatcher._solve_on_device``).

The object's nonce space is shared out over the pipeline driver's
lanes: lane ``k`` searches from ``_copy_base(start, k, lanes)`` in its
own launches, all lanes are launched in the first turn, the first
harvest with a hit resolves the object and what the others still search
is abandoned.  Held here, on four of the suite's virtual devices with
an XLA program of real hashes where ``pallas_search`` is: that the
nonce is one the plain reference accepts, that the shares tie to the
whole, that a checkpoint is the own lane's and a resumed search skips
nothing, that the watchdog and the speculation rule hold on lanes as
they do on one chip; and (ISSUE 44) what the host does at the solve's
head: nothing crosses to a device under ``pow.groups``, a lane's words
and target ride its launch as numpy, its base is on its device from the
last object that began there, every lane is launched, in lane order,
before a fetch is out, and ``pow_pipeline_lone_head_seconds`` times it once a solve.
"""

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
from pybitmessage_tpu.pow import pipeline  # noqa: E402
from pybitmessage_tpu.pow.dispatcher import PowDispatcher  # noqa: E402
from pybitmessage_tpu.pow.pipeline import (_copy_base,  # noqa: E402
                                           plan_batch,
                                           solve_batch_pipelined)

ROWS, CHUNKS, LANES = 8, 2, 4
#: trials of one grid step and of one launch of a lane at that geometry
STEP = ROWS * sha512_pallas.LANE_COLS * sha512_pallas.DEFAULT_UNROLL
SLAB = STEP * CHUNKS
SHARE = (1 << 64) // LANES
MASK = (1 << 64) - 1
#: mean trials of a test object: ten rounds of four lanes or so
EXPECTED = 4 * 10 ** 5


def _item(tag, expected=EXPECTED):
    return (hashlib.sha512(b"lone lanes %s" % str(tag).encode()).digest(),
            2 ** 64 // expected)


def _plan(chunks=CHUNKS):
    return pipeline.BatchPlan("slab", 1, chunks, [0])


def _family(name: str) -> dict:
    return {values: child.value
            for values, child in REGISTRY.get(name).children()}


def _observed(name: str) -> dict:
    """Observations of each child of a histogram."""
    return {values: child.snapshot()[2]
            for values, child in REGISTRY.get(name).children()}


def _grown(name: str, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _family(name).items()
            if v != before.get(k, 0)}


@pytest.fixture
def devices():
    import jax
    devs = jax.devices()[:LANES]
    assert len(devs) == LANES
    return devs


class Slab:
    """Stands where ``pallas_search`` is: an XLA program of real hashes
    with its output contract, on the device its base is on (the one
    operand of a lone object's launch that is on a device before it).
    Keeps every launch: ``(device, base, trials)``."""

    def __init__(self, monkeypatch):
        import jax

        from pybitmessage_tpu.parallel.pow_pallas_sharded import _xla_slab
        self.launches = []
        slab = jax.jit(_xla_slab, static_argnames=("rows", "chunks"))

        def search(ih_words, base, target, rows, chunks, unroll,
                   interpret):
            (device,) = base.devices()
            b = np.asarray(base)
            self.launches.append((device, (int(b[0]) << 32) | int(b[1]),
                                  rows * 128 * chunks * unroll))
            # a grid step is ``unroll`` tiles of ``rows`` rows
            return slab(ih_words, base, target, rows=rows * unroll,
                        chunks=chunks)

        monkeypatch.setattr(sha512_pallas, "pallas_search", search)

    def of(self, device):
        """``[base, end)`` of each launch on ``device``, in order; the
        nonce space is a ring, so an end may lie below its base."""
        return [(base, (base + n) & MASK) for dev, base, n in self.launches
                if dev == device]


def _solve(item, devices, **kwargs):
    stats = {}
    (result,) = solve_batch_pipelined(
        [item], rows=ROWS, impl="pallas", plan=_plan(), devices=devices,
        stats=stats, stall_timeout=30.0, **kwargs)
    return result, stats


# -- (1) the dispatcher's nonce, by the plain reference ------------------


@pytest.fixture
def four_chips(monkeypatch, devices):
    """The dispatcher told that it has four accelerator chips, the
    pipeline at a tile of 8 rows, and the XLA slab where the kernel
    is."""
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: True)
    monkeypatch.setattr(PowDispatcher, "_device_count",
                        lambda self: LANES)
    for key, value in (("rows", ROWS), ("impl", "pallas")):
        monkeypatch.setitem(solve_batch_pipelined.__kwdefaults__,
                            key, value)
    # a lane's slab: two steps each
    monkeypatch.setattr(pipeline, "LONE_LANES_CHUNKS", CHUNKS * LANES)
    return Slab(monkeypatch)


@pytest.mark.parametrize("seed", range(20))
def test_the_dispatcher_s_nonce_for_a_lone_object_on_four_devices_is_one_the_reference_accepts(  # noqa: E501
        seed, four_chips):
    ih, target = _item("seed %d" % seed)
    d = PowDispatcher(use_native=False)
    before = _family("pow_attempts_total")
    wins = _family("pow_pipeline_lone_wins_total")
    nonce, trials = d(ih, target)
    assert reference.trial_value(nonce.to_bytes(8, "big"), ih) <= target
    assert d.last_backend == "tpu-pallas"
    assert _grown("pow_attempts_total", before) == {("tpu-pallas",): 1}
    # laid out over the four: every device launched in the first turn,
    # at its own share of the nonce space, and the winner's lane is the
    # share the nonce lies in
    first = four_chips.launches[:LANES]
    assert len({dev for dev, _b, _n in first}) == LANES
    assert sorted(base for _d, base, _n in first) \
        == [k * SHARE for k in range(LANES)]
    assert _grown("pow_pipeline_lone_wins_total", wins) \
        == {("%d" % (nonce // SHARE),): 1}
    assert 0 < trials <= sum(n for _d, _b, n in four_chips.launches)


# -- (2) the shares tie to the whole ------------------------------------


@pytest.mark.parametrize("lanes, chunks", [(1, 512), (2, 128), (4, 64),
                                           (8, 32)])
def test_a_lone_object_s_slab_is_shared_out_over_the_lanes(lanes, chunks):
    """One chip launches the slab it always did; several share 256 grid
    steps between them."""
    plan = plan_batch([_item("plan", 10 ** 7)], lanes=lanes)
    assert (plan.mode, plan.chunks, plan.order) == ("slab", chunks, [0])
    # announced company makes it a queue, however many lanes
    assert plan_batch([_item("plan", 10 ** 7)], expect=2,
                      lanes=lanes).mode == "batched"


@pytest.mark.parametrize("start", [0, 12345, SHARE - 3 * SLAB // 2])
def test_the_lanes_ranges_are_disjoint_and_begin_at_their_shares(
        start, devices, monkeypatch):
    slab = Slab(monkeypatch)
    item = _item("shares %d" % start)
    TRACER.clear()
    abandoned = _family("pow_pipeline_abandoned_launches_total")
    (nonce, trials), stats = _solve(item, devices, start_nonces=[start])
    assert reference.trial_value(nonce.to_bytes(8, "big"), item[0]) \
        <= item[1]
    assert (stats["mode"], stats["groups"], stats["devices"]) \
        == ("slab", LANES, LANES)
    ranges = [slab.of(dev) for dev in devices]
    for k, mine in enumerate(ranges):
        # each lane begins at its share and goes on slab by slab
        assert mine[0][0] == _copy_base(start, k, LANES)
        assert all((b - a) & MASK == SLAB for a, b in mine)
        assert all(nxt[0] == cur[1] for cur, nxt in zip(mine, mine[1:]))
    # pairwise disjoint, round the ring (the last lane's share may run
    # past 2**64 and on from 0: still nobody else's)
    flat = sorted(r for mine in ranges for r in mine)
    assert all(cur[1] <= nxt[0] or cur[1] < cur[0]
               for cur, nxt in zip(flat, flat[1:]))
    assert sum(b < a for a, b in flat) <= 1
    assert all(b <= flat[0][0] for a, b in flat if b < a)
    # the winner's lane is the one whose range holds the nonce
    lane = next(k for k, mine in enumerate(ranges)
                if any((nonce - a) & MASK < SLAB for a, _b in mine))
    # credited: what the harvested launches searched, lane by lane, the
    # winner's last up to the step of its hit; what was in flight at
    # the win is abandoned unread and in nobody's count
    harvests = TRACER.recent(len(slab.launches) + 1, name="pow.harvest")
    read = [sum(1 for s in harvests if s.attrs["device"] == k)
            for k in range(LANES)]
    left = _grown("pow_pipeline_abandoned_launches_total", abandoned)
    assert sum(read) + left.get(("slab",), 0) == len(slab.launches) \
        == stats["launches"]
    searched = [n * SLAB for n in read]
    hit_base = ranges[lane][read[lane] - 1][0]
    searched[lane] += (((nonce - hit_base) & MASK) // STEP + 1) * STEP \
        - SLAB
    assert trials == stats["credited_trials"] == sum(searched)
    assert stats["executed_trials"] == sum(searched)


def test_one_lane_is_the_lone_object_s_solve_of_one_chip(devices,
                                                         monkeypatch):
    Slab(monkeypatch)
    item = _item("one lane")
    alone, stats_alone = _solve(item, None)
    one, stats_one = _solve(item, devices[:1])
    assert one == alone
    for key in ("mode", "groups", "devices", "launches",
                "credited_trials", "executed_trials"):
        assert stats_one[key] == stats_alone[key], key
    assert (stats_one["groups"], stats_one["devices"]) == (1, 1)


# -- (3) a checkpoint is the own lane's, and a resume skips nothing ------


@pytest.mark.parametrize("lanes", [1, LANES])
def test_progress_is_the_own_lane_s_and_a_resumed_search_skips_nothing(
        lanes, devices, monkeypatch):
    devices = devices[:lanes]
    slab = Slab(monkeypatch)
    item = _item("resume", 3 * EXPECTED)
    start, seen, solved = 777, [], []

    def progress(i, nxt):
        seen.append((i, nxt))

    with pytest.raises(PowInterrupted):
        _solve(item, devices, start_nonces=[start], progress=progress,
               on_solved=lambda i, result: solved.append((i, result)),
               should_stop=lambda: len(seen) >= 2)
    assert not solved
    # the own lane's frontier only: lane 0's slabs read miss-free, in
    # order; nothing of a share 2**62 away
    assert seen and all(i == 0 for i, _n in seen)
    frontier = [n for _i, n in seen]
    assert frontier == [start + (m + 1) * SLAB
                        for m in range(len(frontier))]
    first_run = slab.of(devices[0])
    # every nonce below the checkpoint was searched by lane 0
    assert first_run[0][0] == start
    covered = [r for r in first_run if r[1] <= frontier[-1]]
    assert covered[-1][1] == frontier[-1]
    assert all(nxt[0] == cur[1] for cur, nxt in zip(covered, covered[1:]))
    # resumed from the checkpoint, lane 0 goes on exactly there and the
    # others at their shares of the new start
    del slab.launches[:]
    seen.clear()
    (nonce, trials), _stats = _solve(
        item, devices, start_nonces=[frontier[-1]], progress=progress,
        on_solved=lambda i, result: solved.append((i, result)))
    # the nonce is one hashlib accepts, and the object left once
    check = hashlib.sha512(hashlib.sha512(
        nonce.to_bytes(8, "big") + item[0]).digest()).digest()
    assert int.from_bytes(check[:8], "big") <= item[1]
    assert reference.trial_value(nonce.to_bytes(8, "big"), item[0]) \
        <= item[1]
    assert solved == [(0, (nonce, trials))]
    for k, dev in enumerate(devices):
        assert slab.of(dev)[0][0] == _copy_base(frontier[-1], k, lanes)
    assert all(n > frontier[-1] and (n - frontier[-1]) % SLAB == 0
               for _i, n in seen)


# -- (4) the watchdog, on one of four devices ----------------------------


class _NeverIn:
    """What a launch's output is to the driver's fetch: an array that
    does not come in until ``release`` is set."""

    def __init__(self, release):
        self.release = release

    def __array__(self, *_a, **_kw):
        self.release.wait(10)
        return np.zeros(1, np.int32)


def test_a_launch_that_never_comes_in_on_one_of_four_trips_the_watchdog(
        four_chips, devices, monkeypatch):
    """The rung hands the object down, as on one chip: the stall is
    counted, the Mosaic rung's breaker opens, the next rung solves."""
    import pybitmessage_tpu.parallel as par

    release = threading.Event()
    search = sha512_pallas.pallas_search

    def wedged(ih_words, base, target, **kw):
        out = search(ih_words, base, target, **kw)
        if base.devices() == {devices[2]}:
            return _NeverIn(release), out[1]
        return out

    monkeypatch.setattr(sha512_pallas, "pallas_search", wedged)
    handed = []

    def sharded_solve(ih, target, mesh, **kw):
        handed.append((mesh.devices.size, kw.get("start_nonce")))
        return 4242, 1

    monkeypatch.setattr(par, "sharded_solve", sharded_solve)
    # hard enough that no other lane wins before the deadline
    ih, target = _item("wedged", 10 ** 9)
    d = PowDispatcher(use_native=False, stall_timeout=0.3)
    stalls = REGISTRY.sample("pow_stall_total", {"site": "pow.slab"})
    t0 = time.monotonic()
    nonce, _trials = d(ih, target)
    release.set()
    assert time.monotonic() - t0 < 8
    # the other three were read and launched again meanwhile
    assert len(four_chips.launches) > LANES
    assert nonce == 4242 and handed == [(LANES, 0)]
    assert d.last_backend == "tpu-sharded"
    assert REGISTRY.sample("pow_stall_total",
                           {"site": "pow.slab"}) == stalls + 1
    assert d.breakers["tpu-pallas"].state == "open"


# -- (5) the speculation rule, on lanes ----------------------------------


class Misses:
    """Stands where ``pallas_search`` is and hashes nothing: every
    launch misses until ``after`` have been dispatched, the next hits
    in its first step.  Keeps each launch's device."""

    def __init__(self, after, monkeypatch):
        self.after, self.launches = after, []
        monkeypatch.setattr(sha512_pallas, "pallas_search", self)
        monkeypatch.setattr(pipeline, "_checked_nonce",
                            lambda nonce, initial_hash, target: nonce)

    def __call__(self, ih_words, base, target, rows, chunks, unroll,
                 interpret):
        (device,) = base.devices()
        self.launches.append(device)
        found = np.zeros(chunks, np.int32)
        nonce = np.zeros((chunks, 2), np.uint32)
        if len(self.launches) > self.after:
            found[0], nonce[0] = 1, np.asarray(base)
        return found, nonce


@pytest.mark.parametrize("expected, ahead", [(10 ** 7, False),
                                             (49 * 10 ** 8, True)])
def test_no_launch_is_dispatched_ahead_of_an_unread_one_that_may_end_the_object(  # noqa: E501
        expected, ahead, devices, monkeypatch):
    """PR 30's rule holds on lanes, at the production geometry: the
    unread launches of ALL lanes search the one object, so for a
    network-default object (1e7 trials) no lane gets a second launch in
    flight, and an object of 4.9e9 trials, which they are unlikely to
    end, does."""
    kernel = Misses(3 * LANES, monkeypatch)
    item = _item("rule", expected)
    plan = plan_batch([item], lanes=LANES)
    assert (plan.mode, plan.chunks) == ("slab", 64)
    TRACER.clear()
    before = _family("pow_pipeline_speculation_total")
    stats = {}
    (result,) = solve_batch_pipelined(
        [item], impl="pallas", plan=plan, devices=devices, stats=stats,
        stall_timeout=30.0)
    assert result[0] % SHARE < 2 ** 40
    grown = _grown("pow_pipeline_speculation_total", before)
    launches = TRACER.recent(len(kernel.launches) + 1, name="pow.launch")
    assert len(launches) == len(kernel.launches) == stats["launches"]
    assert any(s.attrs["speculative"] for s in launches) is ahead
    if ahead:
        assert grown.get(("slab", "launched"), 0) > 0
        return
    assert set(grown) == {("slab", "withheld")}
    # lane by lane: a launch, its harvest, the next launch
    events = sorted(
        [(s.start, s.attrs["device"], "launch") for s in launches]
        + [(s.start, s.attrs["device"], "harvest")
           for s in TRACER.recent(len(launches) + 1, name="pow.harvest")])
    for k in range(LANES):
        mine = [what for _t, dev, what in events if dev == k]
        assert mine[0] == "launch"
        assert all(a != b for a, b in zip(mine, mine[1:])), (k, mine)


# -- (6) the head of the solve: what crosses to the devices, and when ----


class Crossings:
    """Stands where ``jax.device_put`` is and keeps every call:
    ``(when, device, value)``; and where the driver hands a call to a
    guard worker, ``(when, what)``.  The bases that earlier solves left
    on the devices are forgotten first."""

    def __init__(self, monkeypatch):
        import jax
        self.puts, self.submits = [], []
        put, submit = jax.device_put, pipeline._PipelineDriver._submit

        def device_put(x, device=None, **kw):
            self.puts.append((time.monotonic(), device, np.asarray(x)))
            return put(x, device, **kw)

        def _submit(driver, fn, *args):
            self.submits.append((time.monotonic(),
                                 getattr(fn, "__name__", "fetch")))
            return submit(driver, fn, *args)

        monkeypatch.setattr(jax, "device_put", device_put)
        monkeypatch.setattr(pipeline._PipelineDriver, "_submit", _submit)
        pipeline._pair_on_device.cache_clear()


class Operands(Misses):
    """``Misses`` that keeps each launch's operands as they came (what
    rides the call as numpy is a view of the group's arrays: a copy of
    it) and when the call returned: ``(when, device, words, base,
    target)``."""

    def __call__(self, ih_words, base, target, **shape):
        out = super().__call__(ih_words, base, target, **shape)
        self.calls = getattr(self, "calls", [])
        assert isinstance(ih_words, np.ndarray) \
            and isinstance(target, np.ndarray)
        (device,) = base.devices()
        self.calls.append((time.monotonic(), device, ih_words.copy(),
                           base, target.copy()))
        return out


def _pair(value):
    return list(pipeline._split64(value & MASK))


@pytest.mark.parametrize("lanes", [1, LANES])
def test_nothing_crosses_before_the_launches_and_a_known_base_never_again(
        lanes, devices, monkeypatch):
    """A lane's operands cross in its launch: words and target as numpy
    riding the call, the base from the device it is on already, put
    there at most once a lane a round and never under ``pow.groups``;
    the next object, which begins where this one did, puts nothing."""
    devices = devices[:lanes]
    kernel = Operands(2 * lanes, monkeypatch)       # two rounds miss
    crossed = Crossings(monkeypatch)
    # easy enough that no launch is dispatched ahead of an unread one
    item, start = _item("crossings", 2 * 10 ** 5), 4242
    TRACER.clear()
    _solve(item, devices, start_nonces=[start])
    (groups,) = TRACER.recent(50, name="pow.groups")
    assert len(kernel.calls) == 3 * lanes
    assert not [t for t, _d, _x in crossed.puts
                if groups.start <= t <= groups.end]
    # lane by lane: one pair a launch, the launch's own base, to the launch's device,
    # after the lane's launch before it had returned
    assert len(crossed.puts) == len(kernel.calls)
    for k, lane_device in enumerate(devices):
        puts = [(t, x) for t, device, x in crossed.puts
                if device == lane_device]
        calls = [call for call in kernel.calls if call[1] == lane_device]
        assert len(puts) == len(calls) == 3
        last = groups.end
        for rnd, ((t_put, value), call) in enumerate(zip(puts, calls)):
            t_call, _device, words, base, target = call
            assert last <= t_put <= t_call
            assert value.tolist() == _pair(
                _copy_base(start, k, lanes) + rnd * SLAB) \
                == np.asarray(base).tolist()
            assert base.devices() == {lane_device}
            # what is new with every solve rides the call
            assert words.shape == (8, 2)
            assert target.tolist() == _pair(item[1])
            last = t_call
    # every round launches the words it launched first
    assert all(call[2].tolist() == kernel.calls[0][2].tolist()
               for call in kernel.calls)
    # the same ranges again, another object: nothing crosses but in
    # the launches themselves
    del crossed.puts[:], kernel.calls[:], kernel.launches[:]
    _solve(_item("crossings, the next object", 2 * 10 ** 5), devices,
           start_nonces=[start])
    assert len(kernel.calls) == 3 * lanes and not crossed.puts


@pytest.mark.parametrize("lanes", [1, LANES])
def test_every_lane_is_launched_in_lane_order_before_a_fetch_is_out(
        lanes, devices, monkeypatch):
    """The first turn: every lane's launch, one after the other in
    lane order, each at its share of the nonce space; the fetches are
    handed to the guard workers when the last of them has returned,
    and nothing else is."""
    devices = devices[:lanes]
    kernel = Operands(lanes, monkeypatch)           # one round misses
    item, start = _item("head", 2 * 10 ** 5), 99
    _solve(item, devices, start_nonces=[start])     # the shape is known
    crossed = Crossings(monkeypatch)
    del kernel.calls[:], kernel.launches[:]
    head = _observed("pow_pipeline_lone_head_seconds")
    t_in = time.monotonic()
    _solve(item, devices, start_nonces=[start])
    wall = time.monotonic() - t_in
    first = kernel.calls[:lanes]
    assert [call[1] for call in first] == devices
    assert [np.asarray(call[3]).tolist() for call in first] \
        == [_pair(_copy_base(start, k, lanes)) for k in range(lanes)]
    out = first[-1][0]
    assert crossed.submits and all(
        what == "default_fetch" and t >= out
        for t, what in crossed.submits)
    # timed once, under the lane count, from the solve's entry on
    now = _observed("pow_pipeline_lone_head_seconds")
    assert {k: n - head.get(k, 0) for k, n in now.items()
            if n != head.get(k, 0)} == {("%d" % lanes,): 1}
    assert 0 < out - t_in <= wall


def test_a_queue_s_solve_observes_no_lone_head(devices):
    head = _observed("pow_pipeline_lone_head_seconds")
    items = [_item("queue %d" % i, 2000) for i in range(3)]
    stats = {}
    results = solve_batch_pipelined(items, rows=ROWS, impl="xla",
                                    devices=devices, stats=stats,
                                    stall_timeout=30.0)
    assert stats["mode"] != "slab" and len(results) == 3
    assert _observed("pow_pipeline_lone_head_seconds") == head

