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
they do on one chip.
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
    with its output contract, on the device its hash words are on.
    Keeps every launch: ``(device, base, trials)``."""

    def __init__(self, monkeypatch):
        import jax

        from pybitmessage_tpu.parallel.pow_pallas_sharded import _xla_slab
        self.launches = []
        slab = jax.jit(_xla_slab, static_argnames=("rows", "chunks"))

        def search(ih_words, base, target, rows, chunks, unroll,
                   interpret):
            (device,) = ih_words.devices()
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


def test_progress_is_the_own_lane_s_and_a_resumed_search_skips_nothing(
        devices, monkeypatch):
    slab = Slab(monkeypatch)
    item = _item("resume", 3 * EXPECTED)
    start, seen = 777, []

    def progress(i, nxt):
        seen.append((i, nxt))

    with pytest.raises(PowInterrupted):
        _solve(item, devices, start_nonces=[start], progress=progress,
               should_stop=lambda: len(seen) >= 2)
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
    (nonce, _trials), _stats = _solve(
        item, devices, start_nonces=[frontier[-1]], progress=progress)
    assert reference.trial_value(nonce.to_bytes(8, "big"), item[0]) \
        <= item[1]
    for k, dev in enumerate(devices):
        assert slab.of(dev)[0][0] == _copy_base(frontier[-1], k, LANES)
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
        if ih_words.devices() == {devices[2]}:
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
        (device,) = ih_words.devices()
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
