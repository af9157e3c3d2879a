"""One object alone on several chips of an accelerator (ISSUE 49;
``pow/pipeline.py`` ``solve_batch_pipelined`` in mode ``slab`` where
``_one_program`` says so, ``ops/sha512_ici.py``).

There the lanes are ONE program over the devices whose kernels stop at
the first hit, and every lane's row says at which step it left and why.
The kernel's flag cannot run here (the TPU interpreter reads no
semaphore on the ``cpu`` backend): it is proven on the chip
(``chip_smoke.py``, ``tools/lone_lanes_bench.py --ici``).  Held here,
on four of the suite's virtual devices with a stand-in that keeps the
entry's contract (its XLA equivalent ``pipeline._ici_search_xla``, real
hashes, which ``impl="xla"`` launches by itself; or rows made by
hand): the host's lay-out around it.  One launch, one fetch and one
harvest a solve; the nonce one the plain reference accepts; the
counters fed from EVERY lane's row; two lanes that hit in one step; a
hard object's second launch dispatched ahead and no other; the
checkpoint lane 0's; ``should_stop`` and ``stall_timeout`` honoured;
the lane states of every device; and what decides the path.
"""

import hashlib
import pathlib
import sys
import threading
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
for _path in (REPO, REPO / "tests"):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from benchmarks import reference  # noqa: E402
from pybitmessage_tpu.observability import REGISTRY, TRACER  # noqa: E402
from pybitmessage_tpu.ops import sha512_ici, sha512_pallas  # noqa: E402
from pybitmessage_tpu.ops.pow_search import PowInterrupted  # noqa: E402
from pybitmessage_tpu.pow import pipeline  # noqa: E402
from pybitmessage_tpu.pow.dispatcher import PowDispatcher  # noqa: E402
from pybitmessage_tpu.pow.pipeline import (_copy_base,  # noqa: E402
                                           plan_batch,
                                           solve_batch_pipelined)
from pybitmessage_tpu.resilience.watchdog import SlabStallError  # noqa: E402

ROWS, CHUNKS, LANES = 8, 8, 4
#: trials of one grid step and of one launch of a lane at that geometry
STEP = ROWS * sha512_pallas.LANE_COLS * sha512_pallas.DEFAULT_UNROLL
SLAB = STEP * CHUNKS
SHARE = (1 << 64) // LANES
MASK = (1 << 64) - 1
#: mean trials of a test object: a launch of four lanes misses it with
#: a chance of e^-8
EXPECTED = 2 * 10 ** 4
WHY = {sha512_ici.OWN_HIT: "own_hit", sha512_ici.CANCELLED: "cancelled",
       sha512_ici.RAN_OUT: "ran_out"}


def _item(tag, expected=EXPECTED):
    return (hashlib.sha512(b"lone ici %s" % str(tag).encode()).digest(),
            2 ** 64 // expected)


def _plan(chunks=CHUNKS):
    return pipeline.BatchPlan("slab", 1, chunks, [0], one_program=True)


def _family(name: str) -> dict:
    return {values: child.value
            for values, child in REGISTRY.get(name).children()}


def _grown(name: str, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _family(name).items()
            if v != before.get(k, 0)}


def _hist(name: str):
    """(sum, count) of a histogram without labels."""
    ((_values, child),) = REGISTRY.get(name).children()
    _buckets, total, count = child.snapshot()
    return total, count


def _heads() -> dict:
    """Observations of ``pow_pipeline_lone_head_seconds`` by label."""
    return {values: child.snapshot()[2] for values, child in
            REGISTRY.get("pow_pipeline_lone_head_seconds").children()}


@pytest.fixture
def devices():
    import jax
    devs = jax.devices()[:LANES]
    assert len(devs) == LANES
    return devs


@pytest.fixture
def one_program(monkeypatch):
    """The pipeline told that these virtual devices take the one
    program, as an accelerator's chips do."""
    monkeypatch.setattr(
        pipeline, "_one_program",
        lambda impl, devices: impl == "pallas" and len(devices) > 1)


class Program:
    """Stands where ``ici_search`` is: its XLA equivalent, real hashes
    under the entry's contract, a cancelled lane leaving ``lag`` steps
    past the winner's.  Keeps every launch: ``(devices, operands)`` and
    the rows it answered with."""

    def __init__(self, monkeypatch, lag=0):
        self.launches, self.rows = [], []

        def search(operands, devices, rows, chunks, unroll, interpret):
            assert isinstance(operands, np.ndarray)
            assert operands.shape == (len(devices),
                                      sha512_ici.OPERAND_WORDS)
            assert operands.dtype == np.uint32
            self.launches.append((list(devices), operands.copy()))
            # a grid step is ``unroll`` tiles of ``rows`` rows
            out = pipeline._ici_search_xla(
                operands, lanes=rows * sha512_pallas.LANE_COLS * unroll,
                chunks=chunks, lag=lag)
            self.rows.append(np.asarray(out))
            return out

        monkeypatch.setattr(sha512_ici, "ici_search", search)

    def bases(self, n=-1):
        """The lanes' bases in launch ``n``."""
        ops = self.launches[n][1]
        return [(int(r[16]) << 32) | int(r[17]) for r in ops]


def _solve(item, devices, **kwargs):
    stats = {}
    (result,) = solve_batch_pipelined(
        [item], rows=ROWS, impl="pallas", plan=_plan(), devices=devices,
        stats=stats, stall_timeout=30.0, **kwargs)
    return result, stats


# -- (1) what decides the path, and its shape ----------------------------


class _Chip:
    platform = "tpu"


def test_the_one_program_is_several_devices_that_can_run_it(devices):
    chips = [_Chip()] * LANES
    assert pipeline._one_program("pallas", chips)
    # the XLA equivalent runs anywhere
    assert pipeline._one_program("xla", chips)
    assert pipeline._one_program("xla", devices)
    # one device: ``pallas_search`` as ever; the Mosaic kernel on the
    # CPU's virtual devices: a launch a lane
    assert not pipeline._one_program("pallas", chips[:1])
    assert not pipeline._one_program("xla", devices[:1])
    assert not pipeline._one_program("pallas", devices)
    assert not pipeline._one_program("pallas", [None])
    assert not pipeline._one_program("xla", [None])


@pytest.mark.parametrize("lanes", [2, 4, 8])
def test_a_launch_of_the_one_program_is_as_long_as_one_chip_s(lanes):
    item = _item("plan", 10 ** 7)
    plan = plan_batch([item], lanes=lanes, one_program=True)
    assert (plan.mode, plan.chunks, plan.order) \
        == ("slab", sha512_pallas.DEFAULT_CHUNKS, [0])
    assert plan.one_program
    alone = plan_batch([item], lanes=1, one_program=True)
    assert (alone.chunks, alone.one_program) == (plan.chunks, False)
    # a launch a lane stays short
    apiece = plan_batch([item], lanes=lanes)
    assert (apiece.chunks, apiece.one_program) \
        == (pipeline.LONE_LANES_CHUNKS // lanes, False)
    # announced company makes it a queue, whoever launches
    assert plan_batch([item], expect=2, lanes=lanes,
                      one_program=True).mode == "batched"


def test_the_virtual_devices_keep_a_launch_a_lane(devices, monkeypatch):
    """Without the fixture: ``impl="pallas"`` on the CPU's devices goes
    through ``pallas_search`` lane by lane, as
    ``tests/test_pow_lone_lanes.py`` holds it."""
    def never(*_a, **_kw):
        raise AssertionError("the one program, on the cpu backend")

    monkeypatch.setattr(sha512_ici, "ici_search", never)
    from test_pow_lone_lanes import Slab
    slab = Slab(monkeypatch)
    monkeypatch.setattr(pipeline, "LONE_LANES_CHUNKS", 2 * LANES)
    (result,) = solve_batch_pipelined(
        [_item("a launch a lane", 4 * 10 ** 5)], rows=ROWS, impl="pallas",
        devices=devices, stall_timeout=30.0)
    assert len({dev for dev, _b, _n in slab.launches}) == LANES
    assert result[1] > 0


@pytest.mark.parametrize("seed", range(4))
def test_the_xla_equivalent_is_launched_as_the_one_program_by_itself(
        seed, devices, monkeypatch):
    """``impl="xla"`` on several devices, nothing replaced: the plan
    says one program, the launch is ``_ici_search_xla``'s, one a
    solve, and every lane's row feeds the counters."""
    def never(*_a, **_kw):
        raise AssertionError("the Mosaic program, on the cpu backend")

    monkeypatch.setattr(sha512_ici, "ici_search", never)
    monkeypatch.setattr(sha512_pallas, "pallas_search", never)
    item = _item("xla %d" % seed)
    lanes_before = _family("pow_pipeline_lone_lanes_total")
    lag_before = _hist("pow_pipeline_lone_cancel_lag_steps")
    stats = {}
    (result,) = solve_batch_pipelined(
        [item], rows=ROWS, impl="xla", unroll=sha512_pallas.DEFAULT_UNROLL,
        plan=_plan(), devices=devices, stats=stats, stall_timeout=30.0)
    nonce, trials = result
    assert reference.trial_value(nonce.to_bytes(8, "big"), item[0]) \
        <= item[1]
    assert (stats["launches"], stats["groups"], stats["devices"]) \
        == (1, 1, LANES)
    lanes = _grown("pow_pipeline_lone_lanes_total", lanes_before)
    assert lanes.pop(("won",)) == 1
    assert sum(lanes.values()) == LANES - 1
    # a cancelled lane of the equivalent leaves a step past the hit,
    # as the chip's mostly do, or at the launch's end
    total, count = _hist("pow_pipeline_lone_cancel_lag_steps")
    cancelled = lanes.get(("cancelled",), 0)
    assert count - lag_before[1] == cancelled
    assert total - lag_before[0] <= cancelled
    assert 0 < trials <= stats["executed_trials"] <= LANES * SLAB


# -- (2) one launch, one fetch, one harvest; every lane's row counted ----


@pytest.mark.parametrize("lag", [0, 1, 2])
@pytest.mark.parametrize("seed", range(4))
def test_a_solve_is_one_launch_one_fetch_one_harvest_and_every_lane_is_counted(  # noqa: E501
        seed, lag, devices, one_program, monkeypatch):
    program = Program(monkeypatch, lag=lag)
    item = _item("one launch %d" % seed)
    before = {name: _family(name) for name in (
        "pow_pipeline_lone_lanes_total", "pow_pipeline_lone_wins_total",
        "pow_pipeline_executed_trials_total",
        "pow_pipeline_needed_trials_total",
        "pow_pipeline_launches_total",
        "pow_pipeline_device_launches_total",
        "pow_pipeline_abandoned_launches_total")}
    lag_before = _hist("pow_pipeline_lone_cancel_lag_steps")
    TRACER.clear()
    solved = []
    (nonce, trials), stats = _solve(
        item, devices, on_solved=lambda i, r: solved.append((i, r)))
    assert reference.trial_value(nonce.to_bytes(8, "big"), item[0]) \
        <= item[1]
    assert solved == [(0, (nonce, trials))]
    # ONE program over the four, once
    assert len(program.launches) == 1
    assert program.launches[0][0] == devices
    assert program.bases() == [_copy_base(0, k, LANES)
                               for k in range(LANES)]
    for name in ("pow.launch", "pow.fetch", "pow.harvest", "pow.groups"):
        assert len(TRACER.recent(50, name=name)) == 1, name
    (launch,) = TRACER.recent(5, name="pow.launch")
    assert launch.attrs["program"] == "ici_slab"
    assert launch.attrs["chunks"] == CHUNKS and launch.attrs["live"] == LANES
    assert (stats["mode"], stats["groups"], stats["devices"],
            stats["launches"]) == ("slab", 1, LANES, 1)
    grown = {name: _grown(name, was) for name, was in before.items()}
    assert grown["pow_pipeline_launches_total"] == {("slab",): 1}
    assert grown["pow_pipeline_device_launches_total"] \
        == {("%d" % k,): 1 for k in range(LANES)}
    assert not grown["pow_pipeline_abandoned_launches_total"]
    # every lane's row: the winner is the first step's first lane, and
    # the lane whose share holds the nonce
    (rows,) = program.rows
    hits = [k for k in range(LANES) if rows[k, sha512_ici.HIT]]
    win = min(hits, key=lambda k: int(rows[k, sha512_ici.HIT]))
    step1 = int(rows[win, sha512_ici.HIT])
    assert nonce // SHARE == win
    assert grown["pow_pipeline_lone_wins_total"] == {("%d" % win,): 1}
    outcomes = ["won" if k == win else WHY[int(rows[k, sha512_ici.WHY])]
                for k in range(LANES)]
    assert grown["pow_pipeline_lone_lanes_total"] \
        == {(o,): outcomes.count(o) for o in set(outcomes)}
    assert "ran_out" not in outcomes
    ran = [int(r[sha512_ici.STEPS]) for r in rows]
    cancelled = [k for k in range(LANES) if outcomes[k] == "cancelled"]
    assert all(ran[k] == min(step1 + lag, CHUNKS) for k in cancelled)
    total, count = _hist("pow_pipeline_lone_cancel_lag_steps")
    assert count - lag_before[1] == len(cancelled)
    assert total - lag_before[0] == sum(ran[k] - step1 for k in cancelled)
    # computed: what every lane ran, the losers too; needed: the winner
    # up to its nonce, the others up to the winner's step; credited to
    # the object: all of it
    executed = sum(ran) * STEP
    assert grown["pow_pipeline_executed_trials_total"] \
        == {("slab",): executed}
    assert trials == stats["credited_trials"] \
        == stats["executed_trials"] == executed
    needed = (nonce - _copy_base(0, win, LANES) + 1) + sum(
        min(ran[k], step1) * STEP for k in range(LANES) if k != win)
    assert grown["pow_pipeline_needed_trials_total"] \
        == {("slab",): needed}
    assert (step1 - 1) * STEP < nonce - _copy_base(0, win, LANES) + 1 \
        <= step1 * STEP
    assert needed <= executed


def test_two_lanes_that_hit_in_one_step_both_report_and_one_is_taken(
        devices, one_program, monkeypatch):
    program = Program(monkeypatch)
    item = _item("all at once", 50)      # every lane hits in its step 0
    lanes = _family("pow_pipeline_lone_lanes_total")
    wins = _family("pow_pipeline_lone_wins_total")
    lag = _hist("pow_pipeline_lone_cancel_lag_steps")
    (nonce, trials), _stats = _solve(item, devices)
    (rows,) = program.rows
    assert [int(r[sha512_ici.HIT]) for r in rows] == [1] * LANES
    assert [int(r[sha512_ici.WHY]) for r in rows] \
        == [sha512_ici.OWN_HIT] * LANES
    # the first lane's, hashlib-checked like any
    assert nonce == (int(rows[0, 1]) << 32) | int(rows[0, 2]) < STEP
    assert reference.trial_value(nonce.to_bytes(8, "big"), item[0]) \
        <= item[1]
    assert _grown("pow_pipeline_lone_lanes_total", lanes) \
        == {("won",): 1, ("own_hit",): LANES - 1}
    assert _grown("pow_pipeline_lone_wins_total", wins) == {("0",): 1}
    assert _hist("pow_pipeline_lone_cancel_lag_steps") == lag
    assert trials == LANES * STEP


def test_a_nonce_that_hashlib_refuses_is_not_published(
        devices, one_program, monkeypatch):
    def lying(operands, devices, rows, chunks, unroll, interpret):
        out = np.zeros((len(devices), sha512_ici.ROW_WORDS), np.uint32)
        out[:, sha512_ici.STEPS], out[:, sha512_ici.WHY] \
            = 1, sha512_ici.CANCELLED
        out[2] = (1, 0, 12345, 1, sha512_ici.OWN_HIT, 0, 0, 0)
        return out

    monkeypatch.setattr(sha512_ici, "ici_search", lying)
    with pytest.raises(ArithmeticError):
        _solve(_item("lying", 10 ** 12), devices)


# -- (3) the dispatcher's nonce, by the plain reference ------------------


@pytest.fixture
def four_chips(monkeypatch, devices, one_program):
    """The dispatcher told that it has four accelerator chips, the
    pipeline at a tile of 8 rows and a launch of eight steps, and the
    stand-in where the one program is."""
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: True)
    monkeypatch.setattr(PowDispatcher, "_device_count",
                        lambda self: LANES)
    for key, value in (("rows", ROWS), ("impl", "pallas")):
        monkeypatch.setitem(solve_batch_pipelined.__kwdefaults__,
                            key, value)
    monkeypatch.setattr(pipeline, "DEFAULT_CHUNKS", CHUNKS)

    def never(*_a, **_kw):
        raise AssertionError("a launch a lane, where the one program is")

    monkeypatch.setattr(sha512_pallas, "pallas_search", never)
    return Program(monkeypatch, lag=1)


@pytest.mark.parametrize("seed", range(12))
def test_the_dispatcher_s_nonce_through_the_one_program_is_one_the_reference_accepts(  # noqa: E501
        seed, four_chips):
    # some of these need a second launch
    ih, target = _item("seed %d" % seed, 10 ** 5)
    d = PowDispatcher(use_native=False)
    before = _family("pow_attempts_total")
    wins = _family("pow_pipeline_lone_wins_total")
    nonce, trials = d(ih, target)
    assert reference.trial_value(nonce.to_bytes(8, "big"), ih) <= target
    assert d.last_backend == "tpu-pallas"
    assert _grown("pow_attempts_total", before) == {("tpu-pallas",): 1}
    # a launch after a launch that every lane ran out of goes on where
    # each lane stopped
    for n in range(len(four_chips.launches)):
        assert four_chips.bases(n) == [
            (_copy_base(0, k, LANES) + n * SLAB) & MASK
            for k in range(LANES)]
    for rows in four_chips.rows[:-1]:
        assert [int(r[sha512_ici.WHY]) for r in rows] \
            == [sha512_ici.RAN_OUT] * LANES
    assert _grown("pow_pipeline_lone_wins_total", wins) \
        == {("%d" % (nonce // SHARE),): 1}
    assert trials == sum(int(r[sha512_ici.STEPS]) for rows in
                         four_chips.rows for r in rows) * STEP


# -- (4) the speculation rule: a hard object's next launch goes ahead ----


class Misses:
    """Stands where ``ici_search`` is and hashes nothing: every lane of
    every launch runs out until ``after`` launches have been
    dispatched; in the next, lane ``lane`` hits in its first step at its
    base and the others are cancelled there.  Keeps each launch's
    bases."""

    def __init__(self, after, monkeypatch, lane=1):
        self.after, self.lane, self.launches = after, lane, []
        monkeypatch.setattr(sha512_ici, "ici_search", self)
        monkeypatch.setattr(pipeline, "_checked_nonce",
                            lambda nonce, initial_hash, target: nonce)

    def __call__(self, operands, devices, rows, chunks, unroll, interpret):
        self.launches.append([(int(r[16]) << 32) | int(r[17])
                              for r in operands])
        out = np.zeros((len(devices), sha512_ici.ROW_WORDS), np.uint32)
        if len(self.launches) <= self.after:
            out[:, sha512_ici.STEPS] = chunks
            out[:, sha512_ici.WHY] = sha512_ici.RAN_OUT
            return out
        out[:, sha512_ici.STEPS] = 1
        out[:, sha512_ici.WHY] = sha512_ici.CANCELLED
        out[self.lane] = (1, operands[self.lane, 16],
                          operands[self.lane, 17], 1, sha512_ici.OWN_HIT,
                          0, 0, 0)
        return out


@pytest.mark.parametrize("expected, ahead", [(10 ** 7, False),
                                             (2 * 10 ** 10, True)])
def test_a_launch_goes_ahead_of_an_unread_one_only_for_an_object_it_is_unlikely_to_end(  # noqa: E501
        expected, ahead, devices, one_program, monkeypatch):
    """At the production geometry a launch of four lanes is 1.7e8
    trials: a network-default object (1e7) never has a second in
    flight, an object of 2e10 trials has."""
    kernel = Misses(3, monkeypatch)
    item = _item("rule", expected)
    plan = plan_batch([item], lanes=LANES, one_program=True)
    TRACER.clear()
    before = _family("pow_pipeline_speculation_total")
    abandoned = _family("pow_pipeline_abandoned_launches_total")
    lanes = _family("pow_pipeline_lone_lanes_total")
    stats = {}
    (result,) = solve_batch_pipelined(
        [item], impl="pallas", plan=plan, devices=devices, stats=stats,
        stall_timeout=30.0)
    slab = plan.chunks * sha512_pallas.DEFAULT_ROWS * 128 \
        * sha512_pallas.DEFAULT_UNROLL
    # the fourth launch's lane 1, at its base: every launch began where
    # the one before it ended, dispatched ahead or not
    assert result[0] == (_copy_base(0, 1, LANES) + 3 * slab) & MASK
    assert kernel.launches[:4] == [
        [(_copy_base(0, k, LANES) + n * slab) & MASK
         for k in range(LANES)] for n in range(4)]
    grown = _grown("pow_pipeline_speculation_total", before)
    launches = TRACER.recent(20, name="pow.launch")
    assert len(launches) == len(kernel.launches) == stats["launches"]
    assert any(s.attrs["speculative"] for s in launches) is ahead
    left = _grown("pow_pipeline_abandoned_launches_total", abandoned)
    read = _grown("pow_pipeline_lone_lanes_total", lanes)
    if ahead:
        assert grown.get(("slab", "launched"), 0) > 0
        # one more was in flight at the hit, and is nobody's
        assert len(kernel.launches) == 5 and left == {("slab",): 1}
    else:
        assert set(grown) == {("slab", "withheld")}
        assert len(kernel.launches) == 4 and not left
        events = sorted(
            [(s.start, "launch") for s in launches]
            + [(s.start, "harvest")
               for s in TRACER.recent(20, name="pow.harvest")])
        assert [what for _t, what in events] == ["launch", "harvest"] * 4
    # four launches read: three that ran out, the one that was won
    assert read == {("ran_out",): 3 * LANES, ("won",): 1,
                    ("cancelled",): LANES - 1}
    assert result[1] == (3 * plan.chunks + 1) * LANES \
        * (slab // plan.chunks)


# -- (5) the checkpoint is lane 0's; should_stop and the watchdog --------


def test_progress_is_lane_0_s_and_should_stop_reads_what_is_in_flight(
        devices, one_program, monkeypatch):
    kernel = Misses(10 ** 6, monkeypatch)
    item, start, seen = _item("stop", 10 ** 7), 777, []
    with pytest.raises(PowInterrupted):
        _solve(item, devices, start_nonces=[start],
               progress=lambda i, nxt: seen.append((i, nxt)),
               should_stop=lambda: len(seen) >= 2)
    # the own range's frontier, launch by launch, and nothing of a
    # share 2**62 away; what was in flight at the stop was read first
    assert [i for i, _n in seen] == [0] * len(seen)
    assert [n for _i, n in seen] \
        == [start + (m + 1) * SLAB for m in range(len(seen))]
    assert len(seen) == len(kernel.launches) >= 2
    assert kernel.launches[0] == [_copy_base(start, k, LANES)
                                  for k in range(LANES)]
    # resumed at the checkpoint, lane 0 goes on exactly there
    resumed = Misses(0, monkeypatch, lane=0)
    (nonce, _trials), _stats = _solve(item, devices,
                                      start_nonces=[seen[-1][1]])
    assert nonce == seen[-1][1]
    # (at this geometry the object is a hard one: a second launch is
    # dispatched ahead, and left unread)
    assert resumed.launches[0] == [_copy_base(seen[-1][1], k, LANES)
                                   for k in range(LANES)]


def test_an_answer_in_flight_at_the_stop_is_returned(devices, one_program,
                                                    monkeypatch):
    Misses(0, monkeypatch, lane=3)
    stops = iter([False, True, True])
    (nonce, _trials), _stats = _solve(_item("stop late", 10 ** 7), devices,
                                      should_stop=lambda: next(stops))
    assert nonce == _copy_base(0, 3, LANES)


class _NeverIn:
    """A launch's output that does not come in until ``release``."""

    def __init__(self, release):
        self.release = release

    def __array__(self, *_a, **_kw):
        self.release.wait(10)
        return np.zeros((LANES, sha512_ici.ROW_WORDS), np.uint32)


def test_a_launch_that_never_comes_in_trips_the_watchdog(
        devices, one_program, monkeypatch):
    """A chip that waits for a peer that never leaves is a fetch that
    never comes in: the solve raises under ``stall_timeout``, for the
    dispatcher to hand the object down."""
    release = threading.Event()
    monkeypatch.setattr(sha512_ici, "ici_search",
                        lambda *_a, **_kw: _NeverIn(release))
    stalls = REGISTRY.sample("pow_stall_total", {"site": "pow.slab"})
    t0 = time.monotonic()
    with pytest.raises(SlabStallError):
        solve_batch_pipelined(
            [_item("wedged", 10 ** 9)], rows=ROWS, impl="pallas",
            plan=_plan(), devices=devices, stall_timeout=0.3)
    release.set()
    assert time.monotonic() - t0 < 8
    assert REGISTRY.sample("pow_stall_total",
                           {"site": "pow.slab"}) == stalls + 1


# -- (6) the lane states, of every device --------------------------------


def test_every_device_is_in_the_one_lane_s_state(devices, one_program,
                                                 monkeypatch):
    Misses(2, monkeypatch)
    seconds = _family("pow_pipeline_lane_seconds_total")
    head = _heads()
    TRACER.clear()
    t0 = time.monotonic()
    # easy enough that no launch is dispatched ahead of an unread one
    _result, stats = _solve(_item("states", 10 ** 4), devices)
    wall = time.monotonic() - t0
    ids = sorted(d.id for d in devices)
    turns = TRACER.recent(200, name="pow.lane.turn")
    # the solve's start and each of three reads: a turn a device each
    assert sorted(s.attrs["device"] for s in turns) == sorted(ids * 4)
    assert {s.attrs["lane"] for s in turns} == {0}
    assert not TRACER.recent(200, name="pow.lane.starved")
    grown = _grown("pow_pipeline_lane_seconds_total", seconds)
    assert {k[0] for k in grown} == {"%d" % i for i in ids}
    by_device = {i: sum(v for k, v in grown.items() if k[0] == "%d" % i)
                 for i in ids}
    # a device's states add up to the solve's wall time, each device's
    assert len({round(v, 9) for v in by_device.values()}) == 1
    assert 0 < by_device[ids[0]] <= wall
    assert sum(by_device.values()) \
        == pytest.approx(LANES * stats["wall_seconds"], rel=1e-3)
    assert 0 < stats["device_busy_ratio"] <= 1
    # and the head is timed once, under the number of devices
    now = _heads()
    assert {k: n - head.get(k, 0) for k, n in now.items()
            if n != head.get(k, 0)} == {("%d" % LANES,): 1}
