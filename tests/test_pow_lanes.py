"""The pipeline driver's lane states (``pow/pipeline.py`` ``_Lane``) and
the interval primitive under them (``observability.interval``).

While ``_PipelineDriver.run`` is under way every lane is ``inflight``,
in its ``turn`` or ``starved``; the last two are intervals in the ring
(and in a profiler trace) that carry the device's id, and every
state's seconds go to ``pow_pipeline_lane_seconds_total``.  Held here:
the three states' seconds are ``lanes`` times the run's wall time
whatever ends the run, ``starved`` opens only on a lane with nothing
live and closes at the refill's launch, nothing stays open, an
interval is nobody's parent, and the busy ratio is the lanes' and not
the driver thread's.
"""

import threading
import time

import pytest

from pybitmessage_tpu.observability import (REGISTRY, TRACER, current_span,
                                            interval, set_batch, trace)
from pybitmessage_tpu.pow.pipeline import (LANE_STATES, PowInterrupted,
                                           _PipelineDriver,
                                           pipeline_snapshot,
                                           solve_batch_pipelined)
from pybitmessage_tpu.resilience.watchdog import SlabStallError

LANE_SPANS = ("pow.lane.turn", "pow.lane.starved")


def _lane_seconds() -> dict:
    """``(device, state) -> seconds`` of the counter, as it stands."""
    fam = REGISTRY.get("pow_pipeline_lane_seconds_total")
    return {values: child.value for values, child in fam.children()}


def _grown(before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in _lane_seconds().items()
            if v != before.get(k, 0.0)}


def _lane_spans():
    return [s for s in TRACER.recent(2048) if s.name in LANE_SPANS]


def _scripted(budget: dict, fetch_s: float = 0.004):
    """``next_launch`` that launches ``budget[lane]`` times a lane, and
    a ``fetch`` that takes ``fetch_s`` seconds."""
    left = dict(budget)

    def next_launch(lane):
        if not left.get(lane):
            return None
        left[lane] -= 1
        return "tag", (lane, left[lane])

    def fetch(dev):
        time.sleep(fetch_s)
        return dev
    return next_launch, fetch


def _all_closed(driver) -> None:
    assert len(driver._lanes) == driver.lanes
    for ln in driver._lanes:
        assert ln.state is None and ln._interval is None
    assert all(s.duration is not None for s in _lane_spans())
    assert sum(driver.lane_seconds.values()) == pytest.approx(
        driver.lanes * driver.wall_seconds, abs=1e-3)


# -- the interval primitive ---------------------------------------------


def test_intervals_overlap_and_close_out_of_order_and_are_no_parent():
    TRACER.clear()
    with trace("t.outer") as outer:
        a = interval("t.state", device=2, lane=2)
        b = interval("t.state", device=3, lane=3)
        a.open()
        assert current_span() is outer
        b.open()
        with trace("t.step") as step:
            assert current_span() is step
        assert step.parent_id == outer.span_id
        a.close()           # opened first, closed first: not a stack
        assert current_span() is outer
        b.close()
        assert current_span() is outer
    assert current_span() is None
    states = TRACER.recent(10, name="t.state")
    assert [s.attrs["device"] for s in states] == [2, 3]
    assert all(s.parent_id == outer.span_id for s in states)
    assert states[0].start <= states[1].start <= states[0].end \
        <= states[1].end


def test_an_interval_carries_the_batch_and_is_mirrored_with_its_stats(
        monkeypatch):
    from pybitmessage_tpu.observability import tracing

    seen = []

    class Annotation:
        def __init__(self, name, **stats):
            seen.append(("open", name, stats))

        def __exit__(self, *exc):
            seen.append(("close",))

    monkeypatch.setattr(tracing, "_annotation", Annotation)
    TRACER.clear()
    set_batch(7)
    try:
        state = interval("t.mirrored", device=1, live=0)
        span = state.open()
        assert seen == [("open", "t.mirrored",
                         {"device": 1, "live": 0, "batch": 7})]
        assert state.close() is span and span.duration >= 0
    finally:
        set_batch(None)
    assert seen[-1] == ("close",)
    assert TRACER.recent(1)[0] is span and span.attrs["batch"] == 7


def test_the_tracer_never_imports_jax_for_an_interval(monkeypatch):
    import sys

    from pybitmessage_tpu.observability import tracing
    monkeypatch.setattr(tracing, "_annotation", None)
    monkeypatch.delitem(sys.modules, "jax.profiler", raising=False)
    state = interval("t.nojax", device=0)
    state.open()
    state.close()
    assert "jax.profiler" not in sys.modules
    assert state.span.duration is not None


# -- the lanes' states --------------------------------------------------


@pytest.mark.parametrize("lanes,budget", [
    (1, {0: 6}),
    (4, {0: 5, 1: 1, 2: 3, 3: 0}),
])
def test_the_three_states_add_up_to_lanes_times_wall(lanes, budget):
    before = _lane_seconds()
    TRACER.clear()
    next_launch, fetch = _scripted(budget)
    driver = _PipelineDriver(depth=2, lanes=lanes, fetch=fetch,
                             kind="t_lane_sum",
                             devices=[70 + k for k in range(lanes)])
    harvested = []
    driver.run(next_launch, lambda _t, host: harvested.append(host))
    assert len(harvested) == sum(budget.values())
    _all_closed(driver)
    grown = _grown(before)
    assert {dev for dev, _state in grown} \
        == {"%d" % (70 + k) for k in range(lanes)}
    assert {state for _dev, state in grown} <= set(LANE_STATES)
    # what the counter grew by is what the driver says of its run
    for state in LANE_STATES:
        assert sum(v for (_d, s), v in grown.items() if s == state) \
            == pytest.approx(driver.lane_seconds[state], abs=1e-9)
    assert sum(grown.values()) == pytest.approx(
        lanes * driver.wall_seconds, abs=1e-3)
    # each lane alone is in one state at a time, all of the run
    for k in range(lanes):
        assert sum(v for (d, _s), v in grown.items()
                   if d == "%d" % (70 + k)) \
            == pytest.approx(driver.wall_seconds, abs=1e-3)
    # a lane that never launched was starved from its first turn on
    if lanes == 4:
        assert grown[("73", "starved")] > 0.8 * driver.wall_seconds
        assert ("73", "inflight") not in grown
    # the intervals in the ring are the counter's turn and starved
    # seconds (the ring's clock reads are the counter's)
    for name, state in zip(LANE_SPANS, ("turn", "starved")):
        assert sum(s.duration for s in _lane_spans() if s.name == name) \
            == pytest.approx(driver.lane_seconds[state], abs=1e-3)


def test_starved_opens_with_nothing_live_and_closes_at_the_refill():
    """Lane 1 has nothing until lane 0's third launch is in; then an
    arrival is dealt to it.  Its ``starved`` interval opens with 0 live
    slots and ends at that launch; lane 0, which always has something
    live, is never starved while it has."""
    TRACER.clear()
    live = {0: 3, 1: 0}
    harvested, launched_at = [], {}
    left = [6]

    def next_launch(lane):
        if lane == 1:
            if len(harvested) < 3 or (1, 0) in launched_at:
                return None
            live[1] = 1
            launched_at[(1, 0)] = time.monotonic()
            return "tag", (1, 0)
        if not left[0]:
            live[0] = 0
            return None
        left[0] -= 1
        return "tag", (0, left[0])

    def harvest(_tag, host):
        harvested.append(host)
        if host == (1, 0):
            live[1] = 0

    def fetch(dev):
        time.sleep(0.003)
        return dev

    driver = _PipelineDriver(depth=1, lanes=2, fetch=fetch,
                             kind="t_lane_refill", devices=[5, 9])
    driver.run(next_launch, harvest, load=live.get)
    assert (1, 0) in harvested and len(harvested) == 7
    _all_closed(driver)
    spans = _lane_spans()
    assert {s.attrs["device"] for s in spans} == {5, 9}
    assert all(s.attrs["lane"] == {5: 0, 9: 1}[s.attrs["device"]]
               for s in spans)
    starved = [s for s in spans if s.name == "pow.lane.starved"]
    assert starved and all(s.attrs["live"] == 0 for s in starved)
    first = min((s for s in starved if s.attrs["device"] == 9),
                key=lambda s: s.start)
    # open from the first turn that found nothing to the refill's
    # launch, not a clock read later than the launch's return
    assert first.end >= launched_at[(1, 0)]
    assert first.end - launched_at[(1, 0)] < 0.05
    turns = [s for s in spans if s.name == "pow.lane.turn"
             and s.attrs["device"] == 9]
    # run()'s start, and the read that emptied the lane's queue
    assert len(turns) == 2 and turns[0].end == pytest.approx(
        first.start, abs=1e-3)
    # lane 0 starves only at the end, when its budget is spent
    for s in starved:
        if s.attrs["device"] == 5:
            assert s.start >= launched_at[(1, 0)]


def _ends_by_done(driver, fetch):
    harvested = []
    next_launch, _ = _scripted({0: 9, 1: 9, 2: 9, 3: 9})
    driver.run(next_launch, lambda _t, host: harvested.append(host),
               done=lambda: len(harvested) >= 3)


def _ends_by_should_stop(driver, fetch):
    harvested = []
    next_launch, _ = _scripted({0: 9, 1: 9, 2: 9, 3: 9})
    driver.should_stop = lambda: len(harvested) >= 2
    with pytest.raises(PowInterrupted):
        driver.run(next_launch, lambda _t, host: harvested.append(host))
    # what was in flight was drained first
    assert len(harvested) > 2


def _ends_by_a_stall(driver, fetch):
    next_launch, _ = _scripted({0: 9, 1: 9, 2: 9, 3: 9})
    driver.stall_timeout = 0.2
    fetch.wedge.clear()
    try:
        with pytest.raises(SlabStallError):
            driver.run(next_launch, lambda _t, _host: None)
    finally:
        fetch.wedge.set()


def _ends_by_an_exception_from_harvest(driver, fetch):
    next_launch, _ = _scripted({0: 9, 1: 9, 2: 9, 3: 9})
    seen = []

    def harvest(_tag, host):
        seen.append(host)
        if len(seen) == 3:
            raise RuntimeError("harvest failed")

    with pytest.raises(RuntimeError, match="harvest failed"):
        driver.run(next_launch, harvest)


@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("ending", [
    _ends_by_done, _ends_by_should_stop, _ends_by_a_stall,
    _ends_by_an_exception_from_harvest], ids=lambda f: f.__name__[6:])
def test_every_interval_is_closed_however_run_leaves(ending, lanes):
    TRACER.clear()
    before = _lane_seconds()

    def fetch(dev):
        assert fetch.wedge.wait(10)
        time.sleep(0.002)
        return dev
    fetch.wedge = threading.Event()
    fetch.wedge.set()
    driver = _PipelineDriver(depth=2, lanes=lanes, fetch=fetch,
                             kind="t_lane_end",
                             devices=[80 + k for k in range(lanes)])
    ending(driver, fetch)
    _all_closed(driver)
    assert sum(_grown(before).values()) == pytest.approx(
        lanes * driver.wall_seconds, abs=1e-3)
    # a second run of the same driver starts from clean lanes
    next_launch, _ = _scripted({0: 1})
    driver.should_stop, driver.stall_timeout = None, 0.0
    driver.run(next_launch, lambda _t, _host: None)
    _all_closed(driver)


def test_a_launch_under_an_open_lane_interval_is_the_solves_child():
    TRACER.clear()
    left = [3]

    def next_launch(lane):
        if not left[0]:
            return None
        left[0] -= 1
        with trace("pow.launch", device=lane):
            pass
        return "tag", left[0]

    driver = _PipelineDriver(depth=1, fetch=lambda dev: dev,
                             kind="t_lane_parent")
    with trace("pow.solve_batch") as solve:
        driver.run(next_launch, lambda _t, _host: None)
        assert current_span() is solve
    assert current_span() is None
    launches = TRACER.recent(20, name="pow.launch")
    assert len(launches) == 3
    turns = TRACER.recent(20, name="pow.lane.turn")
    # every launch began inside the lane's open turn...
    for launch in launches:
        assert any(t.start <= launch.start and launch.end <= t.end
                   for t in turns)
        # ...and is the solve's child, as the intervals are
        assert launch.parent_id == solve.span_id
    for span in _lane_spans():
        assert span.parent_id == solve.span_id
    assert TRACER.recent(20, name="pow.fetch")[0].parent_id \
        == solve.span_id


def test_the_busy_ratio_is_the_lanes_and_not_the_drivers_thread():
    """Four lanes, three with nothing to do: the driver's thread is
    blocked in a fetch nearly all of the time (the old gauge read 1.0),
    a quarter of the lanes have a launch in flight."""
    next_launch, fetch = _scripted({0: 8}, fetch_s=0.02)
    driver = _PipelineDriver(depth=1, lanes=4, fetch=fetch,
                             kind="t_lane_busy")
    driver.run(next_launch, lambda _t, _host: None)
    _all_closed(driver)
    assert driver.lane_seconds["inflight"] >= 8 * 0.02
    assert driver.lane_seconds["starved"] > 2.5 * driver.wall_seconds
    assert 0.15 < driver.busy_ratio <= 0.25 + 1e-6
    assert REGISTRY.sample("pow_pipeline_device_busy_ratio") \
        == pytest.approx(driver.busy_ratio)
    assert pipeline_snapshot()["deviceBusyRatio"] == pytest.approx(
        driver.busy_ratio, abs=1e-4)
    assert not hasattr(driver, "wait_seconds")


@pytest.mark.parametrize("ndev", [1, 4])
def test_a_pipelined_solve_names_its_lanes_by_the_devices_ids(ndev):
    import hashlib

    import jax
    devices = jax.devices()[4:4 + ndev] if ndev > 1 else None
    ids = [d.id for d in devices] if devices else [0]
    before = _lane_seconds()
    TRACER.clear()
    items = [(hashlib.sha512(b"lane %d" % i).digest(), 2 ** 64 // 2000)
             for i in range(6)]
    stats = {}
    results = solve_batch_pipelined(items, impl="xla", rows=8, stats=stats,
                                    devices=devices)
    assert all(r is not None for r in results)
    grown = _grown(before)
    assert {dev for dev, _s in grown} == {"%d" % i for i in ids}
    assert sum(grown.values()) == pytest.approx(
        ndev * stats["wall_seconds"], abs=1e-3)
    assert 0.0 < stats["device_busy_ratio"] <= 1.0
    inflight = sum(v for (_d, s), v in grown.items() if s == "inflight")
    assert stats["device_busy_ratio"] == pytest.approx(
        inflight / (ndev * stats["wall_seconds"]), rel=1e-6)
    spans = _lane_spans()
    assert {s.attrs["device"] for s in spans} == set(ids)
    # a lane is starved only when every slot of its groups is done
    assert all(s.attrs["live"] == 0 for s in spans
               if s.name == "pow.lane.starved")
