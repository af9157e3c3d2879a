"""Async double-buffered PoW pipeline (ISSUE 2): packing, planning,
dispatch-ahead, autotuning, and the exported pipeline metrics.

Runs on the CPU mesh: the packed Mosaic kernel is exercised through its
XLA stand-in (``impl="xla"``), which shares the planner, the
dispatch-ahead driver, the winner contract and the metrics with the
device path — the same CI pattern as the sharded Pallas tier.
"""

import asyncio
import hashlib
import time

import pytest

from pybitmessage_tpu.ops.pow_search import PowInterrupted
from pybitmessage_tpu.pow.pipeline import (
    AUTOTUNER, BatchPlan, SlabAutotuner, expected_trials, plan_batch,
    pipeline_snapshot, solve_batch_pipelined)


def _host_trial(nonce: int, initial_hash: bytes) -> int:
    d = hashlib.sha512(hashlib.sha512(
        nonce.to_bytes(8, "big") + initial_hash).digest()).digest()
    return int.from_bytes(d[:8], "big")


def _items(n, target, tag=b"pipe"):
    return [(hashlib.sha512(tag + b" %d" % i).digest(), target)
            for i in range(n)]


# ---------------------------------------------------------------------------
# slab-size invariance (satellite): the winning nonce must not depend
# on slab geometry, including autotuned shapes
# ---------------------------------------------------------------------------


def test_pow_search_jit_slab_shape_invariance():
    from pybitmessage_tpu.ops.pow_search import pow_search_jit
    from pybitmessage_tpu.ops.sha512_jax import initial_hash_words
    from pybitmessage_tpu.ops.u64 import u64_from_int

    ih = hashlib.sha512(b"slab invariance").digest()
    target = 2 ** 57                       # mean ~128 trials
    ih_hi, ih_lo = initial_hash_words(ih)
    t_hi, t_lo = u64_from_int(target)
    tuner = SlabAutotuner(target_seconds=0.25)
    tuner.record("xla", 8, 0.2)            # pretend 25 ms/chunk
    shapes = [(256, 8), (512, 4),
              (256, tuner.suggest("xla", 8))]   # tuned -> (256, 8)
    winners = set()
    for start in (0, 5000):
        nonces = []
        for lanes, chunks in shapes:
            s_hi, s_lo = u64_from_int(start)
            found, n_hi, n_lo, _ = pow_search_jit(
                ih_hi, ih_lo, t_hi, t_lo, s_hi, s_lo, lanes, chunks)
            assert bool(found), (lanes, chunks)
            nonces.append((int(n_hi) << 32) | int(n_lo))
        assert len(set(nonces)) == 1, (
            "winning nonce varies with slab shape: %r" % nonces)
        winners.add(nonces[0])
        assert _host_trial(nonces[0], ih) <= target
    assert len(winners) == 2               # different starts, both real


@pytest.mark.slow
def test_solve_batch_pipelined_shape_invariance():
    """The pipelined solver must return the same nonces regardless of
    pack factor / chunk count (forced via explicit plans).  Slow-marked
    (two jit shape compiles); the tier-1 gate keeps the satellite
    pow_search_jit invariance test above."""
    items = _items(5, 2 ** 56, tag=b"invariant")
    # per-object lane shares 1024 and 512 at the same chunk count —
    # shapes shared with the other tests so jit compiles amortize
    plans = [BatchPlan("packed", 2, 4, list(range(5))),
             BatchPlan("packed", 4, 4, list(range(5)))]
    all_nonces = []
    for plan in plans:
        results = solve_batch_pipelined(items, rows=16, impl="xla",
                                        plan=plan)
        all_nonces.append([n for n, _ in results])
        for (ih, target), (nonce, trials) in zip(items, results):
            assert _host_trial(nonce, ih) <= target
            assert trials > 0
    assert all_nonces[0] == all_nonces[1]


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


def test_plan_packs_storm_and_keeps_hard_batches_whole():
    storm = _items(64, 2 ** 60)            # tiny: mean 16 trials
    plan = plan_batch(storm, rows=128)
    assert plan.mode == "packed"
    assert plan.pack == 16                 # max pack for tiny objects

    hard = _items(8, 2 ** 38)              # mean ~67M trials/object
    plan = plan_batch(hard, rows=128)
    assert plan.mode == "batched"
    assert plan.pack == 1


def test_plan_degenerate_single_tiny_object_is_sync():
    plan = plan_batch(_items(1, 2 ** 60), rows=128)
    assert plan.mode == "single-sync"


def test_plan_sorts_by_difficulty():
    items = [(hashlib.sha512(b"a").digest(), 2 ** 50),
             (hashlib.sha512(b"b").digest(), 2 ** 62),
             (hashlib.sha512(b"c").digest(), 2 ** 56)]
    plan = plan_batch(items, rows=128)
    exp = [expected_trials(t) for _, t in items]
    assert [exp[i] for i in plan.order] == sorted(exp)


# ---------------------------------------------------------------------------
# pipelined solving (XLA impl, CPU)
# ---------------------------------------------------------------------------


def test_pipelined_storm_solves_all_objects():
    items = _items(23, 2 ** 57, tag=b"storm")   # pads to uneven groups
    results = solve_batch_pipelined(
        items, rows=32, impl="xla",
        plan=BatchPlan("packed", 8, 4, list(range(23))))
    assert len(results) == 23
    for (ih, target), (nonce, trials) in zip(items, results):
        assert _host_trial(nonce, ih) <= target
        assert trials > 0


def test_pipelined_degenerate_single_falls_back_to_sync_path():
    """Acceptance: one tiny object must take the latency-optimal path
    (mode counter 'single-sync' increments; result still verifies)."""
    from pybitmessage_tpu.observability import REGISTRY

    before = REGISTRY.sample("pow_pipeline_mode_total",
                             {"mode": "single-sync"})
    items = _items(1, 2 ** 57, tag=b"degenerate")
    # plan_batch's choice for this input is asserted separately
    # (test_plan_degenerate_single_tiny_object_is_sync); pinning the
    # chunk count here keeps the jit shape ladder short
    assert plan_batch(items, rows=16).mode == "single-sync"
    [(nonce, trials)] = solve_batch_pipelined(
        items, rows=16, impl="xla",
        plan=BatchPlan("single-sync", 1, 4, [0]))
    assert _host_trial(nonce, items[0][0]) <= items[0][1]
    assert trials > 0
    after = REGISTRY.sample("pow_pipeline_mode_total",
                            {"mode": "single-sync"})
    assert after == before + 1


def test_pipelined_interrupt_raises():
    items = _items(8, 2 ** 30, tag=b"hardwall")  # unreachably hard
    calls = {"n": 0}

    def stop():
        calls["n"] += 1
        return calls["n"] > 3

    with pytest.raises(PowInterrupted):
        solve_batch_pipelined(
            items, rows=16, impl="xla",
            plan=BatchPlan("packed", 4, 4, list(range(8))),
            should_stop=stop)


def test_pipeline_metrics_exported():
    """Device-busy fraction, dispatch-ahead depth and pack occupancy
    must land in the registry and the Prometheus exposition."""
    from pybitmessage_tpu.observability import REGISTRY, render_prometheus

    items = _items(8, 2 ** 57, tag=b"metrics")
    solve_batch_pipelined(items, rows=16, impl="xla",
                          plan=BatchPlan("packed", 4, 4,
                                         list(range(8))))
    text = render_prometheus()
    for name in ("pow_pipeline_device_busy_ratio",
                 "pow_pipeline_depth",
                 "pow_pipeline_dispatch_ahead_size",
                 "pow_pack_size",
                 "pow_pack_occupancy_ratio",
                 "pow_pipeline_mode_total",
                 "pow_slab_seconds"):
        assert name in text, name
    assert REGISTRY.sample("pow_pipeline_device_busy_ratio") >= 0.0
    # pack occupancy of the last launch is a real fraction
    occ = REGISTRY.sample("pow_pack_occupancy_ratio")
    assert 0.0 < occ <= 1.0
    snap = pipeline_snapshot()
    assert set(snap) == {"deviceBusyRatio", "depth", "packOccupancy"}


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------


def test_a_run_that_ends_with_a_slab_in_flight_counts_it_abandoned():
    """``_PipelineDriver.run`` drops what is still in flight once every
    result is in; the program counts each such launch, and every
    launch, where it happens."""
    from pybitmessage_tpu.observability import REGISTRY
    from pybitmessage_tpu.pow.pipeline import _PipelineDriver

    def counted(name):
        return REGISTRY.sample(name, {"kind": "t_abandon"})

    launches0 = counted("pow_pipeline_launches_total")
    abandoned0 = counted("pow_pipeline_abandoned_launches_total")
    harvested = []
    slabs = iter(range(10))
    driver = _PipelineDriver(depth=2, fetch=lambda dev: dev,
                             kind="t_abandon")
    driver.run(lambda lane: (next(slabs),) * 2,
               lambda tag, host: harvested.append(host),
               done=lambda: bool(harvested))
    # two dispatched ahead, the first harvested and enough: the second
    # is left on the device unfetched
    assert harvested == [0]
    assert counted("pow_pipeline_launches_total") == launches0 + 2
    assert counted("pow_pipeline_abandoned_launches_total") \
        == abandoned0 + 1
    # the fetch's wait lies inside the lane's seconds with a launch in
    # flight (the second launch is still out when the run ends)
    assert driver.last_wait >= 0 \
        and driver.lane_seconds["inflight"] >= driver.last_wait

    # a run that needs every slab it dispatched abandons nothing
    budget = iter(range(3))
    driver = _PipelineDriver(depth=2, fetch=lambda dev: dev,
                             kind="t_abandon")
    driver.run(lambda lane: next(((b, b) for b in budget), None),
               lambda tag, host: harvested.append(host))
    assert harvested == [0, 0, 1, 2]
    assert counted("pow_pipeline_launches_total") == launches0 + 5
    assert counted("pow_pipeline_abandoned_launches_total") \
        == abandoned0 + 1


@pytest.mark.parametrize("stall_timeout", [0.0, 30.0])
def test_a_slow_device_does_not_hold_the_others_launches_back(
        stall_timeout):
    """Two devices: the launch on device 0 comes in only when device 1
    has had six launches read.  Each device's oldest launch is fetched
    on a thread of its own, so device 1 is harvested and launched again
    meanwhile, and never waits for device 0's launch to be read."""
    import threading

    from pybitmessage_tpu.pow.pipeline import _PipelineDriver

    release = threading.Event()
    launched, harvested = {0: 0, 1: 0}, []

    def fetch(dev):
        if dev[0] == 0:
            assert release.wait(20)
        return dev

    def next_launch(lane):
        if launched[lane] >= (1, 6)[lane]:
            return None
        launched[lane] += 1
        return "tag", (lane, launched[lane])

    def harvest(_tag, host):
        harvested.append(host)
        if host == (1, 6):
            release.set()

    driver = _PipelineDriver(depth=2, lanes=2, fetch=fetch,
                             stall_timeout=stall_timeout, kind="t_lanes")
    driver.run(next_launch, harvest)
    assert harvested == [(1, k) for k in range(1, 7)] + [(0, 1)]
    assert driver.slabs == 7


def test_a_wedged_device_is_a_stall_though_the_others_come_in():
    """The watchdog's deadline is each fetch's own: a launch that is
    not in ``stall_timeout`` seconds after its fetch began raises,
    whatever the other devices have delivered meanwhile."""
    import threading

    from pybitmessage_tpu.pow.pipeline import _PipelineDriver
    from pybitmessage_tpu.resilience.watchdog import SlabStallError

    never = threading.Event()
    harvested = []

    def fetch(dev):
        if dev[0] == 0:
            never.wait(5)
        else:
            time.sleep(0.02)
        return dev

    count = [0]

    def next_launch(lane):
        count[0] += 1
        return "tag", (lane, count[0])

    driver = _PipelineDriver(depth=1, lanes=2, fetch=fetch,
                             stall_timeout=0.3, kind="t_lanes")
    t0 = time.monotonic()
    with pytest.raises(SlabStallError):
        driver.run(next_launch, lambda _tag, host: harvested.append(host))
    never.set()
    assert time.monotonic() - t0 < 3
    assert harvested and all(host[0] == 1 for host in harvested)


def test_the_device_with_least_in_flight_and_least_to_do_is_asked_first():
    """Four devices, ``depth`` 2.  A turn asks breadth first: every
    device gets its first launch before any gets its second, so one
    that has run out is asked before the others are topped up; among
    devices with as many launches in flight the one whose ``load`` is
    least is asked first, so what has arrived since goes to the chip
    that needs it (REVIEW of PR 37; in index order an arrival went to
    the chip read last and a chip that had run out was asked last)."""
    from pybitmessage_tpu.pow.pipeline import _PipelineDriver

    load = {0: 40, 1: 10, 2: 30, 3: 20}
    asked, budget = [], [10]

    def next_launch(lane):
        asked.append(lane)
        if not budget[0]:
            return None
        budget[0] -= 1
        return "tag", (lane, len(asked))

    driver = _PipelineDriver(depth=2, lanes=4, fetch=lambda dev: dev,
                             kind="t_lanes")
    harvested = []
    driver.run(next_launch, lambda _tag, host: harvested.append(host),
               load=load.get)
    # the first turn: all four once by load, then all four again
    assert asked[:8] == [1, 3, 2, 0, 1, 3, 2, 0]
    # the oldest launch, device 1's, is read: it alone has room
    assert harvested[0] == (1, 1) and asked[8] == 1
    assert driver.slabs == 10


@pytest.mark.parametrize("stall_timeout", [0.0, 30.0])
def test_one_device_is_fetched_as_it_always_was(stall_timeout):
    """One device: in place with the watchdog off, else one guard
    worker and ``Future.result(stall_timeout)`` — no table of fetches
    in progress, no ``concurrent.futures.wait`` (the parent's path,
    REVIEW of PR 37)."""
    import threading

    from pybitmessage_tpu.pow.pipeline import _PipelineDriver

    threads, count = set(), [0]

    def fetch(dev):
        threads.add(threading.current_thread().name)
        return dev

    def next_launch(lane):
        assert lane == 0
        count[0] += 1
        return ("tag", count[0]) if count[0] <= 5 else None

    driver = _PipelineDriver(depth=2, fetch=fetch,
                             stall_timeout=stall_timeout, kind="t_lanes")
    class NoFetchTable(dict):
        def __setitem__(self, key, value):
            raise AssertionError("a fetch in progress was tabled")

    driver._fetching = NoFetchTable()
    harvested = []
    driver.run(next_launch, lambda _tag, host: harvested.append(host))
    assert harvested == [1, 2, 3, 4, 5]
    if stall_timeout:
        assert len(threads) == 1 and "slab-guard" in threads.pop()
    else:
        assert threads == {threading.current_thread().name}


def test_pipelined_solve_counts_launches_and_executed_trials():
    from pybitmessage_tpu.observability import REGISTRY, TRACER

    def total(name):
        fam = REGISTRY.get(name)
        return sum(child.value for _v, child in fam.children())

    launches0 = total("pow_pipeline_launches_total")
    trials0 = total("pow_pipeline_executed_trials_total")
    TRACER.clear()
    stats = {}
    items = _items(5, 2 ** 64 // 3000, tag=b"counted")
    results = solve_batch_pipelined(items, impl="xla", rows=8, stats=stats)
    assert all(r is not None for r in results)
    assert total("pow_pipeline_launches_total") - launches0 \
        == stats["launches"]
    assert total("pow_pipeline_executed_trials_total") - trials0 \
        == stats["executed_trials"] > 0
    names = [s.name for s in TRACER.recent(500)]
    for name in ("pow.plan", "pow.groups", "pow.launch", "pow.fetch",
                 "pow.harvest"):
        assert name in names, name
    assert names.count("pow.launch") == stats["launches"]
    launch = TRACER.recent(500, name="pow.launch")[-1]
    assert set(launch.attrs) >= {"program", "chunks", "live"}


def test_autotuner_targets_poll_interval():
    t = SlabAutotuner(target_seconds=0.5, min_chunks=4, max_chunks=2048)
    assert t.suggest("k", 64) == 64        # no data -> default
    t.record("k", 64, 6.4)                 # 100 ms/chunk
    assert t.suggest("k", 64) == 4         # 0.5s/0.1 = 5 -> pow2 4
    t2 = SlabAutotuner(target_seconds=0.5)
    t2.record("k", 64, 0.0064)             # 0.1 ms/chunk
    assert t2.suggest("k", 64) == 2048     # clamped at max
    # EWMA: one outlier decays instead of sticking
    t3 = SlabAutotuner(target_seconds=0.5, alpha=0.4)
    for _ in range(20):
        t3.record("k", 64, 0.64)           # steady 10 ms/chunk
    t3.record("k", 64, 64.0)               # one relay stall
    for _ in range(20):
        t3.record("k", 64, 0.64)
    assert t3.suggest("k", 64) in (32, 64)


def test_autotuner_thread_safety():
    import threading

    t = SlabAutotuner()

    def hammer():
        for i in range(500):
            t.record("k", 8, 0.1)
            t.suggest("k", 8)

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert t.seconds_per_chunk("k") == pytest.approx(0.1 / 8)


# ---------------------------------------------------------------------------
# service integration: registry is the single source of truth
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_service_counters_read_from_registry():
    from pybitmessage_tpu.observability import REGISTRY
    from pybitmessage_tpu.pow.service import PowService

    class FakeDispatcher:
        last_backend = "fake"

        def solve_batch(self, items, should_stop=None):
            return [(1, 1)] * len(items)

    svc = PowService(FakeDispatcher(), window=0.01)
    svc.start()
    try:
        await asyncio.gather(*(svc.solve(b"\x00" * 64, 2 ** 60)
                               for _ in range(3)))
        assert svc.batches == 1
        assert svc.solved == 3
        # the same numbers must be visible registry-side
        assert REGISTRY.sample("pow_batches_total") >= 1
        assert REGISTRY.sample("pow_solved_total") >= 3
    finally:
        await svc.stop()


def test_service_window_configurable():
    # load core/config.py standalone: the core package __init__ pulls
    # in optional deps (cryptography) absent from the CI image
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent
            / "pybitmessage_tpu" / "core" / "config.py")
    spec = importlib.util.spec_from_file_location("_pybm_config", path)
    cfg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cfg)
    Settings, SettingsError = cfg.Settings, cfg.SettingsError

    s = Settings()
    assert s.getfloat("powbatchwindow") == 0.05
    s.set("powbatchwindow", "0.2")
    assert s.getfloat("powbatchwindow") == 0.2
    with pytest.raises(SettingsError):
        s.set("powbatchwindow", "-1")
    with pytest.raises(SettingsError):
        s.set("powbatchwindow", "not-a-float")
