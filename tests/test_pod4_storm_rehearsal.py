"""The cell ``pod4_storm_10k`` (ISSUE 46) rehearsed on the CPU, beside
``tests/test_pod4_rehearsal.py`` and for its reason outside
``tests/benchmarks``: it compiles for four devices.  A scratch copy of
the benchmark holds the cell's configuration at test difficulty and a
backlog of 24 broadcasts; the dispatcher is told that it has FOUR
accelerator chips (four of the suite's virtual devices) and XLA
programs stand where the kernels are.  The outbox streams through the
pipeline placed over the four: every published nonce is held to
``benchmarks/reference.py``, every launch group has a device of its
own, and the broadcasts still outstanding when the window closes are
nobody's loss.

A solve that is fed outlives the profiler's session, which records
only what begins and ends inside it: the trace holds no solve span, and
the cell's three lane readers take the window to lie inside the solve
(``benchmarks/layers/_lanes_fed.py``); that is held here too.

Beside it, unit tests of the four series the cell makes matter:
``sender_admit_seconds`` / ``sender_admit_rows_total`` (one observation
a ``_sweep`` that reads the sent table) and
``cryptopool_busy_seconds_total`` / ``cryptopool_queue_wait_seconds``
(a job's length and its wait for the pool's thread).

The entry, the configuration and the readers are held in
``tests/benchmarks/test_pod4_storm_10k.py``.
"""

import asyncio
import json
import pathlib
import shutil
import sys
import time
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
for _path in (REPO, REPO / "tests" / "benchmarks", REPO / "tests"):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from benchmarks import harness, reference  # noqa: E402
from test_pod4_rehearsal import four_chips  # noqa: E402,F401  (fixtures)
from test_queue_1k import one_chip  # noqa: E402,F401

CELL = "pod4_storm_10k"
REHEARSAL = "rehearse_storm4"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the cell's configuration at test
    difficulty and a short backlog of broadcasts, added as new files."""
    root = tmp_path_factory.mktemp("storm4_tree")
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load(REPO, CELL)
    bdir = root / "benchmarks"
    cfg = dict(bench.config, name=REHEARSAL + "_cfg", test_mode=True,
               ntpb=10, extra=10)
    (bdir / "configs" / (REHEARSAL + "_cfg.json")).write_text(
        json.dumps(cfg))
    (bdir / "traffic" / (REHEARSAL + "_mix.json")).write_text(json.dumps(
        dict(bench.traffic, backlog=24, report=6,
             body_bytes=[[1.0, 40, 120]])))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": REHEARSAL + "_cfg", "source": "test", "reduced": [],
        "file": "benchmarks/configs/%s_cfg.json" % REHEARSAL,
        "why": "test"})
    spec["workloads"].append(dict(bench.cell, name=REHEARSAL,
                                  config=REHEARSAL + "_cfg",
                                  traffic=REHEARSAL + "_mix"))
    for metric in spec["per_layer"]:
        if metric.get("workloads") == [CELL]:
            metric["workloads"] = [CELL, REHEARSAL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_the_storm_streams_over_four_devices_and_is_correct(
        tree, four_chips, monkeypatch):  # noqa: F811
    from benchmarks import check
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    from pybitmessage_tpu.observability import TRACER
    setup_jax()
    TRACER.clear()
    # every object the check holds to the reference, kept for a second
    # look here
    held = []
    verify = check.verify

    def keeping(dep, sent, objects, counters, now=None):
        now = time.time() if now is None else now
        held.append((dict(objects), dep.config, now))
        return verify(dep, sent, objects, counters, now)

    monkeypatch.setattr(check, "verify", keeping)
    lines = []
    bench = harness.load(tree, REHEARSAL)
    assert (bench.traffic["generator"], bench.traffic["send"],
            bench.traffic["backlog"]) == ("backlog", "broadcast", 24)
    result = asyncio.run(harness.run_cell(
        bench, 2**31 + 46, 1.0, True, lines.append,
        t_start=time.monotonic()))
    assert result["correct"] is True, lines
    window = result["window"]
    # what the window counts is what ended in it; the two dozen still
    # queued or searching when it closed are neither sent nor failed
    assert result["failed"] == 0
    assert result["attempted"] == len(window.sent) >= 6
    assert all(s.t_done is not None for s in window.sent)
    verdict = window.verdict
    assert {k: v["value"] for k, v in verdict["compared"].items()} \
        == {"invalid_nonces": 0, "undelivered": 0, "off_tier": 0}
    assert verdict["missing_objects"] == 0
    assert verdict["objects"] >= len(window.sent)
    # every published nonce, one by one, by the plain reference
    # (hashlib's double SHA-512) at the rehearsal's difficulty
    (objects, cfg, now), = held
    assert len(objects) == verdict["objects"]
    for payload in objects.values():
        assert reference.object_ok(payload, cfg["ntpb"], cfg["extra"],
                                   now), payload[:8].hex()
    assert verdict["worst_value_over_target"] <= 1.0
    # the queue on the pipeline; an object left alone is the same rung's
    assert set(verdict["attempts_by_backend"]) <= {
        "tpu-pallas-batch", "tpu-pallas"}
    assert "tpu-pallas-batch" in verdict["attempts_by_backend"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    storm4 = {m["name"] for m in bench.metrics("per_layer")
              if m["name"].endswith(".storm4")}
    assert len(storm4) == 16
    # every new metric but the two that need a device's planes
    assert storm4 - set(metrics) == {"kernel_mhash_per_s.storm4",
                                     "chip_busy_share_min.storm4"}
    assert metrics["off_device_solves"] == 0
    if "tpu-pallas" not in verdict["attempts_by_backend"]:
        assert metrics["compiles_in_window"] == 0
        assert metrics["program_lowerings_in_window.storm4"] == 0
    assert 0 < metrics["chip_launch_share_max.storm4"] < 100
    assert 0 < metrics["live_slot_share.storm4"] <= 100
    assert 0 < metrics["useful_trial_share.storm4"] <= 100
    # one proof of work a broadcast, each through a freed slot or the
    # solve's start
    assert 0 < metrics["slot_refills_per_msg.storm4"]
    assert metrics["speculated_launch_share.storm4"] >= 0
    assert metrics["pow_wait_ms.storm4"] > 0
    assert metrics["pipeline_host_ms_per_launch.storm4"] > 0
    assert metrics["sender_host_ms_per_msg.storm4"] > 0
    # ONE solve outlives the window, so the trace has no span of it
    # and ``lanereduce`` calls all idle "between solves"; the cell's
    # readers take the window to lie inside the solve: the devices
    # (which run nothing here) idle all of it, and what neither lane
    # interval covers had a launch in flight
    assert len(TRACER.recent(50, name="pow.solve_batch")) <= 1
    lanes = window.notes["lane_reduction"]
    assert lanes["idle_between_solves_s"] == pytest.approx(lanes["idle_s"])
    shares = [metrics["lane_%s_idle_share.storm4" % state]
              for state in ("inflight", "turn", "starved")]
    assert all(share >= 0 for share in shares), shares
    assert sum(shares) == pytest.approx(metrics["device_idle_share"])
    assert shares[0] > 50
    # the two readings the cell is there for: the admission passes cost
    # something a broadcast, the one crypto thread worked part of the
    # window and no more than all of it
    assert metrics["sender_admit_ms_per_msg.storm4"] > 0
    assert 0 < metrics["crypto_pool_busy_share.storm4"] <= 100
    counters = window.counters
    _admit_s, passes = counters.hist("sender_admit_seconds")
    rows = counters.delta("sender_admit_rows_total")
    assert passes > 0 and set(rows) == {("broadcast",)}
    _wait_s, jobs = counters.hist("cryptopool_queue_wait_seconds")
    assert jobs >= len(window.sent)
    # every solve was laid out over the four, every launch group has a
    # device of its own, every launch of the window is counted on one
    # of them, and every one of them launched
    groups = TRACER.recent(50, name="pow.groups")
    assert groups and all(s.attrs["devices"] == 4 for s in groups)
    grown = counters.delta("pow_pipeline_device_launches_total")
    assert set(grown) == {("0",), ("1",), ("2",), ("3",)}, grown
    assert sum(grown.values()) \
        == counters.delta("pow_pipeline_launches_total")[("batch",)]
    launches = TRACER.recent(10000, name="pow.launch")
    assert {s.attrs["device"] for s in launches} == {0, 1, 2, 3}


# -- the sender's admission pass ----------------------------------------


class _Outbox:
    """A sent table of ``n`` queued rows that counts its reads."""

    def __init__(self, n: int):
        self.rows = [types.SimpleNamespace(
            ackdata=b"row-%d" % i, fromaddress="a", toaddress="b")
            for i in range(n)]
        self.reads = []

    def sent_by_status(self, *statuses, limit=None):
        self.reads.append((statuses, limit))
        return self.rows[:limit]


def _worker(store):
    from pybitmessage_tpu.workers.sender import SendWorker
    return SendWorker(keystore=None, store=store, inventory=None,
                      pool=None, solver=None)


def _admitted() -> tuple[int, float]:
    """(passes observed, rows read) so far, for broadcasts."""
    from pybitmessage_tpu.observability import REGISTRY
    labels = {"kind": "broadcast"}
    return (REGISTRY.sample("sender_admit_seconds", labels),
            REGISTRY.sample("sender_admit_rows_total", labels))


@pytest.mark.asyncio
async def test_a_sweep_that_reads_the_table_is_observed_once():
    from pybitmessage_tpu.workers import sender
    store = _Outbox(5)
    worker = _worker(store)
    sent = []

    async def send_one(m):
        sent.append(m.ackdata)

    passes0, rows0 = _admitted()
    total0 = sender.ADMIT_SECONDS.labels(kind="broadcast").snapshot()[1]
    await worker._sweep("broadcast", send_one)
    passes1, rows1 = _admitted()
    assert len(sent) == 5 and len(store.reads) == 1
    assert store.reads[0][1] == sender.MAX_IN_FLIGHT + 1
    assert passes1 - passes0 == 1
    assert rows1 - rows0 == 5
    total1 = sender.ADMIT_SECONDS.labels(kind="broadcast").snapshot()[1]
    assert 0 < total1 - total0 < 1.0
    # the messages' series are their own
    from pybitmessage_tpu.observability import REGISTRY
    messages0 = REGISTRY.sample("sender_admit_seconds",
                                {"kind": "message"})
    await worker._sweep("broadcast", send_one)
    assert REGISTRY.sample("sender_admit_seconds",
                           {"kind": "message"}) == messages0
    assert _admitted()[0] - passes1 == 1


@pytest.mark.asyncio
async def test_a_pass_that_admits_nothing_is_observed_all_the_same():
    """Every row read is in flight already: the pass was paid for."""
    store = _Outbox(3)
    worker = _worker(store)
    worker._in_flight.update(m.ackdata for m in store.rows)

    async def never(m):
        raise AssertionError("nothing was there to admit")

    passes0, rows0 = _admitted()
    await worker._sweep("broadcast", never)
    passes1, rows1 = _admitted()
    assert (passes1 - passes0, rows1 - rows0) == (1, 3)
    assert not worker._held


@pytest.mark.asyncio
async def test_a_sweep_with_no_room_reads_nothing_and_observes_nothing(
        monkeypatch):
    from pybitmessage_tpu.workers import sender
    monkeypatch.setattr(sender, "MAX_IN_FLIGHT", 2)
    store = _Outbox(5)
    worker = _worker(store)
    worker._in_flight.update({b"x", b"y"})

    async def never(m):
        raise AssertionError("there was no room")

    before = _admitted()
    await worker._sweep("broadcast", never)
    assert _admitted() == before
    assert store.reads == []
    assert worker._held == {("sendbroadcast",)}


# -- the crypto pool's two series ---------------------------------------


def _pool_series(name: str) -> tuple[float, int, float]:
    """(busy seconds, jobs that started, their summed wait)."""
    from pybitmessage_tpu.workers import cryptopool
    _, waited, jobs = cryptopool.QUEUE_WAIT.labels(pool=name).snapshot()
    return (cryptopool.BUSY_SECONDS.labels(pool=name).value, jobs, waited)


@pytest.mark.asyncio
async def test_the_pool_counts_a_job_s_length_and_its_wait():
    from pybitmessage_tpu.workers.cryptopool import CryptoPool
    pool = CryptoPool(1, name="test_storm4")
    try:
        busy0, jobs0, wait0 = _pool_series("test_storm4")
        assert await pool.run(lambda: time.sleep(0.05) or "done") == "done"
        busy1, jobs1, wait1 = _pool_series("test_storm4")
        assert jobs1 - jobs0 == 1
        # a job's length, within the tolerance of a sleep
        assert 0.05 <= busy1 - busy0 < 0.05 + 0.1
        assert 0 <= wait1 - wait0 < 0.1
        # two jobs on one thread: the second waits the first's length
        await asyncio.gather(pool.run(time.sleep, 0.05),
                             pool.run(time.sleep, 0.05))
        busy2, jobs2, wait2 = _pool_series("test_storm4")
        assert jobs2 - jobs1 == 2
        assert 0.1 <= busy2 - busy1 < 0.1 + 0.2
        assert 0.05 <= wait2 - wait1 < 0.05 + 0.2
    finally:
        pool.close()


@pytest.mark.asyncio
async def test_a_job_that_raises_is_counted_too_and_another_pool_is_not():
    from pybitmessage_tpu.workers.cryptopool import CryptoPool
    pool = CryptoPool(1, name="test_storm4_raises")
    other0 = _pool_series("sender")
    try:
        def fails():
            time.sleep(0.02)
            raise ValueError("no")

        busy0, jobs0, _ = _pool_series("test_storm4_raises")
        with pytest.raises(ValueError):
            await pool.run(fails)
        busy1, jobs1, _ = _pool_series("test_storm4_raises")
        assert jobs1 - jobs0 == 1 and busy1 - busy0 >= 0.02
    finally:
        pool.close()
    assert _pool_series("sender") == other0
    # inline execution has no thread to wait for or to keep busy
    inline = CryptoPool(0, name="test_storm4_inline")
    assert await inline.run(lambda: 7) == 7
    assert _pool_series("test_storm4_inline") == (0.0, 0, 0.0)
