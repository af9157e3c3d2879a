"""Telemetry subsystem tests: registry, tracer, conventions, overhead.

Covers the ISSUE 1 satellite checklist: concurrent increments from
threads AND asyncio tasks, histogram bucket-edge semantics, the label
cardinality guard, golden-matched Prometheus text output, the metric
naming-convention lint, and the <2% tracing-overhead budget on the
python-tier solve loop.
"""

import asyncio
import importlib
import threading
import time

import pytest

from pybitmessage_tpu.observability import (
    REGISTRY, Counter, Gauge, Histogram, Registry, Tracer, current_span,
    set_batch, snapshot, trace)
from pybitmessage_tpu.observability.metrics import MAX_LABEL_SETS

# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    reg = Registry()
    c = reg.counter("stuff_total", "things")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("level", "a gauge")
    g.set(10)
    g.dec(4)
    assert g.value == 6.0


def test_counter_requires_total_suffix():
    with pytest.raises(ValueError):
        Counter("bad_name", "no suffix")
    with pytest.raises(ValueError):
        Registry().counter("CamelCase_total", "not snake")


def test_labels_validation_and_reuse():
    reg = Registry()
    c = reg.counter("hits_total", "h", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc()
    assert c.labels(kind="a").value == 2
    with pytest.raises(ValueError):
        c.labels(wrong="a")
    with pytest.raises(ValueError):
        c.inc()  # labeled family has no default child


def test_registry_register_is_idempotent():
    reg = Registry()
    a = reg.counter("same_total", "one")
    b = reg.counter("same_total", "one again")
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("same_total")  # type change must be refused


def test_label_cardinality_guard_drops_never_raises():
    """ISSUE 6 satellite: beyond MAX_LABEL_SETS the guard must DROP
    (shared unrendered overflow child + a drop counter), never raise —
    high-cardinality lifecycle labels must not crash the hot path."""
    reg = Registry()
    c = reg.counter("wide_total", "w", ("peer",))
    for i in range(MAX_LABEL_SETS):
        c.labels(peer=str(i)).inc()
    drops0 = REGISTRY.sample("observability_dropped_series_total",
                             {"metric": "wide_total"})
    # overflow series: inc works (never raises on the hot path)...
    c.labels(peer="one-too-many").inc()
    c.labels(peer="two-too-many").inc(5)
    # ...each drop is counted, attributable to the family...
    assert REGISTRY.sample("observability_dropped_series_total",
                           {"metric": "wide_total"}) == drops0 + 2
    # ...and the exposition never renders fabricated overflow series
    rendered = [ln for ln in reg.render().splitlines()
                if ln.startswith("wide_total{")]
    assert len(rendered) == MAX_LABEL_SETS
    assert not any("too-many" in ln for ln in rendered)
    # existing series keep working normally
    c.labels(peer="0").inc()
    assert c.labels(peer="0").value == 2


def test_cardinality_guard_histogram_overflow_observe():
    """The overflow child is type-correct: a guarded histogram's
    observe() works past the cap (the drop is the only signal)."""
    reg = Registry()
    h = reg.histogram("wide_seconds", "w", ("k",), buckets=(1.0,))
    for i in range(MAX_LABEL_SETS):
        h.labels(k=str(i)).observe(0.5)
    h.labels(k="overflow").observe(0.5)   # must not raise
    assert REGISTRY.sample("observability_dropped_series_total",
                           {"metric": "wide_seconds"}) >= 1


def test_histogram_bucket_edges():
    reg = Registry()
    h = reg.histogram("edge_seconds", "e", buckets=(0.1, 1.0, 10.0))
    # Prometheus buckets are `le`: a value exactly on a bound counts
    # into that bound's bucket
    for v in (0.1, 1.0, 10.0, 10.000001):
        h.observe(v)
    text = reg.render()
    assert 'edge_seconds_bucket{le="0.1"} 1' in text
    assert 'edge_seconds_bucket{le="1"} 2' in text
    assert 'edge_seconds_bucket{le="10"} 3' in text
    assert 'edge_seconds_bucket{le="+Inf"} 4' in text
    assert h.count == 4


def test_histogram_percentile_interpolation():
    reg = Registry()
    h = reg.histogram("p_seconds", "p", buckets=(1.0, 2.0, 4.0))
    for _ in range(100):
        h.observe(1.5)
    p50 = h.percentile(0.5)
    assert 1.0 <= p50 <= 2.0
    assert h.percentile(0.0) <= h.percentile(0.99)


def test_concurrent_increments_threads_and_asyncio():
    reg = Registry()
    c = reg.counter("race_total", "r")
    h = reg.histogram("race_seconds", "r", buckets=(1.0,))
    per_thread, threads = 5000, 8

    def hammer():
        for _ in range(per_thread):
            c.inc()
            h.observe(0.5)

    ts = [threading.Thread(target=hammer) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()

    async def async_hammer():
        async def one():
            for _ in range(1000):
                c.inc()
        await asyncio.gather(*(one() for _ in range(5)))

    asyncio.run(async_hammer())
    assert c.value == per_thread * threads + 5000
    assert h.count == per_thread * threads


def test_prometheus_text_golden():
    reg = Registry()
    c = reg.counter("events_total", "Things that happened", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc()
    c.labels(kind="b").inc(3)
    g = reg.gauge("depth", "Queue depth")
    g.set(7)
    h = reg.histogram("lat_seconds", "Latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.1, 0.1, 0.5, 20.0):
        h.observe(v)
    assert reg.render() == """\
# HELP depth Queue depth
# TYPE depth gauge
depth 7
# HELP events_total Things that happened
# TYPE events_total counter
events_total{kind="a"} 2
events_total{kind="b"} 3
# HELP lat_seconds Latency
# TYPE lat_seconds histogram
lat_seconds_bucket{le="0.1"} 2
lat_seconds_bucket{le="1"} 3
lat_seconds_bucket{le="10"} 3
lat_seconds_bucket{le="+Inf"} 4
lat_seconds_sum 20.7
lat_seconds_count 4
"""


def test_label_value_escaping():
    reg = Registry()
    c = reg.counter("esc_total", "e", ("what",))
    c.labels(what='say "hi"\nback\\slash').inc()
    line = [ln for ln in reg.render().splitlines()
            if ln.startswith("esc_total{")][0]
    assert line == 'esc_total{what="say \\"hi\\"\\nback\\\\slash"} 1'


def test_exposition_escaping_golden():
    """ISSUE 6 satellite: full golden text with every escapable class
    in label values (backslash, newline, double-quote) AND in HELP —
    where the spec escapes ONLY backslash and newline (a quote stays
    verbatim)."""
    from pybitmessage_tpu.observability import (escape_help,
                                                escape_label_value)
    assert escape_label_value('a\\b\nc"d') == 'a\\\\b\\nc\\"d'
    assert escape_help('a\\b\nc"d') == 'a\\\\b\\nc"d'
    reg = Registry()
    c = reg.counter("esc2_total", 'help with "quotes"\nand\\slash',
                    ("v",))
    c.labels(v='x\\y\n"z"').inc()
    assert reg.render() == (
        '# HELP esc2_total help with "quotes"\\nand\\\\slash\n'
        "# TYPE esc2_total counter\n"
        'esc2_total{v="x\\\\y\\n\\"z\\""} 1\n')


def test_sample_and_snapshot():
    reg = Registry()
    c = reg.counter("s_total", "s", ("k",))
    c.labels(k="x").inc(4)
    assert reg.sample("s_total", {"k": "x"}) == 4
    assert reg.sample("s_total", {"k": "missing"}) == 0
    assert reg.sample("no_such_metric") == 0
    h = reg.histogram("s_seconds", "s")
    h.observe(0.25)
    snap = snapshot(reg)
    assert snap["s_total"]["type"] == "counter"
    hist = snap["s_seconds"]["series"][0]
    assert hist["count"] == 1 and hist["sum"] == 0.25
    assert "p50" in hist and "p99" in hist


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_trace_parent_linkage_and_ring_buffer():
    t = Tracer(maxlen=4)
    with trace("outer", tracer=t) as outer:
        with trace("inner", tracer=t, tier="tpu") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.attrs["tier"] == "tpu"
    assert outer.parent_id is None
    names = [s.name for s in t.recent()]
    assert names == ["inner", "outer"]  # inner finishes first
    assert all(s.duration is not None and s.duration >= 0
               for s in t.recent())
    for i in range(10):
        with trace("fill%d" % i, tracer=t):
            pass
    assert len(t.recent(100)) == 4  # ring retention


def test_trace_parent_linkage_across_await():
    t = Tracer()

    async def inner():
        with trace("child", tracer=t) as span:
            await asyncio.sleep(0)
            return span

    async def outer():
        with trace("parent", tracer=t) as parent:
            child = await inner()
        return parent, child

    parent, child = asyncio.run(outer())
    assert child.parent_id == parent.span_id


def test_trace_decorator_and_exception_marking():
    t = Tracer()

    @trace("fn.work", tracer=t)
    def work(x):
        return x * 2

    assert work(21) == 42
    assert t.recent()[-1].name == "fn.work"

    with pytest.raises(RuntimeError):
        with trace("boom", tracer=t):
            raise RuntimeError("x")
    assert t.recent()[-1].attrs["error"] == "RuntimeError"


def test_trace_parent_restored_when_body_raises():
    """ISSUE 6 satellite: the parent contextvar must be restored on
    the exception path — a raising span body must not leave later
    spans parented under a dead span."""
    from pybitmessage_tpu.observability import current_span
    t = Tracer()
    assert current_span() is None
    with trace("outer", tracer=t) as outer:
        with pytest.raises(RuntimeError):
            with trace("inner", tracer=t):
                assert current_span().name == "inner"
                raise RuntimeError("boom")
        # inner's exit must restore outer as the current span
        assert current_span() is outer
        with trace("sibling", tracer=t) as sib:
            assert sib.parent_id == outer.span_id
    assert current_span() is None
    # the raising span was still recorded, marked, and timed
    inner = [s for s in t.recent() if s.name == "inner"][0]
    assert inner.attrs["error"] == "RuntimeError"
    assert inner.duration is not None


def test_trace_decorator_restores_parent_on_raise():
    t = Tracer()
    from pybitmessage_tpu.observability import current_span

    @trace("fn.boom", tracer=t)
    def boom():
        raise ValueError("x")

    with pytest.raises(ValueError):
        boom()
    assert current_span() is None


def test_trace_feeds_histogram():
    reg = Registry()
    h = reg.histogram("span_seconds", "s")
    t = Tracer()
    with trace("timed", tracer=t, histogram=h):
        pass
    assert h.count == 1


def _host_events(trace_dir, names):
    """``{name: [(start_s, dur_s, stats), ...]}`` of the host events
    called ``names`` in the newest profile under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(str(trace_dir) + "/plugins/profile/*/*.xplane.pb")
    out = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append((ev.start_ns / 1e9,
                                         ev.duration_ns / 1e9,
                                         dict(ev.stats)))
    return out


def test_spans_follow_the_profiler_without_a_switch(tmp_path):
    """A profiler session finds the program's spans with nobody
    switching anything on; with no session open a span still works;
    and two spans that cross an await and overlap without nesting on
    one thread (worker.pow) are both recorded as whole intervals."""
    import jax.profiler as prof

    t = Tracer()
    with trace("bridged.quiet", tracer=t):      # no session: no event
        pass
    prof.start_trace(str(tmp_path))
    try:
        with trace("bridged.nested", tracer=t, live=3):
            with trace("bridged.inner", tracer=t):
                time.sleep(0.002)

        async def waits(i):
            await asyncio.sleep(0.002 * i)
            with trace("bridged.overlap", tracer=t, i=i):
                await asyncio.sleep(0.004)

        async def both():       # the first leaves before the second:
            await asyncio.gather(waits(0), waits(1))    # no stack
        asyncio.run(both())
    finally:
        prof.stop_trace()
    assert [s.name for s in t.recent()] == [
        "bridged.quiet", "bridged.inner", "bridged.nested",
        "bridged.overlap", "bridged.overlap"]
    events = _host_events(tmp_path, ["bridged.quiet", "bridged.nested",
                                     "bridged.inner", "bridged.overlap"])
    assert events["bridged.quiet"] == []
    (nested,), (inner,) = events["bridged.nested"], events["bridged.inner"]
    assert nested[2]["live"] == 3               # attributes ride along
    assert nested[0] <= inner[0] and \
        inner[0] + inner[1] <= nested[0] + nested[1] + 1e-6
    first, second = sorted(events["bridged.overlap"])
    assert first[1] >= 0.004 and second[1] >= 0.004
    assert first[0] < second[0] < first[0] + first[1] < second[0] + second[1]


def test_a_span_never_imports_jax():
    import subprocess
    import sys
    code = ("import sys\n"
            "from pybitmessage_tpu.observability import TRACER, trace\n"
            "with trace('cold', k=1):\n"
            "    pass\n"
            "assert TRACER.recent()[-1].name == 'cold'\n"
            "assert 'jax' not in sys.modules, 'a span imported jax'\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_parent_and_batch_survive_both_executor_hops():
    """The send path hops to the default executor twice
    (``SendWorker._run_crypto``, ``PowService._run``); both copy the
    context, so the crypto spans are children of the sweep and every
    span of a solve carries its batch's number, which the waiting
    ``worker.pow`` is given too."""
    from pybitmessage_tpu.pow.service import PowService
    from pybitmessage_tpu.workers.sender import SendWorker, _encrypt, _sign
    from pybitmessage_tpu.crypto import priv_to_pub
    from pybitmessage_tpu.observability import TRACER

    class Solver:
        last_backend = "fake"

        def solve_batch(self, items, **_kw):
            with trace("pow.solve_batch", objects=len(items)):
                with trace("pow.launch", program="fake"):
                    pass
            return [(7, 1)] * len(items)

    async def run():
        worker = SendWorker.__new__(SendWorker)
        service = PowService(Solver(), window=0.01)
        service.start()
        try:
            with trace("sender.sweep", kind="test", objects=1) as sweep:
                sig = await worker._run_crypto(_sign, b"data", b"\x01" * 32)
                await worker._run_crypto(
                    _encrypt, b"plain", priv_to_pub(b"\x02" * 32))
                with trace("worker.pow") as waiting:
                    assert await service.solve(b"h" * 64, 1 << 60) == (7, 1)
            return sweep, waiting, sig
        finally:
            await service.stop()

    TRACER.clear()
    sweep, waiting, sig = asyncio.run(run())
    assert sig
    spans = {s.name: s for s in TRACER.recent(200)}
    assert spans["sender.sign"].parent_id == sweep.span_id
    assert spans["sender.encrypt"].parent_id == sweep.span_id
    batch = spans["pow.queue.window"].attrs["batch"]
    assert spans["pow.queue.window"].attrs["objects"] == 1
    assert spans["pow.solve_batch"].attrs["batch"] == batch
    assert spans["pow.launch"].attrs["batch"] == batch
    assert spans["pow.launch"].parent_id == spans["pow.solve_batch"].span_id
    assert waiting.attrs["batch"] == batch
    assert "batch" not in sweep.attrs and current_span() is None


# ---------------------------------------------------------------------------
# lifecycle tracer (ISSUE 6 tentpole #1)
# ---------------------------------------------------------------------------


def _fresh_tracer(maxlen=8, **kw):
    from pybitmessage_tpu.observability import LifecycleTracer
    reg = Registry()
    hist = reg.histogram("t_stage_seconds", "s", ("from", "to"))
    prop = reg.histogram("t_prop_seconds", "p")
    return LifecycleTracer(maxlen=maxlen, stage_histogram=hist,
                           propagation_histogram=prop,
                           update_gauge=False, **kw), hist, prop


def test_lifecycle_timeline_and_stage_latency():
    clock = {"t": 0.0}
    tracer, hist, _ = _fresh_tracer(clock=lambda: clock["t"])
    h = b"\x01" * 32
    for stage, t in (("received", 0.0), ("parsed", 0.5),
                     ("decrypted", 1.5), ("verified", 1.75),
                     ("stored", 2.0), ("delivered", 2.5)):
        clock["t"] = t
        tracer.record(h, stage)
    timeline = tracer.timeline(h)
    assert [e["stage"] for e in timeline] == [
        "received", "parsed", "decrypted", "verified", "stored",
        "delivered"]
    # stage-to-stage latency landed per (from, to) pair
    assert hist.labels(**{"from": "received", "to": "parsed"})._count == 1
    assert hist.labels(**{"from": "parsed",
                          "to": "decrypted"})._count == 1
    assert abs(hist.labels(**{"from": "parsed",
                              "to": "decrypted"})._sum - 1.0) < 1e-9


def test_lifecycle_lru_retention_bound():
    tracer, _, _ = _fresh_tracer(maxlen=4)
    for i in range(10):
        tracer.record(bytes([i]) * 32, "received")
    assert tracer.tracked() == 4
    # oldest evicted, newest kept
    assert tracer.timeline(bytes([0]) * 32) == []
    assert tracer.timeline(bytes([9]) * 32)
    # per-timeline event cap
    h = b"\xFF" * 32
    for _ in range(200):
        tracer.record(h, "announced")
    assert len(tracer.timeline(h)) <= tracer.MAX_EVENTS


def test_lifecycle_capped_timeline_stops_observing_latency():
    """Past MAX_EVENTS the stale last event must not keep feeding the
    stage histogram with ever-growing fabricated deltas."""
    clock = {"t": 0.0}
    tracer, hist, _ = _fresh_tracer(maxlen=4,
                                    clock=lambda: clock["t"])
    h = b"\xFE" * 32
    for i in range(tracer.MAX_EVENTS + 50):
        clock["t"] = float(i)
        tracer.record(h, "announced")
    child = hist.labels(**{"from": "announced", "to": "announced"})
    # MAX_EVENTS appended events -> MAX_EVENTS - 1 transitions; the 50
    # capped calls observed nothing
    assert child._count == tracer.MAX_EVENTS - 1
    assert child._sum == float(tracer.MAX_EVENTS - 1)


def test_lifecycle_snapshot_counts_follow_eviction():
    """snapshot() per-stage counts are maintained incrementally and
    shrink when timelines are evicted or discarded."""
    tracer, _, _ = _fresh_tracer(maxlen=2)
    a, b, c = (bytes([i]) * 32 for i in (1, 2, 3))
    tracer.record(a, "received")
    tracer.record(b, "received")
    tracer.record(b, "stored")
    assert tracer.snapshot()["stageEvents"] == {
        "received": 2, "stored": 1}
    tracer.record(c, "received")        # evicts a
    assert tracer.snapshot()["stageEvents"] == {
        "received": 2, "stored": 1}
    tracer.discard(b)
    assert tracer.snapshot()["stageEvents"] == {"received": 1}


def test_lifecycle_propagation_percentiles():
    clock = {"t": 0.0}
    tracer, _, prop = _fresh_tracer(maxlen=64,
                                    clock=lambda: clock["t"])
    for i in range(10):
        h = bytes([i]) * 32
        clock["t"] = float(i)
        tracer.record(h, "received")
        clock["t"] = float(i) + (1.0 if i < 9 else 5.0)
        delta = tracer.observe_propagation(h)
        assert delta is not None
    pcts = tracer.propagation_percentiles()
    assert pcts["count"] == 10
    assert pcts["p50"] == 1.0
    assert pcts["p99"] == 5.0
    assert prop._default_child()._count == 10
    # unknown hash: no origin event, no observation
    assert tracer.observe_propagation(b"\xEE" * 32) is None


def test_lifecycle_record_never_raises():
    """The hot-path contract: a broken histogram must not surface."""
    tracer, _, _ = _fresh_tracer()

    class Boom:
        def labels(self, **kv):
            raise RuntimeError("broken")

    tracer._stage_hist = Boom()
    tracer.record(b"\x01" * 32, "received")
    tracer.record(b"\x01" * 32, "parsed")   # latency path -> Boom
    assert [e["stage"] for e in tracer.timeline(b"\x01" * 32)] == [
        "received", "parsed"]


def test_lifecycle_disabled_is_noop():
    tracer, _, _ = _fresh_tracer()
    tracer.enabled = False
    tracer.record(b"\x02" * 32, "received")
    assert tracer.tracked() == 0


def test_lifecycle_global_hooks_stage_chain():
    """The process-wide tracer accumulates the documented chain from
    the real hook sites' stage names."""
    from pybitmessage_tpu.observability import LIFECYCLE
    from pybitmessage_tpu.observability.lifecycle import STAGES
    for s in ("received", "parsed", "decrypted", "verified", "stored",
              "announced", "sync_pushed", "delivered"):
        assert s in STAGES
    h = b"\xAB" * 32
    LIFECYCLE.record(h, "received")
    LIFECYCLE.record(h, "parsed")
    assert [e["stage"] for e in LIFECYCLE.timeline(h)] == [
        "received", "parsed"]
    LIFECYCLE.discard(h)
    assert LIFECYCLE.timeline(h) == []


# ---------------------------------------------------------------------------
# flight recorder (ISSUE 6 tentpole #2)
# ---------------------------------------------------------------------------


def test_flightrec_ring_bound_and_filter():
    from pybitmessage_tpu.observability import FlightRecorder
    rec = FlightRecorder(maxlen=16)
    for i in range(50):
        rec.record("breaker" if i % 2 else "chaos", i=i)
    events = rec.events()
    assert len(events) == 16
    assert events[-1]["i"] == 49          # newest kept
    assert all(e["i"] >= 34 for e in events)
    assert all(e["kind"] == "chaos" for e in rec.events(kind="chaos"))
    assert len(rec.events(3)) == 3
    rec.resize(8)
    assert len(rec.events()) == 8


def test_flightrec_dump_counts_and_logs():
    import logging

    from pybitmessage_tpu.observability import FlightRecorder
    rec = FlightRecorder(maxlen=16)
    rec.record("stall", site="pow.slab")
    before = REGISTRY.sample("flightrec_dumps_total",
                             {"trigger": "stall"})
    logger = logging.getLogger("test.flightrec")
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger.addHandler(handler)
    try:
        events = rec.dump("stall", log=logger)
    finally:
        logger.removeHandler(handler)
    assert events and events[-1]["kind"] == "stall"
    assert REGISTRY.sample("flightrec_dumps_total",
                           {"trigger": "stall"}) == before + 1
    assert records and "flightrec_dump" in records[0].getMessage()


def test_flightrec_stall_guard_auto_dumps():
    """StallGuard's stall detection must leave the triggering event in
    the ring and emit an automatic dump (the acceptance path)."""
    from pybitmessage_tpu.observability import FLIGHT_RECORDER
    from pybitmessage_tpu.resilience.watchdog import (SlabStallError,
                                                      StallGuard)
    before = REGISTRY.sample("flightrec_dumps_total",
                             {"trigger": "stall"})
    guard = StallGuard(timeout=0.05, site="pow.slab")
    with pytest.raises(SlabStallError):
        guard.run(lambda: time.sleep(2.0))
    assert REGISTRY.sample("flightrec_dumps_total",
                           {"trigger": "stall"}) == before + 1
    stalls = FLIGHT_RECORDER.events(kind="stall")
    assert stalls and stalls[-1]["site"] == "pow.slab"


def test_flightrec_breaker_and_chaos_events():
    """Breaker transitions and chaos fires land in the ring."""
    from pybitmessage_tpu.observability import FLIGHT_RECORDER
    from pybitmessage_tpu.resilience import CHAOS, CircuitBreaker
    br = CircuitBreaker("test.flight", threshold=1, cooldown=60.0,
                        register=False)
    br.record_failure()
    flips = FLIGHT_RECORDER.events(kind="breaker")
    assert flips and flips[-1]["name"] == "test.flight"
    assert flips[-1]["to"] == "open"
    CHAOS.arm("test.flight_site", probability=1.0, count=1)
    try:
        with pytest.raises(Exception):
            CHAOS.inject("test.flight_site")
    finally:
        CHAOS.disarm("test.flight_site")
    fires = FLIGHT_RECORDER.events(kind="chaos")
    assert fires and fires[-1]["site"] == "test.flight_site"


# ---------------------------------------------------------------------------
# health probes (ISSUE 6 tentpole #3)
# ---------------------------------------------------------------------------


def test_loop_lag_probe_observes_blockage():
    from pybitmessage_tpu.observability import LoopLagProbe

    reg = Registry()
    hist = reg.histogram("lag_seconds", "l")

    async def scenario():
        probe = LoopLagProbe(0.005, histogram=hist)
        probe.start()
        await asyncio.sleep(0.03)
        time.sleep(0.08)          # block the loop
        await asyncio.sleep(0.03)
        await probe.stop()
        return probe

    probe = asyncio.run(scenario())
    assert hist.count >= 2
    assert probe.max_lag >= 0.05
    # the health verdict reads the RECENT window, not the cumulative
    # histogram — the blockage must show up in it
    assert probe.recent_p99() >= 0.05


def test_health_block_shapes():
    from pybitmessage_tpu.observability import HealthMonitor
    mon = HealthMonitor(None)
    block = mon.health_block()
    assert block["loop"]["status"] in ("ok", "degraded")
    assert "lagP99Ms" in block["loop"]

    class _Queue:
        paused = False

        def qsize(self):
            return 3

    class _Proc:
        concurrency = 8
        active = 2
        crypto = None
        _wb = None

    class _Node:
        processor = _Proc()
        reconciler = None

        class ctx:
            object_queue = _Queue()

    mon = HealthMonitor(_Node())
    mon.sample()
    block = mon.health_block()
    assert set(block) >= {"loop", "pow", "ingest", "storage"}
    assert block["ingest"]["queueDepth"] == 3
    assert block["ingest"]["status"] == "ok"
    _Queue.paused = True
    assert mon.health_block()["ingest"]["status"] == "degraded"
    _Queue.paused = False


# ---------------------------------------------------------------------------
# perf guard (ISSUE 6 tentpole #4: tools/bench_compare.py)
# ---------------------------------------------------------------------------


def _bench_compare():
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).parent.parent / "tools"
            / "bench_compare.py")
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perfguard_compare_tolerance_bands():
    """Per-metric bands: 'higher' fails below baseline*(1-tol),
    'lower' fails above baseline*(1+tol), 'equal' fails on any
    difference."""
    bc = _bench_compare()
    guards = [("rate", "higher", 0.50), ("lag", "lower", 1.00),
              ("lossless", "equal", 0.0)]
    base = {"rate": 100.0, "lag": 2.0, "lossless": True}
    ok = {"rate": 51.0, "lag": 3.9, "lossless": True}
    failures, notes = bc.compare(base, ok, guards)
    assert not failures and len(notes) == 3
    bad = {"rate": 49.0, "lag": 4.1, "lossless": False}
    failures, _ = bc.compare(base, bad, guards)
    assert len(failures) == 3


def test_perfguard_missing_metric_is_a_regression():
    """A metric the baseline carries but the run lost FAILS (silent
    coverage loss is itself a regression) — unless its section is
    explicitly marked skipped (optional dep absent on the host)."""
    bc = _bench_compare()
    guards = [("configs.ingest.objects_per_s", "higher", 0.5)]
    base = {"configs": {"ingest": {"objects_per_s": 50.0}}}
    failures, _ = bc.compare(base, {"configs": {}}, guards)
    assert failures and failures[0].startswith("LOST")
    skipped = {"configs": {"ingest": {"skipped": "no cryptography"}}}
    failures, notes = bc.compare(base, skipped, guards)
    assert not failures
    assert any("skipped" in n for n in notes)
    # absent from the BASELINE: skipped quietly (new metric, old file)
    failures, notes = bc.compare({}, {"configs": {}}, guards)
    assert not failures and any(n.startswith("SKIP") for n in notes)


def test_perfguard_env_scale_scales_floors_down_only():
    """Calibration-aware bands (ISSUE 17 satellite): a slower host
    than the baseline recorder gets its wall-clock 'higher' floors
    scaled down by the measured speed ratio; a faster host never gets
    a ratcheted-up bar; runs without the stamp compare neutrally."""
    bc = _bench_compare()
    base = {"calibration": {"cpu_count": 24,
                            "single_thread_hps": 1000.0},
            "rate": 100.0}
    guards = [("rate", "higher", 0.60)]
    slow = {"calibration": {"cpu_count": 1,
                            "single_thread_hps": 500.0},
            "rate": 15.0}
    scale = bc.env_scale(base, slow)
    assert 0.05 <= scale < 0.5
    failures, notes = bc.compare(base, slow, guards)
    assert not failures, failures
    assert any("host x" in n for n in notes)
    # without scaling this run would have failed the 40-point floor
    assert slow["rate"] < base["rate"] * 0.40
    fast = {"calibration": {"cpu_count": 48,
                            "single_thread_hps": 2000.0},
            "rate": 41.0}
    assert bc.env_scale(base, fast) == 1.0
    assert bc.env_scale({}, slow) == 1.0          # no stamp: neutral
    assert bc.env_scale(base, {}) == 1.0


def test_perfguard_committed_baseline_is_consistent():
    """The committed smoke baseline must parse and carry at least the
    machine-independent invariant guards (the 'equal' kind) so
    perfguard can never silently guard nothing."""
    import json
    import pathlib
    bc = _bench_compare()
    path = pathlib.Path(bc.DEFAULT_BASELINE)
    assert path.exists(), "commit bench_baseline_smoke.json " \
        "(generate: python tools/bench_compare.py --run --update)"
    baseline = json.loads(path.read_text())
    equal_guards = [p for p, kind, _ in bc.GUARDS if kind == "equal"]
    carried = [p for p in equal_guards
               if bc.dig(baseline, p) is not None]
    assert carried, "baseline carries no invariant guards"


# ---------------------------------------------------------------------------
# overhead budget (acceptance: <2% on the python-tier solve loop)
# ---------------------------------------------------------------------------


def test_tracing_overhead_under_two_percent():
    """One span + the ISSUE 6 per-object telemetry (two lifecycle
    stage records and one flight-recorder event) wrap one dispatcher
    solve; their combined cost must be <2% of a realistic python-tier
    solve (~20k trials).  Measured generously: amortized over 2000
    iterations."""
    import hashlib

    from pybitmessage_tpu.observability import (FlightRecorder,
                                                LifecycleTracer)
    from pybitmessage_tpu.ops.pow_search import PowInterrupted
    from pybitmessage_tpu.pow import python_solve

    reg = Registry()
    h = reg.histogram("ovh_seconds", "o")
    stage_h = reg.histogram("ovh_stage_seconds", "o", ("from", "to"))
    lc = LifecycleTracer(maxlen=4096, stage_histogram=stage_h,
                         update_gauge=False)
    fr = FlightRecorder(maxlen=256)
    t = Tracer()
    n = 2000
    keys = [i.to_bytes(32, "big") for i in range(n)]
    t0 = time.perf_counter()
    for i in range(n):
        with trace("pow.solve", histogram=h):
            pass
        lc.record(keys[i], "received")
        lc.record(keys[i], "parsed")
        fr.record("slab_launch", n=i)
    span_cost = (time.perf_counter() - t0) / n

    # the span alone, mirrored into the profiler's annotation (always
    # on once jax is imported) with no session open: under 5 us on an
    # idle host (PERF.md section 6); the ceiling here is loose so that
    # a loaded test host cannot fail it
    import jax.profiler  # noqa: F401 - the bridge needs it imported
    from pybitmessage_tpu.observability import tracing
    t0 = time.perf_counter()
    for i in range(n):
        with trace("pow.launch", tracer=t, chunks=64, live=i):
            pass
    bare_cost = (time.perf_counter() - t0) / n
    assert tracing._annotation is not None      # the bridge was on
    assert bare_cost < 25e-6, "one span costs %.2fus" % (bare_cost * 1e6)

    calls = []

    def stop():
        calls.append(1)
        return len(calls) > 5  # ~20k trials (checked every 4096)

    ih = hashlib.sha512(b"overhead test").digest()
    t0 = time.perf_counter()
    with pytest.raises(PowInterrupted):
        python_solve(ih, 0, should_stop=stop)
    solve_time = time.perf_counter() - t0
    assert span_cost / solve_time < 0.02, (
        "span %.2fus vs solve %.2fms" % (span_cost * 1e6,
                                         solve_time * 1e3))


# ---------------------------------------------------------------------------
# convention lints — thin wrappers over the bmlint engine (ISSUE 10).
# The ad-hoc AST walks and their hand-maintained per-module include
# lists moved into tools/bmlint checkers that sweep the WHOLE package
# plus tools/; these wrappers keep the conventions gated inside tier-1
# by name (the full gate lives in tests/test_bmlint.py).
# ---------------------------------------------------------------------------


def _bmlint_new_findings(rules):
    from tests.test_bmlint import repo_new_and_stale
    new, _ = repo_new_and_stale()     # cached: one sweep per session
    return ["%s:%d %s" % (f.path, f.line, f.message)
            for f in new if f.rule in rules]


def test_no_silent_exception_swallows():
    """ISSUE 3 satellite lint, now package-wide via bmlint: a broad
    handler whose body only passes silently destroys the error.  New
    swallows anywhere in pybitmessage_tpu/ or tools/ fail here."""
    offenders = _bmlint_new_findings({"silent-swallow",
                                      "except-discipline"})
    assert not offenders, (
        "silent/uncounted broad exception handlers (log + count them "
        "instead, see docs/resilience.md): %s" % ", ".join(offenders))


def test_metric_naming_conventions():
    """Metric conventions, now AST-enforced package-wide via bmlint
    (no per-module import list): snake_case everywhere, counters end
    _total, histograms carry a unit suffix, gauges are bare nouns,
    REGISTRY-only registration, bounded label values."""
    offenders = _bmlint_new_findings({"metric-naming",
                                      "metric-registry",
                                      "metric-labels"})
    assert not offenders, (
        "metric convention violations (docs/observability.md): %s"
        % ", ".join(offenders))


def test_metric_naming_runtime_complement():
    """The AST sweep cannot see DYNAMICALLY-composed metric names, so
    the runtime half survives: import every module of the
    instrumented subpackages (discovered from the filesystem — no
    hand-maintained per-module list) and lint what actually landed in
    the default registry."""
    import pathlib
    import re

    import pybitmessage_tpu

    root = pathlib.Path(pybitmessage_tpu.__file__).parent
    for sub in ("pow", "network", "storage", "sync", "observability",
                "workers", "crypto", "utils", "resilience", "api",
                "roles", "powfarm"):
        for path in sorted((root / sub).glob("*.py")):
            name = "pybitmessage_tpu.%s" % sub if \
                path.stem == "__init__" else \
                "pybitmessage_tpu.%s.%s" % (sub, path.stem)
            try:
                importlib.import_module(name)
            except ImportError:
                continue    # optional deps (cryptography, qrcode, ...)
    snake = re.compile(r"^[a-z][a-z0-9_]*$")
    fams = REGISTRY.families()
    assert len(fams) >= 10, "instrumented modules must register metrics"
    for fam in fams:
        assert snake.match(fam.name), fam.name
        for ln in fam.labelnames:
            assert snake.match(ln), (fam.name, ln)
        if isinstance(fam, Counter):
            assert fam.name.endswith("_total"), fam.name
        elif isinstance(fam, Histogram):
            assert fam.name.endswith(("_seconds", "_size", "_bytes",
                                      "_steps")), fam.name
        elif isinstance(fam, Gauge):
            assert not fam.name.endswith("_total"), fam.name


# ---------------------------------------------------------------------------
# distributed observability plane (ISSUE 9)
# ---------------------------------------------------------------------------


def test_peer_bucket_labeler_stable_and_bounded():
    """ISSUE 9 satellite: hashed peer buckets are deterministic,
    bounded by the configured count, and spread distinct peers."""
    from pybitmessage_tpu.observability import (peer_bucket,
                                                peer_bucket_label,
                                                set_peer_buckets)
    from pybitmessage_tpu.observability.metrics import peer_buckets
    assert peer_bucket("10.0.0.1:8444") == peer_bucket("10.0.0.1:8444")
    labels = {peer_bucket("peer-%d" % i) for i in range(1000)}
    assert len(labels) <= peer_buckets()
    assert len(labels) > 1
    assert peer_bucket_label("sync.reconcile", "h:1").startswith(
        "sync.reconcile/b")
    old = peer_buckets()
    try:
        set_peer_buckets(4)
        assert len({peer_bucket("p%d" % i) for i in range(100)}) <= 4
    finally:
        set_peer_buckets(old)


def test_peer_bucket_migrated_breaker_labels():
    """The per-peer sync/dial breakers carry bucketed labels, not one
    shared label (per-bucket visibility) and not raw peers (bounded
    cardinality)."""
    import re as _re

    from pybitmessage_tpu.sync.reconciler import SyncSession

    class _Conn:
        host, port = "203.0.113.9", 8444

    s = SyncSession(_Conn())
    assert _re.fullmatch(r"sync\.reconcile/b\d{2}", s.breaker.label)


def test_trace_context_roundtrip_and_rejection():
    from pybitmessage_tpu.observability import TRACE_CTX_LEN, TraceContext
    ctx = TraceContext(b"\x42" * 16, 1234, 1000.5)
    data = ctx.encode()
    assert len(data) == TRACE_CTX_LEN
    back = TraceContext.decode(data)
    assert back.trace_id == b"\x42" * 16
    assert back.parent_span == 1234
    assert abs(back.sent_at - 1000.5) < 1e-5
    with pytest.raises(ValueError):
        TraceContext.decode(data[:-1])
    # message-layer split: payload + trailer roundtrip
    from pybitmessage_tpu.network.messages import (MessageError,
                                                   append_trace_ctx,
                                                   split_trace_ctx)
    framed = append_trace_ctx(b"payload", ctx)
    payload, parsed = split_trace_ctx(framed)
    assert payload == b"payload"
    assert parsed.trace_id == ctx.trace_id
    with pytest.raises(MessageError):
        split_trace_ctx(b"short")


def test_skew_estimator_bounded_and_converges():
    from pybitmessage_tpu.observability import SkewEstimator
    est = SkewEstimator()
    assert est.offset() == 0.0
    for _ in range(50):
        est.observe(1010.0, 1000.0)   # remote runs 10s ahead
    assert abs(est.offset() - 10.0) < 0.5
    assert abs(est.normalize(1010.0) - 1000.0) < 0.5
    # an insane peer clock is clamped, not adopted
    est2 = SkewEstimator(max_abs=60.0)
    est2.observe(1e9, 0.0)
    assert est2.offset() <= 60.0
    snap = est.snapshot()
    assert snap["samples"] == 50 and "offsetSeconds" in snap


def test_lifecycle_trace_adoption_and_ctx():
    """adopt() stitches a remote trace onto a hash (first writer
    wins); trace_ctx_for mints a fresh trace for origin objects and
    reuses the adopted one for relayed objects."""
    from pybitmessage_tpu.observability import LifecycleTracer
    tracer = LifecycleTracer(maxlen=8, stage_histogram=None,
                             propagation_histogram=None,
                             update_gauge=False)
    h = b"\x77" * 32
    tracer.adopt(h, b"\x01" * 16, parent_span=99)
    meta = tracer.trace_meta(h)
    assert meta["trace_id"] == b"\x01" * 16
    assert meta["parent_span"] == 99
    # a later duplicate push must not rebind the origin trace
    tracer.adopt(h, b"\x02" * 16, parent_span=5)
    assert tracer.trace_meta(h)["trace_id"] == b"\x01" * 16
    ctx = tracer.trace_ctx_for(h)
    assert ctx.trace_id == b"\x01" * 16
    assert ctx.parent_span == meta["span"]  # OUR span becomes their parent
    # origin object: fresh 16-byte trace id
    ctx2 = tracer.trace_ctx_for(b"\x88" * 32)
    assert len(ctx2.trace_id) == 16 and ctx2.trace_id != ctx.trace_id
    # the meta map is bounded even for hashes that never get timelines
    for i in range(5 * tracer.maxlen):
        tracer.trace_ctx_for(i.to_bytes(32, "big"))
    assert len(tracer._trace_meta) <= 2 * tracer.maxlen


# ---------------------------------------------------------------------------
# federation: snapshot merge goldens (ISSUE 9 tentpole b)
# ---------------------------------------------------------------------------


def _fed():
    from pybitmessage_tpu.observability import (Aggregator,
                                                FederationPublisher)
    return Aggregator, FederationPublisher


def test_federation_counter_and_gauge_merge_golden():
    Aggregator, FederationPublisher = _fed()
    agg = Aggregator()
    regs = []
    for n in (3, 5):
        reg = Registry()
        reg.counter("jobs_total", "j", ("lane",)).labels(
            lane="bulk").inc(n)
        reg.gauge("depth", "d").set(n)
        regs.append(reg)
    for i, reg in enumerate(regs):
        pub = FederationPublisher("node%d" % i, reg,
                                  transport=agg.ingest)
        assert pub.push_once()["ok"]
    assert agg.merged_value("jobs_total", {"lane": "bulk"}) == 8
    assert agg.merged_value("depth") == 8
    text = agg.render()
    assert 'jobs_total{lane="bulk"} 8' in text
    assert "depth 8" in text


def test_federation_histogram_bucketwise_merge_golden():
    """Histograms merge bucket-WISE: counts add per bucket, sum/count
    add, and the merged percentile reads the combined distribution."""
    Aggregator, FederationPublisher = _fed()
    agg = Aggregator()
    for i, values in enumerate(((0.5, 0.5, 0.5), (3.0,))):
        reg = Registry()
        h = reg.histogram("lat_seconds", "l", buckets=(1.0, 2.0, 4.0))
        for v in values:
            h.observe(v)
        FederationPublisher("n%d" % i, reg,
                            transport=agg.ingest).push_once()
    merged = agg.merged()["lat_seconds"]
    series = merged["series"][0]
    assert series["c"] == [3, 0, 1, 0]   # bucket-wise, not concatenated
    assert series["n"] == 4 and abs(series["s"] - 4.5) < 1e-9
    assert agg.merged_value("lat_seconds") == 4
    p50 = agg.merged_percentile("lat_seconds", 0.5)
    assert 0.0 < p50 <= 1.0
    text = agg.render()
    assert 'lat_seconds_bucket{le="1"} 3' in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text


def test_federation_version_mismatch_rejected():
    Aggregator, _ = _fed()
    from pybitmessage_tpu.observability.federation import \
        FEDERATION_VERSION
    agg = Aggregator()
    before = REGISTRY.sample("federation_rejected_total",
                             {"reason": "version"})
    ack = agg.ingest({"v": FEDERATION_VERSION + 1, "node": "x",
                      "seq": 1, "full": True, "metrics": {}})
    assert ack["ok"] is False and ack["reason"] == "version"
    assert REGISTRY.sample("federation_rejected_total",
                           {"reason": "version"}) == before + 1
    # malformed pushes are refused without raising
    assert agg.ingest(None)["ok"] is False
    assert agg.ingest({"v": FEDERATION_VERSION})["ok"] is False
    assert agg.status()["fleet"]["nodes"] == 0


def test_federation_delta_encoding_and_resync():
    """Second push carries ONLY changed series, yet the merged view
    stays complete; a delta for an unknown node forces a full
    resync."""
    Aggregator, FederationPublisher = _fed()
    agg = Aggregator()
    reg = Registry()
    c1 = reg.counter("a_total", "a")
    c2 = reg.counter("b_total", "b")
    c1.inc(1)
    c2.inc(7)
    pub = FederationPublisher("n", reg, transport=agg.ingest)
    push1, _ = pub.build_push()
    assert push1["full"] and set(push1["metrics"]) == {"a_total",
                                                       "b_total"}
    assert agg.ingest(push1)["ok"]
    pub._settle({"ok": True}, __import__(
        "pybitmessage_tpu.observability.federation",
        fromlist=["mergeable_snapshot"]).mergeable_snapshot(reg))
    c1.inc(2)  # only a_total changes
    push2, _ = pub.build_push()
    assert not push2["full"]
    assert set(push2["metrics"]) == {"a_total"}
    assert agg.ingest(push2)["ok"]
    assert agg.merged_value("a_total") == 3
    assert agg.merged_value("b_total") == 7   # unchanged series kept
    # a delta reaching an aggregator that never saw the node: resync
    agg2 = Aggregator()
    pub2 = FederationPublisher("n", reg, transport=agg2.ingest)
    pub2._acked = {}  # pretend something was acked -> builds a delta
    pub2.seq = 5
    ack = agg2.ingest(pub2.build_push()[0])
    assert ack["ok"] is False and ack["reason"] == "resync"
    # the publisher reacts by going full on the next push
    pub2._settle(ack, {})
    push_full, _ = pub2.build_push()
    assert push_full["full"]
    assert agg2.ingest(push_full)["ok"]


def test_federation_sequence_gap_forces_resync():
    Aggregator, FederationPublisher = _fed()
    agg = Aggregator()
    reg = Registry()
    reg.counter("g_total", "g").inc()
    pub = FederationPublisher("n", reg, transport=agg.ingest)
    assert pub.push_once()["ok"]
    pub.seq += 3   # simulate lost pushes
    ack = pub.push_once()
    assert ack["ok"] is False and ack["reason"] == "resync"
    # next push self-heals as full
    assert pub.push_once()["ok"]
    assert agg.merged_value("g_total") == 1


def test_federation_status_health_verdicts():
    Aggregator, FederationPublisher = _fed()
    agg = Aggregator(expiry=0.5, clock=lambda: 100.0)
    reg = Registry()
    pub = FederationPublisher(
        "sick", reg, transport=agg.ingest,
        health=lambda: {"loop": {"status": "degraded", "lagP99Ms": 80}},
        skew=lambda: 1.5)
    pub.push_once()
    FederationPublisher(
        "fine", reg, transport=agg.ingest,
        health=lambda: {"loop": {"status": "ok"}}).push_once()
    status = agg.status()
    assert status["nodes"]["sick"]["verdict"] == "degraded"
    assert status["nodes"]["sick"]["skewSeconds"] == 1.5
    assert status["nodes"]["fine"]["verdict"] == "ok"
    assert status["fleet"] == {"nodes": 2, "degraded": 1, "stale": 0,
                               "ok": 1}
    # stale: no push within expiry
    agg.clock = lambda: 10_000.0
    assert agg.status()["nodes"]["fine"]["verdict"] == "stale"


def test_federated_mesh_runs_real_federation_path():
    """ISSUE 9 tentpole c: the simulated mesh's propagation and byte
    figures come from MERGED per-node snapshots pushed through the
    real publisher/aggregator machinery."""
    import asyncio
    import os

    from pybitmessage_tpu.sync.mesh import Mesh

    async def run():
        mesh = Mesh(6, sync=True, fanout=1, federation=True,
                    federate_every=2)
        mesh.seed(0, [b"\x05" * 32])
        await mesh.establish()
        for i in range(8):
            mesh.inject(i % 6, os.urandom(32))
            await mesh.tick()
        await mesh.run_until_converged()
        mesh.federate_once()
        return mesh

    mesh = asyncio.run(run())
    prop = mesh.federated_propagation_percentiles()
    assert prop is not None and prop["count"] >= 8
    assert prop["p50"] <= prop["p99"]
    bpd = mesh.federated_bytes_per_delivered()
    assert bpd is not None and bpd > 0
    assert mesh.aggregator.status()["fleet"]["nodes"] == 6
    assert mesh.federation_seconds > 0


# ---------------------------------------------------------------------------
# flight recorder merge (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


def _flightrec_merge():
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).parent.parent / "tools"
            / "flightrec_merge.py")
    spec = importlib.util.spec_from_file_location("flightrec_merge", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flightrec_dump_records_skew_and_node():
    from pybitmessage_tpu.observability import FlightRecorder
    rec = FlightRecorder(maxlen=8)
    rec.node_id = "deadbeef"
    rec.skew_provider = lambda: 2.5
    rec.record("breaker", name="x")
    out = rec.dump_record("api")
    assert out["node"] == "deadbeef"
    assert out["skew"] == 2.5
    assert out["events"][-1]["kind"] == "breaker"
    # a broken provider degrades to 0.0, never fails the dump
    rec.skew_provider = lambda: 1 / 0
    assert rec.dump_record("api")["skew"] == 0.0


def test_flightrec_merge_normalizes_skew():
    """Two nodes' dumps with disagreeing clocks merge into one
    causally-ordered timeline after skew normalization."""
    fm = _flightrec_merge()
    # nodeA's clock runs 5s ahead: its raw t=105 happened at ref t=100
    dump_a = {"node": "A", "skew": 5.0, "events": [
        {"kind": "breaker", "t": 105.0, "seq": 1},
        {"kind": "chaos", "t": 107.0, "seq": 2}]}
    dump_b = {"node": "B", "skew": 0.0, "events": [
        {"kind": "stall", "t": 101.0, "seq": 1}]}
    merged = fm.merge([dump_a, dump_b])
    assert [e["kind"] for e in merged] == ["breaker", "stall", "chaos"]
    assert merged[0]["t_norm"] == 100.0
    # raw-t order would have been wrong: stall, breaker, chaos
    text = fm.render_text(merged)
    assert "breaker" in text.splitlines()[0]


def test_flightrec_merge_parses_log_lines_and_json():
    import json as _json
    fm = _flightrec_merge()
    dumps = fm.parse_dumps(_json.dumps(
        {"node": "n1", "skew": 1.0,
         "events": [{"kind": "x", "t": 1.0, "seq": 1}]}))
    assert dumps[0]["node"] == "n1"
    log = ("2026-08-03 INFO noise\n"
           "2026-08-03 WARNING flightrec_dump trigger=stall events=1 "
           '{"node": "n2", "skew": 0.0, "events": '
           '[{"kind": "stall", "t": 2.0, "seq": 1}]}\n')
    dumps = fm.parse_dumps(log, source="debug.log")
    assert dumps[0]["node"] == "n2"
    assert dumps[0]["events"][0]["kind"] == "stall"
    # legacy bare-array dumps: skew 0, node falls back to the source
    dumps = fm.parse_dumps('[{"kind": "y", "t": 3.0, "seq": 1}]',
                           source="old.json")
    assert dumps[0]["skew"] == 0.0 and dumps[0]["node"] == "old.json"
    with pytest.raises(ValueError):
        fm.parse_dumps("no dumps here", source="empty.log")


# ---------------------------------------------------------------------------
# wire trace context over a real two-node TCP pair (ISSUE 9 tentpole a)
# ---------------------------------------------------------------------------


def _trace_node(trace: bool = True, interval: float = 0.2):
    """Two-node-pattern node builder (extends test_sync.py's
    _sync_node) with the NODE_TRACE service bit toggleable."""
    from pybitmessage_tpu.models.constants import NODE_SYNC, NODE_TRACE
    from pybitmessage_tpu.network.dandelion import Dandelion
    from pybitmessage_tpu.network.pool import ConnectionPool, NodeContext
    from pybitmessage_tpu.storage import Database, Inventory, KnownNodes
    from pybitmessage_tpu.sync import InventoryDigest, Reconciler

    inv = Inventory(Database(":memory:"))
    ctx = NodeContext(inventory=inv, knownnodes=KnownNodes(),
                      dandelion=Dandelion(enabled=False), port=0,
                      allow_private_peers=True, announce_buckets=1,
                      pow_ntpb=1, pow_extra=1)
    pool = ConnectionPool(ctx, listen_host="127.0.0.1")
    digest = InventoryDigest()
    inv.attach_digest(digest)
    pool.reconciler = Reconciler(pool, digest=digest, interval=interval)
    ctx.services |= NODE_SYNC
    if trace:
        ctx.services |= NODE_TRACE
    return ctx, pool


def _traced_object(body: bytes, ttl: int = 3600):
    from pybitmessage_tpu.models.objects import serialize_object
    from pybitmessage_tpu.models.pow_math import (pow_initial_hash,
                                                  pow_target)
    from pybitmessage_tpu.pow import python_solve

    expires = int(time.time()) + ttl
    obj = serialize_object(expires, 2, 1, 1, body)
    target = pow_target(len(obj), ttl, 1, 1, clamp=False)
    nonce, _ = python_solve(pow_initial_hash(obj[8:]), target)
    return nonce.to_bytes(8, "big") + obj[8:], expires


async def _await_until(predicate, timeout=25.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        await asyncio.sleep(0.05)
    return False


def _spy_object_commands(conn):
    """Instance-level capture of object/tobject frames reaching one
    connection (the read loop resolves handlers via getattr, so an
    instance attribute shadows the class method)."""
    seen = {"tobject": [], "object": []}
    orig_tobj = conn.cmd_tobject
    orig_obj = conn.cmd_object

    # snapshot to bytes: the zero-copy read loop hands these handlers
    # memoryviews over a pooled buffer that is reused after the packet
    async def spy_tobj(payload, **kw):
        seen["tobject"].append(bytes(payload))
        await orig_tobj(payload, **kw)

    async def spy_obj(payload, **kw):
        seen["object"].append(bytes(payload))
        await orig_obj(payload, **kw)

    conn.cmd_tobject = spy_tobj
    conn.cmd_object = spy_obj
    return seen


@pytest.mark.asyncio
async def test_trace_ctx_roundtrips_two_real_tcp_nodes():
    """Negotiation + propagation end to end: both ends advertise
    NODE_TRACE, so an object pushed A->B travels as `tobject` carrying
    the trace context, B's skew estimator samples it, and B's
    timeline adopts A's trace id."""
    from pybitmessage_tpu.observability import LIFECYCLE, TraceContext
    from pybitmessage_tpu.observability.tracing import TRACE_CTX_LEN
    from pybitmessage_tpu.storage import Peer
    from pybitmessage_tpu.utils.hashes import inventory_hash

    ctx_a, pool_a = _trace_node()
    ctx_b, pool_b = _trace_node()
    await pool_a.start()
    await pool_b.start(listen=False)
    try:
        conn = await pool_b.connect_to(
            Peer("127.0.0.1", pool_a.listen_port))
        assert conn is not None
        assert await _await_until(lambda: conn.fully_established)
        assert conn.trace_negotiated
        seen = _spy_object_commands(conn)

        payload, expires = _traced_object(b"traced push")
        h = inventory_hash(payload)
        ctx_a.inventory.add(h, 2, 1, payload, expires)
        pool_a.announce_object(h, local=False)
        assert await _await_until(lambda: h in ctx_b.inventory), \
            "object did not propagate"
        # the push crossed as tobject (trace-context-prefixed) ...
        assert seen["tobject"], "no tobject frame reached B"
        wire_ctx = TraceContext.decode(seen["tobject"][0][:TRACE_CTX_LEN])
        # ... carrying A's trace id for this object, which B adopted
        meta = LIFECYCLE.trace_meta(h)
        assert meta is not None
        assert wire_ctx.trace_id == meta["trace_id"]
        assert wire_ctx.parent_span == meta["span"]
        # skew estimator sampled the context's send timestamp;
        # loopback clocks agree, so the bounded estimate is tiny
        assert conn.skew.samples >= 1
        assert abs(conn.skew.offset()) < 5.0
        LIFECYCLE.discard(h)
    finally:
        await pool_b.stop()
        await pool_a.stop()


@pytest.mark.asyncio
async def test_trace_ctx_silent_for_legacy_peer():
    """Degradation: against a peer without NODE_TRACE the wire is
    byte-identical to the classic protocol — plain `object` frames,
    no trailers on sync rounds, zero trace contexts parsed."""
    from pybitmessage_tpu.storage import Peer
    from pybitmessage_tpu.utils.hashes import inventory_hash

    ctx_a, pool_a = _trace_node(trace=True)
    ctx_b, pool_b = _trace_node(trace=False)   # legacy end
    await pool_a.start()
    await pool_b.start(listen=False)
    try:
        conn = await pool_b.connect_to(
            Peer("127.0.0.1", pool_a.listen_port))
        assert await _await_until(lambda: conn.fully_established)
        assert not conn.trace_negotiated
        seen = _spy_object_commands(conn)

        payload, expires = _traced_object(b"legacy push")
        h = inventory_hash(payload)
        ctx_a.inventory.add(h, 2, 1, payload, expires)
        pool_a.announce_object(h, local=False)
        assert await _await_until(lambda: h in ctx_b.inventory), \
            "object did not propagate to the legacy peer"
        # classic frames only, the payload bit-exact, nothing sampled
        assert not seen["tobject"], "tobject sent to a legacy peer"
        assert payload in seen["object"]
        assert conn.skew.samples == 0
    finally:
        await pool_b.stop()
        await pool_a.stop()
