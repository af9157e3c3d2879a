"""The cell ``pod4_storm_10k`` (ISSUE 46), on the CPU.

``storm_10k``'s traffic on a configuration of its own,
``chan_broadcaster_pod4``: ``chan_broadcaster`` key for key on a
four-chip host (BASELINE config 4 at its stated 10,000 on config 5's
host).  The entries of ``BENCHMARK.json`` are held to what the issue
names, BY NAME and never by position or count, so that the next PR that
appends a cell, a configuration or a metric does not turn this file
red: one configuration, one cell on four chips with the traffic file
``storm_10k`` uses, and sixteen per-layer metrics ``*.storm4`` that list
that cell alone.  Each new reader is read on a hand-made recorded
window with four device planes, without a trace, on the window of the
parent (every series but the four this PR adds) and on that of a
program that has none of the pipeline's series.  The cell is rehearsed
in ``tests/test_pod4_storm_rehearsal.py``, outside this directory
because it compiles for four devices.
"""

import json
import pathlib
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import (harness, probes, spanreduce,  # noqa: E402
                        tracereduce)

CELL = "pod4_storm_10k"
CONFIG = "chan_broadcaster_pod4"
PLANNER = "planner/pipeline"
#: name: (unit, better, source, layer, the reader whose entry it follows)
STORM4 = {
    "kernel_mhash_per_s.storm4": ("MH/s", "higher", "device_trace",
                                  "kernels", "kernel_mhash_per_s.queue"),
    "useful_trial_share.storm4": ("%", "higher", "program_counter",
                                  "kernels", "useful_trial_share.queue"),
    "chip_busy_share_min.storm4": ("%", "higher", "device_trace",
                                   "device", "chip_busy_share_min"),
    "chip_launch_share_max.storm4": ("%", "lower", "program_counter",
                                     PLANNER, "chip_launch_share_max"),
    "live_slot_share.storm4": ("%", "higher", "program_counter", PLANNER,
                               "live_slot_share"),
    "slot_refills_per_msg.storm4": ("refills/msg", "higher",
                                    "program_counter", PLANNER,
                                    "slot_refills_per_msg"),
    "speculated_launch_share.storm4": ("%", "lower", "program_counter",
                                       PLANNER, "speculated_launch_share"),
    "pipeline_host_ms_per_launch.storm4": (
        "ms/launch", "lower", "program_span", PLANNER,
        "pipeline_host_ms_per_launch.queue"),
    "program_lowerings_in_window.storm4": (
        "count", "lower", "program_counter", PLANNER,
        "program_lowerings_in_window"),
    "lane_inflight_idle_share.storm4": ("%", "lower", "device_trace",
                                        PLANNER,
                                        "lane_inflight_idle_share"),
    "lane_turn_idle_share.storm4": ("%", "lower", "device_trace", PLANNER,
                                    "lane_turn_idle_share"),
    "lane_starved_idle_share.storm4": ("%", "lower", "device_trace",
                                       PLANNER, "lane_starved_idle_share"),
    "pow_wait_ms.storm4": ("ms", "lower", "program_counter", "send queue",
                           "pow_wait_ms"),
    "sender_host_ms_per_msg.storm4": ("ms/msg", "lower", "program_span",
                                      "sender",
                                      "sender_host_ms_per_msg.queue"),
    "sender_admit_ms_per_msg.storm4": ("ms/msg", "lower",
                                       "program_counter", "sender", None),
    "crypto_pool_busy_share.storm4": ("%", "lower", "program_counter",
                                      "sender", None),
}
#: the two that read the series this PR adds to the program
NEW_SERIES_READERS = {"sender_admit_ms_per_msg.storm4",
                      "crypto_pool_busy_share.storm4"}
#: the three that follow an accepted reader's entry and are files of
#: their own: the cell's one solve is in no profiler session, so they
#: take the window to lie inside it (``layers/_lanes_fed.py``)
FED_LANES = {"lane_inflight_idle_share.storm4",
             "lane_turn_idle_share.storm4",
             "lane_starved_idle_share.storm4"}
#: the metrics that list no cells, which every cell reports
EVERY_CELL = {"off_device_solves", "compiles_in_window",
              "device_idle_share"}
#: the readers that read the trace: None on an untraced window
NEED_TRACE = {"kernel_mhash_per_s.storm4", "useful_trial_share.storm4",
              "chip_busy_share_min.storm4",
              "pipeline_host_ms_per_launch.storm4",
              "sender_host_ms_per_msg.storm4",
              "lane_inflight_idle_share.storm4",
              "lane_turn_idle_share.storm4",
              "lane_starved_idle_share.storm4"}
#: what names the deployment and not what it runs
OWN_KEYS = {"name", "stands_for", "source", "chips", "layout", "reduced",
            "assumed"}


def _spec() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


# -- the entries --------------------------------------------------------


def test_the_cell_is_the_one_the_issue_names():
    bench = harness.load(REPO, CELL)
    assert bench.cell == {
        "name": CELL, "config": CONFIG, "traffic": "storm_backlog_10k",
        "chips": 4, "why": bench.cell["why"]}
    assert 0 < len(bench.cell["why"]) <= 200
    assert "storm_10k" in bench.cell["why"]
    # one cell of that name, and one on that pair of configuration and
    # traffic
    cells = bench.spec["workloads"]
    assert [c["name"] for c in cells].count(CELL) == 1
    assert [(c["config"], c["traffic"]) for c in cells].count(
        (CONFIG, "storm_backlog_10k")) == 1
    # four-chip cells cost four times the chip time and are at most
    # half of all cells, rounded down
    four = [c["name"] for c in cells if c["chips"] == 4]
    assert CELL in four and len(four) <= len(cells) // 2


def test_the_traffic_file_is_storm_10k_s_untouched():
    bench = harness.load(REPO, CELL)
    assert bench.traffic == harness.load(REPO, "storm_10k").traffic == {
        "generator": "backlog", "send": "broadcast", "backlog": 10000,
        "report": 256, "body_bytes": [[1.0, 100, 300]],
        "warm_verify_batches": [], "warm_quiet_sweeps": 1,
        "warm_max_sweeps": 4}
    assert bench.config["queue_objects"] == bench.traffic["backlog"]


def _configs():
    return (harness.load(REPO, CELL).config,
            harness.load(REPO, "storm_10k").config)


def test_the_configuration_has_chan_broadcaster_s_keys_and_its_own():
    cfg, one = _configs()
    assert set(cfg) - OWN_KEYS == set(one) - OWN_KEYS
    assert set(cfg) - set(one) == {"chips", "layout"}
    assert cfg["name"] == CONFIG and cfg["chips"] == 4
    assert "chips" not in one


@pytest.mark.parametrize("key, value", [
    ("topology", "single"), ("chan_passphrase", "benchmark chan"),
    ("test_mode", False), ("ntpb", 1000), ("extra", 1000),
    ("ttl", 345600), ("acks", False), ("solve_backends", ["tpu-pallas"]),
    ("queue_objects", 10000)])
def test_the_configuration_is_chan_broadcaster_key_for_key(key, value):
    cfg, one = _configs()
    assert cfg[key] == one[key] == value


@pytest.mark.parametrize("guarantee", ["pow", "delivery", "tier"])
def test_a_guarantee_is_chan_broadcaster_s_word_for_word(guarantee):
    cfg, one = _configs()
    assert set(cfg["guarantees"]) == {"pow", "delivery", "tier"}
    assert cfg["guarantees"][guarantee] == one["guarantees"][guarantee]
    assert len(cfg["guarantees"][guarantee]) > 40


def test_the_layout_the_cut_and_what_is_assumed():
    cfg, one = _configs()
    assert set(cfg["layout"]) == {"node", "queue", "object", "deployment"}
    assert "one pipeline host loop" in cfg["layout"]["node"]
    assert "64 slots" in cfg["layout"]["queue"]
    assert "at least two a chip" in cfg["layout"]["queue"]
    assert "one chip" in cfg["layout"]["object"]
    assert "copy" in cfg["layout"]["object"]
    assert "v5e-8" in cfg["layout"]["deployment"]
    assert set(cfg["reduced"]) == {"chips"}
    assert "8 -> 4" in cfg["reduced"]["chips"]
    assert set(cfg["assumed"]) == {"body_bytes", "split_by_object",
                                   "prefix_dedup"}
    assert cfg["assumed"]["body_bytes"] == one["assumed"]["body_bytes"]
    assert "nonce range" in cfg["assumed"]["split_by_object"]
    assert "no-op" in cfg["assumed"]["prefix_dedup"]
    assert "config 5" in cfg["source"] and "config 4" in cfg["source"]


def test_the_configuration_s_entry():
    spec = _spec()
    (entry,) = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry == {
        "name": CONFIG, "source": entry["source"],
        "file": "benchmarks/configs/%s.json" % CONFIG,
        "reduced": ["chips"], "why": entry["why"]}
    assert entry["source"] == (
        "BASELINE.json config 5 (v5e-8 pod nonce-range partition, ICI "
        "first-hit early-exit) with config 4 (chan broadcast storm: 10k "
        "small broadcasts); PyBitmessage src/defaults.py 1000/1000; "
        "TTL 4 d")
    assert all(0 < len(entry[k]) <= 200 for k in ("source", "why"))
    assert sorted(entry["reduced"]) == sorted(
        harness.load(REPO, CELL).config["reduced"])
    # no other configuration's file, and a source of its own
    assert [c["file"] for c in spec["configs"]].count(entry["file"]) == 1
    assert [c["source"] for c in spec["configs"]].count(
        entry["source"]) == 1
    # chan_broadcaster's own entry is as it was
    (theirs,) = [c for c in spec["configs"]
                 if c["name"] == "chan_broadcaster"]
    assert theirs["reduced"] == []
    assert theirs["file"] == "benchmarks/configs/chan_broadcaster.json"


def test_the_benchmark_file_keeps_its_limits():
    spec = _spec()
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [m["name"] for m in spec[group]]
        assert len(set(names)) == len(names), group
    assert spec["run_seconds"] == 51


@pytest.mark.parametrize("name", list(STORM4))
def test_a_storm4_metric_lists_the_cell_alone(name):
    spec = _spec()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    unit, better, source, layer, like = STORM4[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "sent_msgs_per_s", "workloads": [CELL]}
    # a layer the benchmark already names, letter for letter
    assert layer in {m["layer"] for m in spec["per_layer"]
                     if not m["name"].endswith(".storm4")}
    path = REPO / "benchmarks" / "layers" / (name + ".py")
    assert path.exists()
    if like is None or name in FED_LANES:
        assert "_twin" not in path.read_text()
    else:
        # a twin of a reader that is there
        assert '_twin.of("%s")' % like in path.read_text()
    if name in FED_LANES:
        assert "_lanes_fed import idle_share" in path.read_text()
    if like is not None:
        # under the unit, the direction, the source and the layer of
        # the accepted entry it follows, which does not list this cell
        (theirs,) = [m for m in spec["per_layer"] if m["name"] == like]
        assert (theirs["unit"], theirs["better"], theirs["source"],
                theirs["layer"]) == (unit, better, source, layer)
        assert CELL not in theirs.get("workloads", ())


def test_the_cell_reports_the_storm4_metrics_and_those_of_every_cell():
    bench = harness.load(REPO, CELL)
    assert set(STORM4) | EVERY_CELL \
        <= {m["name"] for m in bench.metrics("per_layer")}
    # at least these, so that a later append turns nothing red; its
    # outbox is filled before the window, so submit-to-sent is a place
    # in the queue and no latency (ISSUE 47)
    ends = {m["name"] for m in bench.metrics("end_to_end")}
    assert ends >= {"sent_msgs_per_s", "setup_s"}
    assert not ends & {"send_p50_ms", "send_p90_ms"}
    # and no other cell reports a metric of this one
    for cell in bench.spec["workloads"]:
        if cell["name"] == CELL:
            continue
        theirs = {m["name"] for m in
                  harness.load(REPO, cell["name"]).metrics("per_layer")}
        assert not theirs & set(STORM4), cell["name"]


# -- the readers, on a hand-made recorded window ------------------------


def _read(name, window):
    return harness.load_module(REPO, "layers", name).read(window)


LAUNCHES = "pow_pipeline_launches_total"
BY_DEVICE = "pow_pipeline_device_launches_total"
SLOTS = "pow_pipeline_slots_total"
AHEAD = "pow_pipeline_speculation_total"
REFILLS = "pow_pipeline_refills_total"
LOWERED = ("jax_compile_events_total", ("lower",))
NEEDED = ("pow_pipeline_needed_trials_total", ("batch",))
WAIT = ("worker_pow_wait_seconds", ())
LANE_SECONDS = "pow_pipeline_lane_seconds_total"
ADMIT = ("sender_admit_seconds", ("broadcast",))
ADMIT_ROWS = ("sender_admit_rows_total", ("broadcast",))
BUSY = "cryptopool_busy_seconds_total"
QUEUE_WAIT = ("cryptopool_queue_wait_seconds", ("sender",))
NEW_SERIES = {ADMIT, ADMIT_ROWS, (BUSY, ("sender",)),
              (BUSY, ("processor",)), QUEUE_WAIT}
#: seconds of the window's ten that each chip computes
BUSY_S = (9.0, 8.5, 8.0, 9.5)


def _recorded():
    """A window of ten seconds on four device planes, each chip busy in
    two launches of the batch program (chip 2 the least: eight
    seconds); the host's spans lie where the one loop that drives all
    four would put them."""
    module = "jit_pallas_batch_search(123)"
    op = "%pallas_batch_search.1"

    def plane(busy):
        runs = [(100.1, busy / 2), (105.1, busy / 2)]
        return [[line, name, s, d] for s, d in runs
                for line, name in (("XLA Modules", module),
                                   ("XLA Ops", op))]

    host = [["python3", tracereduce.WINDOW_SPAN, 100.0, 10.0]]
    for k in range(8):
        host.append(["python3", "pow.launch", 100.0 + k, 0.004])
        host.append(["python3", "pow.harvest", 100.5 + k, 0.006])
    for k in range(20):
        host.append(["python3", "sender.sign", 100.2 + 0.4 * k, 0.001])
        host.append(["python3", "sender.encrypt", 100.3 + 0.4 * k, 0.002])
    return {"device": {"/device:TPU:%d" % k: plane(busy)
                       for k, busy in enumerate(BUSY_S)}, "host": host}


def _window(*, traced: bool, program: str = "change"):
    """``program``: ``change`` has every series; ``parent`` all but the
    four this PR adds; ``bare`` none of the pipeline's or the sender's
    (a program older than they are)."""
    raw = _recorded()
    before = {NEEDED: 1e9, WAIT: (10.0, 4), LOWERED: 9.0,
              ADMIT: (0.5, 100), ADMIT_ROWS: 25700.0,
              (BUSY, ("sender",)): 1.0, (BUSY, ("processor",)): 7.0,
              QUEUE_WAIT: (0.2, 100)}
    after = {NEEDED: 1e9 + 1.0395e10, WAIT: (56.0, 24), LOWERED: 9.0,
             (LAUNCHES, ("batch",)): 8.0, (LAUNCHES, ("slab",)): 0.0,
             (BY_DEVICE, ("0",)): 3.0, (BY_DEVICE, ("1",)): 2.0,
             (BY_DEVICE, ("2",)): 2.0, (BY_DEVICE, ("3",)): 1.0,
             (AHEAD, ("batch", "launched")): 0.0,
             (AHEAD, ("batch", "withheld")): 8.0,
             (REFILLS, ("batch",)): 21.0,
             (SLOTS, ("batch", "live")): 256.0,
             (SLOTS, ("batch", "idle")): 256.0,
             (LANE_SECONDS, ("0", "turn")): 1.0,
             ADMIT: (0.54, 121), ADMIT_ROWS: 25700.0 + 21 * 257,
             (BUSY, ("sender",)): 3.9, (BUSY, ("processor",)): 9.0,
             QUEUE_WAIT: (0.3, 121)}
    if program == "parent":
        before = {k: v for k, v in before.items() if k not in NEW_SERIES}
        after = {k: v for k, v in after.items() if k not in NEW_SERIES}
    elif program == "bare":
        before, after = {}, {}
    # the window began 50 s before its last send was seen published
    sent = [types.SimpleNamespace(t_done=40.0 + i) for i in range(20)]
    launches = [{"program": "batch", "t": 10.0 + k, "trials": 1.3125e9}
                for k in range(8)]
    window = harness.Window(
        bench=harness.load(REPO, CELL), seconds=50.0, setup_s=50.0,
        sent=sent, counters=probes.Counters(before, after),
        launches=launches, verdict={"needed_trials": 10**12,
                                    "off_device_solves": 0},
        notes={"lowerings": 0})
    if traced:
        window.trace = tracereduce.reduce_trace(
            raw, {"slab": "pallas_search", "batch": "pallas_batch_search"})
        window.notes["recorded_trace"] = raw
        window.notes["span_reduction"] = spanreduce.reduce_spans(
            raw, spanreduce.load_spans(REPO))
        # lanereduce's reduction, already made, as it reads a trace
        # that holds no solve span (the one solve outlives the
        # session): every idle second "between solves", ``inflight``
        # minus the other two.  Chip 0 idled 0.4 of its second in its
        # lane's turn, chip 2 0.4 of its two
        chips = {"/device:TPU:%d" % k: {
            "device": k, "idle_s": 10.0 - busy, "turn": turn,
            "starved": 0.0, "inflight": -turn, "between_solves":
            10.0 - busy} for k, (busy, turn) in enumerate(
                zip(BUSY_S, (0.4, 0.0, 0.4, 0.0)))}
        window.notes["lane_reduction"] = {
            "window_s": 10.0, "idle_s": 1.25,
            "idle_between_solves_s": 1.25, "idle_by_state": {
                "inflight": -0.2, "turn": 0.2, "starved": 0.0},
            "chips": chips}
    return window


EXPECTED = {
    # 1.05e10 trials over the planes' mean kernel time, 35 / 4 seconds
    "kernel_mhash_per_s.storm4": 1.05e10 / 8.75 / 1e6,
    # what the harvests credited over what the four chips computed
    "useful_trial_share.storm4": 99.0,
    # the least busy plane: chip 2
    "chip_busy_share_min.storm4": 80.0,
    # chip 0 took three launches of eight
    "chip_launch_share_max.storm4": 37.5,
    "live_slot_share.storm4": 50.0,
    # 21 objects entered through freed slots, 20 were published
    "slot_refills_per_msg.storm4": 1.05,
    "speculated_launch_share.storm4": 0.0,
    "pipeline_host_ms_per_launch.storm4": 8 * (4.0 + 6.0) / 8,
    "program_lowerings_in_window.storm4": 0.0,
    # the planes idle 1.25 s of ten on average, 0.2 of it in a turn
    "lane_inflight_idle_share.storm4": 10.5,
    "lane_turn_idle_share.storm4": 2.0,
    "lane_starved_idle_share.storm4": 0.0,
    "pow_wait_ms.storm4": 46.0 / 20 * 1e3,
    "sender_host_ms_per_msg.storm4": 20 * (1.0 + 2.0) / 20,
    # 21 passes took 40 ms; 20 broadcasts were published
    "sender_admit_ms_per_msg.storm4": 2.0,
    # the sender's thread worked 2.9 of the 10 seconds between the
    # traced window's snapshots (the processor's pool is another's)
    "crypto_pool_busy_share.storm4": 29.0,
}
#: what a reader gives on a program with none of the program's series:
#: what the trace and the launch log alone can say
BARE = {"kernel_mhash_per_s.storm4": EXPECTED["kernel_mhash_per_s.storm4"],
        "chip_busy_share_min.storm4": 80.0,
        "sender_host_ms_per_msg.storm4": 3.0}


@pytest.mark.parametrize("name", list(STORM4))
def test_a_new_reader_on_a_window_with_four_device_planes(name):
    assert set(EXPECTED) == set(STORM4)
    window = _window(traced=True)
    assert window.trace["device_planes"] == 4
    assert _read(name, window) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(STORM4))
def test_a_new_reader_without_a_trace(name):
    untraced = _read(name, _window(traced=False))
    if name in NEED_TRACE:
        # the readers of the trace have nothing to read
        assert untraced is None
    elif name == "crypto_pool_busy_share.storm4":
        # the snapshots then lie at the ends of the window's 50 seconds
        assert untraced == pytest.approx(100.0 * 2.9 / 50.0)
    else:
        assert untraced == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(STORM4))
def test_a_new_reader_on_the_parent_s_window(name):
    """The parent has every series but the four this PR adds: the two
    readers of those leave their metric out of the line and raise
    nothing, the twins read what they read on the change."""
    parent = _read(name, _window(traced=True, program="parent"))
    if name in NEW_SERIES_READERS:
        assert parent is None
    else:
        assert parent == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(STORM4))
def test_a_new_reader_on_a_program_without_any_of_the_series(name):
    bare = _read(name, _window(traced=True, program="bare"))
    if name in BARE:
        assert bare == pytest.approx(BARE[name])
    else:
        assert bare is None
    assert _read(name, _window(traced=False, program="bare")) is None


def test_the_admission_reader_counts_every_kind_of_sweep():
    """``sender_admit_seconds`` is labelled by the sweep's kind; the
    reader sums them (a chan operator's node sends no message, so the
    cell's reading is the broadcasts')."""
    window = _window(traced=True)
    window.counters.after[("sender_admit_seconds", ("message",))] \
        = (0.02, 4)
    assert _read("sender_admit_ms_per_msg.storm4", window) \
        == pytest.approx(3.0)
    # nothing published: nothing to divide by
    window = _window(traced=True)
    window.sent.clear()
    assert _read("sender_admit_ms_per_msg.storm4", window) is None


def test_the_lane_shares_add_up_to_the_device_s_idle_share():
    """The window lies inside the one fed solve: no idle second of it
    is between two solves, and the accepted readers, which need the
    solve's span in the trace, would read ``inflight`` under zero."""
    window = _window(traced=True)
    shares = [_read("lane_%s_idle_share.storm4" % state, window)
              for state in ("inflight", "turn", "starved")]
    assert shares == [pytest.approx(10.5), pytest.approx(2.0), 0.0]
    assert sum(shares) == pytest.approx(
        _read("device_idle_share", window))
    assert _read("lane_inflight_idle_share", window) \
        == pytest.approx(-2.0)


def test_the_program_has_the_series_the_new_readers_read():
    from pybitmessage_tpu.observability import REGISTRY
    from pybitmessage_tpu.workers import cryptopool, sender
    assert REGISTRY.get(ADMIT[0]).labelnames == ("kind",)
    assert REGISTRY.get(ADMIT[0]).kind == "histogram"
    assert REGISTRY.get(ADMIT_ROWS[0]).labelnames == ("kind",)
    assert REGISTRY.get(BUSY).labelnames == ("pool",)
    assert REGISTRY.get(BUSY).kind == "counter"
    assert REGISTRY.get(QUEUE_WAIT[0]).labelnames == ("pool",)
    assert REGISTRY.get(QUEUE_WAIT[0]).kind == "histogram"
    # the sender's pool is the one the reader's label names, and it
    # stays at one thread (PERF.md section 6, PR 33)
    assert sender.SendWorker.crypto.name == "sender"
    assert sender.SendWorker.crypto.size == 1
    assert cryptopool.CryptoPool().name == "processor"
    # a snapshot of the registry has the sender's series from the
    # start, so a window in which the thread never worked reads 0
    snap = probes.registry_snapshot()
    assert (BUSY, ("sender",)) in snap
