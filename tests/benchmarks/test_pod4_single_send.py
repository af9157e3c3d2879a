"""The cell ``pod4_single_send`` (ISSUE 43), on the CPU.

``single_send``'s traffic on a configuration of its own,
``sender_lone_pod4``: ``sender_default`` key for key on a four-chip
host, where every solve is ONE object whose nonce space is shared out
over the chips (BASELINE config 5 as it is written).  The entries of
``BENCHMARK.json`` are held to what the issue names: one configuration,
one cell on four chips with the traffic file ``single_send`` uses, and
twelve per-layer metrics ``*.lone4`` that list that cell alone,
appended after everything the benchmark had.  Each new reader is read
on a hand-made recorded window with four device planes, without a
trace, and on the window of a program that has none of the series (the
parent's, whose partition counts no launch of the pipeline's).  The
cell is rehearsed in ``tests/test_pod4_lone_rehearsal.py``, outside
this directory because it compiles for four devices.
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

CELL = "pod4_single_send"
CONFIG = "sender_lone_pod4"
PLANNER = "planner/pipeline"
#: name: (unit, better, source, layer, the reader it is a twin of), in
#: the order of the entries
LONE4 = {
    "kernel_mhash_per_s.lone4": ("MH/s", "higher", "device_trace",
                                 "kernels", "kernel_mhash_per_s.slab"),
    "useful_trial_share.lone4": ("%", "higher", "program_counter",
                                 "kernels", "useful_trial_share.queue"),
    "chip_busy_share_min.lone4": ("%", "higher", "device_trace",
                                  "device", "chip_busy_share_min"),
    "pow_wait_ms.lone4": ("ms", "lower", "program_counter", "send queue",
                          "pow_wait_ms"),
    "solves_per_msg.lone4": ("solves/msg", "lower", "program_counter",
                             "send queue", "solves_per_msg"),
    "partition_win_share.lone4": ("%", "higher", "program_counter",
                                  PLANNER, None),
    "pipeline_host_ms_per_launch.lone4": (
        "ms/launch", "lower", "program_span", PLANNER,
        "pipeline_host_ms_per_launch"),
    "sender_host_ms_per_msg.lone4": ("ms/msg", "lower", "program_span",
                                     "sender", "sender_host_ms_per_msg"),
    "program_lowerings_in_window.lone4": (
        "count", "lower", "program_counter", PLANNER,
        "program_lowerings_in_window"),
    "lane_inflight_idle_share.lone4": ("%", "lower", "device_trace",
                                       PLANNER, "lane_inflight_idle_share"),
    "lane_turn_idle_share.lone4": ("%", "lower", "device_trace", PLANNER,
                                   "lane_turn_idle_share"),
    "lane_starved_idle_share.lone4": ("%", "lower", "device_trace",
                                      PLANNER, "lane_starved_idle_share"),
}
#: the metrics that list no cells, which every cell reports
EVERY_CELL = {"off_device_solves", "compiles_in_window",
              "device_idle_share"}
#: the readers that read the trace: None on an untraced window
NEED_TRACE = {"kernel_mhash_per_s.lone4", "useful_trial_share.lone4",
              "chip_busy_share_min.lone4",
              "pipeline_host_ms_per_launch.lone4",
              "sender_host_ms_per_msg.lone4",
              "lane_inflight_idle_share.lone4",
              "lane_turn_idle_share.lone4",
              "lane_starved_idle_share.lone4"}


# -- the entries --------------------------------------------------------


def test_the_cell_is_the_one_the_issue_names():
    bench = harness.load(REPO, CELL)
    assert bench.cell == {
        "name": CELL, "config": CONFIG, "traffic": "one_at_a_time",
        "chips": 4, "why": bench.cell["why"]}
    assert 0 < len(bench.cell["why"]) <= 200
    assert "single_send" in bench.cell["why"]
    # the traffic file is single_send's, untouched
    single = harness.load(REPO, "single_send")
    assert bench.traffic == single.traffic == {
        "generator": "closed_loop", "send": "message", "sweep": 1,
        "body_bytes": [[1.0, 1000, 1000]], "warm_verify_batches": [],
        "warm_quiet_sweeps": 2, "warm_max_sweeps": 12}
    # appended behind the two four-chip cells that were there, found by
    # name wherever later appends leave it; of all cells at most half,
    # rounded down, are on four chips
    cells = bench.spec["workloads"]
    assert [c for c in cells if c["name"] == CELL] == [bench.cell]
    four = [c["name"] for c in cells if c["chips"] == 4]
    assert four[:3] == ["pod4_queue_1k", "pod4_burst_64", CELL]
    assert len(four) <= len(cells) // 2


def test_the_configuration_is_sender_default_on_four_chips():
    bench = harness.load(REPO, CELL)
    cfg, one = bench.config, harness.load(REPO, "single_send").config
    own = {"name", "stands_for", "source", "chips", "layout", "reduced",
           "assumed"}
    # key for key, but for what names the deployment; no queue
    assert set(cfg) - own == set(one) - own - {"queue_objects"}
    for key in set(cfg) - own:
        assert cfg[key] == one[key], key
    assert (cfg["topology"], cfg["test_mode"], cfg["ntpb"], cfg["extra"],
            cfg["ttl"], cfg["acks"], cfg["recipient_on_host"]) \
        == ("pair", False, 1000, 1000, 345600, True, True)
    assert cfg["guarantees"] == one["guarantees"]       # word for word
    assert cfg["solve_backends"] == ["tpu-pallas"]
    assert cfg["name"] == CONFIG and cfg["chips"] == 4
    assert "chips" not in one and "queue_objects" not in cfg
    assert set(cfg["layout"]) == {"node", "object", "queue", "deployment"}
    assert "first" in cfg["layout"]["object"]
    assert "v5e-8" in cfg["layout"]["deployment"]
    assert set(cfg["reduced"]) == {"chips"}
    assert "8 -> 4" in cfg["reduced"]["chips"]
    assert set(cfg["assumed"]) == {"body_bytes",
                                   "first_hit_at_the_harvest"}
    assert "config 5" in cfg["source"] and "config 1" in cfg["source"]
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    assert entry == {
        "name": CONFIG, "source": entry["source"],
        "file": "benchmarks/configs/%s.json" % CONFIG,
        "reduced": ["chips"], "why": entry["why"]}
    assert entry["source"] == (
        "BASELINE.json config 5 (v5e-8 pod nonce-range partition, ICI "
        "first-hit early-exit across chips) with config 1 (single msg "
        "object PoW at network default); PyBitmessage defaults.py "
        "1000/1000; TTL 4 d")
    assert all(0 < len(entry[k]) <= 200 for k in ("source", "why"))
    # no other configuration's file
    assert [c["file"] for c in bench.spec["configs"]].count(
        entry["file"]) == 1


def test_the_twelve_entries_are_appended_in_the_issue_s_order():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    # found by name, in the issue's order and side by side, wherever
    # later appends leave them
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index(list(LONE4)[0])
    assert names[first:first + len(LONE4)] == list(LONE4)
    assert len({m["name"] for m in spec["per_layer"]}) \
        == len(spec["per_layer"])
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("name", list(LONE4))
def test_a_lone4_metric_lists_the_cell_alone(name):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    unit, better, source, layer, twin = LONE4[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "sent_msgs_per_s", "workloads": [CELL]}
    # a layer the benchmark already names, letter for letter
    assert layer in {m["layer"] for m in spec["per_layer"]
                     if not m["name"].endswith(".lone4")}
    path = REPO / "benchmarks" / "layers" / (name + ".py")
    assert path.exists()
    if twin is not None:
        # a twin of a reader that is there, under its unit
        assert '_twin.of("%s")' % twin in path.read_text()
        (theirs,) = [m for m in spec["per_layer"] if m["name"] == twin]
        assert (theirs["unit"], theirs["better"], theirs["source"],
                theirs["layer"]) == (unit, better, source, layer)


def test_the_cell_reports_the_lone4_metrics_and_those_of_every_cell():
    bench = harness.load(REPO, CELL)
    assert set(LONE4) | EVERY_CELL \
        <= {m["name"] for m in bench.metrics("per_layer")}
    # at least these, so that a later append turns nothing red: a
    # closed-loop cell times every send by itself (ISSUE 47)
    assert {m["name"] for m in bench.metrics("end_to_end")} \
        >= {"sent_msgs_per_s", "setup_s", "send_p50_ms", "send_p90_ms"}
    # and no other cell reports a metric of this one
    for cell in bench.spec["workloads"]:
        if cell["name"] == CELL:
            continue
        theirs = {m["name"] for m in
                  harness.load(REPO, cell["name"]).metrics("per_layer")}
        assert not theirs & set(LONE4), cell["name"]


# -- the readers, on a hand-made recorded window ------------------------


def _read(name, window):
    return harness.load_module(REPO, "layers", name).read(window)


LAUNCHES = ("pow_pipeline_launches_total", ("slab",))
WINS = "pow_pipeline_lone_wins_total"
LOWERED = ("jax_compile_events_total", ("lower",))
NEEDED = ("pow_pipeline_needed_trials_total", ("slab",))
WAIT = ("worker_pow_wait_seconds", ())
BATCHES = ("pow_batch_size", ())
LANE_SECONDS = "pow_pipeline_lane_seconds_total"
#: seconds of the window's ten that each chip computes
BUSY = (6.0, 5.0, 4.0, 5.0)


def _recorded():
    """A window of ten seconds on four device planes, each chip busy in
    two launches of the slab program (chip 2 the least: four seconds);
    the host's spans lie where the loop that drives all four would put
    them."""
    module = "jit_pallas_search(123)"
    op = "%pallas_search.1"

    def plane(busy):
        runs = [(100.5, busy / 2), (105.5, busy / 2)]
        return [[line, name, s, d] for s, d in runs
                for line, name in (("XLA Modules", module),
                                   ("XLA Ops", op))]

    host = [["python3", tracereduce.WINDOW_SPAN, 100.0, 10.0]]
    for k in range(8):
        host.append(["python3", "pow.launch", 100.0 + k, 0.001])
    for k in range(4):
        host.append(["python3", "pow.harvest", 100.5 + 2 * k, 0.001])
    for k in range(20):
        host.append(["python3", "sender.sign", 100.2 + 0.4 * k, 0.001])
        host.append(["python3", "sender.encrypt", 100.3 + 0.4 * k, 0.002])
    return {"device": {"/device:TPU:%d" % k: plane(busy)
                       for k, busy in enumerate(BUSY)}, "host": host}


def _window(*, traced: bool, counted: bool):
    raw = _recorded()
    before = {NEEDED: 1e9, WAIT: (10.0, 4), LOWERED: 9.0,
              BATCHES: (7.0, 7)}
    after = {NEEDED: 1e9 + 1.45e9, WAIT: (10.64, 44), LOWERED: 9.0,
             BATCHES: (47.0, 47), LAUNCHES: 8.0,
             (WINS, ("0",)): 10.0, (WINS, ("1",)): 9.0,
             (WINS, ("2",)): 11.0, (WINS, ("3",)): 10.0,
             (LANE_SECONDS, ("0", "turn")): 1.0}
    if not counted:
        # the parent: the sender's and the service's series, none of
        # the pipeline's (its partition is a host loop of its own)
        before = {k: v for k, v in before.items() if k != NEEDED}
        after = {k: v for k, v in after.items()
                 if k in (WAIT, LOWERED, BATCHES)}
    # the window began 50 s before its last send was seen published
    sent = [types.SimpleNamespace(t_done=40.0 + i) for i in range(20)]
    launches = [{"program": "slab", "t": 10.0 + k, "trials": 7.25e8}
                for k in range(8)] if counted else []
    window = harness.Window(
        bench=harness.load(REPO, CELL), seconds=50.0, setup_s=30.0,
        sent=sent, counters=probes.Counters(before, after),
        launches=launches, verdict={"needed_trials": 10**20,
                                    "off_device_solves": 0},
        notes={"lowerings": 0})
    if traced:
        window.trace = tracereduce.reduce_trace(
            raw, {"slab": "pallas_search", "batch": "pallas_batch_search"})
        window.notes["recorded_trace"] = raw
        window.notes["span_reduction"] = spanreduce.reduce_spans(
            raw, spanreduce.load_spans(REPO))
        # lanereduce's reduction, already made: of the window's ten
        # seconds the planes' mean idles 0.5 with a launch in flight
        # and 0.9 in its lane's turn
        window.notes["lane_reduction"] = {
            "window_s": 10.0, "idle_by_state": {
                "inflight": 0.5 if counted else 1.4,
                "turn": 0.9 if counted else 0.0, "starved": 0.0}}
    return window


EXPECTED = {
    # 5.8e9 trials over the planes' mean kernel time, (6 + 5 + 4 + 5) / 4
    "kernel_mhash_per_s.lone4": 5.8e9 / 5.0 / 1e6,
    # what the harvests credited over what the four chips computed
    "useful_trial_share.lone4": 25.0,
    # the least busy plane: chip 2
    "chip_busy_share_min.lone4": 40.0,
    "pow_wait_ms.lone4": 0.64 / 40 * 1e3,
    "solves_per_msg.lone4": 2.0,
    # 30 of 40 solves were won by another lane than the object's own
    "partition_win_share.lone4": 75.0,
    "pipeline_host_ms_per_launch.lone4": (8 * 1.0 + 4 * 1.0) / 8,
    "sender_host_ms_per_msg.lone4": 3.0,
    "program_lowerings_in_window.lone4": 0.0,
    "lane_inflight_idle_share.lone4": 5.0,
    "lane_turn_idle_share.lone4": 9.0,
    "lane_starved_idle_share.lone4": 0.0,
}


@pytest.mark.parametrize("name", list(LONE4))
def test_a_new_reader_on_a_window_with_four_device_planes(name):
    assert set(EXPECTED) == set(LONE4)
    window = _window(traced=True, counted=True)
    assert window.trace["device_planes"] == 4
    assert _read(name, window) == pytest.approx(EXPECTED[name])
    # without a trace the readers of the trace have nothing to read
    untraced = _read(name, _window(traced=False, counted=True))
    if name in NEED_TRACE:
        assert untraced is None
    else:
        assert untraced == pytest.approx(EXPECTED[name])
    # and the parent (no launch in the log, no series of the
    # pipeline's) leaves out what it cannot give, and raises nothing
    parent = _read(name, _window(traced=True, counted=False))
    if name in ("kernel_mhash_per_s.lone4", "useful_trial_share.lone4",
                "partition_win_share.lone4",
                "pipeline_host_ms_per_launch.lone4",
                "lane_inflight_idle_share.lone4",
                "lane_turn_idle_share.lone4",
                "lane_starved_idle_share.lone4"):
        assert parent is None
    else:
        assert parent == pytest.approx(EXPECTED[name])


def test_the_lane_shares_of_a_program_that_keeps_lane_states_add_up():
    """The parent keeps ``pow_pipeline_lane_seconds_total`` and opens
    no interval in its partition: all of its idle inside a solve reads
    as ``inflight``."""
    window = _window(traced=True, counted=False)
    window.counters.after[(LANE_SECONDS, ("0", "turn"))] = 1.0
    shares = [_read("lane_%s_idle_share.lone4" % state, window)
              for state in ("inflight", "turn", "starved")]
    assert shares == [pytest.approx(14.0), 0.0, 0.0]


def test_the_program_has_the_series_the_new_reader_reads():
    from pybitmessage_tpu.observability import REGISTRY
    from pybitmessage_tpu.pow import pipeline     # noqa: F401
    assert REGISTRY.get(WINS).labelnames == ("lane",)
    assert REGISTRY.get(NEEDED[0]).labelnames == ("kind",)
