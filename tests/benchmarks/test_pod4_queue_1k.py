"""The cell ``pod4_queue_1k`` (ISSUE 37), on the CPU.

The entries of ``BENCHMARK.json`` are held to what the issue names:
one configuration, ``sender_queue_1k_pod4`` (``sender_queue_1k`` key
for key, plus the layout over four chips), one cell on four chips
with the traffic file ``queue_1k`` uses, and eleven per-layer metrics
that list that cell and four-chip cells only (``pod4_burst_64`` joined
them with ISSUE 38).  Each new reader is read on a hand-made
recorded window with two device planes, without a trace, and on the
window of a program that has none of the series (the parent's).  The
cell is rehearsed in ``tests/test_pod4_rehearsal.py``: a file of its
own outside this directory, so that its minute of compiling for four
devices does not fall on the suite's first minute, when every file
here times a one-second window in a process of its own.
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

CELL = "pod4_queue_1k"
CONFIG = "sender_queue_1k_pod4"
NEW_LAYERS = {
    # name: (unit, better, source, layer)
    "chip_busy_share_min": ("%", "higher", "device_trace", "device"),
    "chip_launch_share_max": ("%", "lower", "program_counter",
                              "planner/pipeline"),
    "kernel_mhash_per_s.pod4": ("MH/s", "higher", "device_trace",
                                "kernels"),
    "useful_trial_share.pod4": ("%", "higher", "program_counter",
                                "kernels"),
    "live_slot_share.pod4": ("%", "higher", "program_counter",
                             "planner/pipeline"),
    "pow_wait_ms.pod4": ("ms", "lower", "program_counter", "send queue"),
    "pipeline_host_ms_per_launch.pod4": ("ms/launch", "lower",
                                         "program_span",
                                         "planner/pipeline"),
    "sender_host_ms_per_msg.pod4": ("ms/msg", "lower", "program_span",
                                    "sender"),
    "speculated_launch_share.pod4": ("%", "lower", "program_counter",
                                     "planner/pipeline"),
    "slot_refills_per_msg.pod4": ("refills/msg", "higher",
                                  "program_counter", "planner/pipeline"),
    "program_lowerings_in_window.pod4": ("count", "lower",
                                         "program_counter",
                                         "planner/pipeline"),
}
#: the readers that read the trace: None on an untraced window
NEED_TRACE = {"chip_busy_share_min", "kernel_mhash_per_s.pod4",
              "useful_trial_share.pod4",
              "pipeline_host_ms_per_launch.pod4",
              "sender_host_ms_per_msg.pod4"}


# -- the entries --------------------------------------------------------


def test_the_cell_is_the_one_the_issue_names():
    bench = harness.load(REPO, CELL)
    assert bench.cell == {
        "name": CELL, "config": CONFIG, "traffic": "backlog_1k",
        "chips": 4, "why": bench.cell["why"]}
    assert len(bench.cell["why"]) <= 200
    # the traffic file is queue_1k's, byte for byte the same file
    assert bench.traffic == harness.load(REPO, "queue_1k").traffic
    # it is one of the four-chip cells, which cost four times the chip
    # time and are at most half of all cells, rounded down
    four = [c["name"] for c in bench.spec["workloads"]
            if c["chips"] == 4]
    assert CELL in four
    assert len(four) <= len(bench.spec["workloads"]) // 2


def test_the_configuration_is_sender_queue_1k_on_four_chips():
    bench = harness.load(REPO, CELL)
    cfg, one = bench.config, harness.load(REPO, "queue_1k").config
    for key in ("topology", "test_mode", "ntpb", "extra", "ttl", "acks",
                "recipient_on_host", "queue_objects", "solve_backends",
                "guarantees", "object_kinds", "mixed_extra_bytes"):
        assert cfg[key] == one[key], key
    assert cfg["chips"] == 4 and "chips" not in one
    for key in ("object_kinds", "mixed_extra_bytes"):
        assert cfg["reduced"][key] == one["reduced"][key]
    assert cfg["assumed"]["body_bytes"] == one["assumed"]["body_bytes"]
    assert "nonce range" in cfg["assumed"]["split_by_object"]
    assert set(cfg["layout"]) == {"node", "queue", "object",
                                  "lone_object", "deployment"}
    entry = [c for c in bench.spec["configs"] if c["name"] == CONFIG][0]
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert entry["reduced"] == ["chips", "object_kinds",
                                "mixed_extra_bytes"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert "config 5" in entry["source"] and "config 2" in entry["source"]
    assert all(len(entry[k]) <= 200 for k in ("source", "why"))


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_a_layer_metric_lists_the_cell_and_four_chip_cells_only(name):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    unit, better, source, layer = NEW_LAYERS[name]
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "sent_msgs_per_s"}
    assert listed[0] == CELL
    chips = {c["name"]: c["chips"] for c in spec["workloads"]}
    assert all(chips[cell] == 4 for cell in listed), listed
    assert (REPO / "benchmarks" / "layers" / (name + ".py")).exists()


def test_the_cell_reports_the_metrics_that_list_no_cells():
    bench = harness.load(REPO, CELL)
    assert set(NEW_LAYERS) | {"off_device_solves", "compiles_in_window",
                              "device_idle_share"} \
        <= {m["name"] for m in bench.metrics("per_layer")}
    # at least these, so that a later append turns nothing red; its
    # outbox is filled before the window, so submit-to-sent is a place
    # in the queue and no latency (ISSUE 47)
    ends = {m["name"] for m in bench.metrics("end_to_end")}
    assert ends >= {"sent_msgs_per_s", "setup_s"}
    assert not ends & {"send_p50_ms", "send_p90_ms"}
    # and no cell that was there reports a metric of this one
    for cell in ("queue_1k", "burst_send_64", "chan_storm_256",
                 "single_send"):
        theirs = {m["name"] for m in
                  harness.load(REPO, cell).metrics("per_layer")}
        assert not theirs & set(NEW_LAYERS), cell


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


def _recorded():
    """A window of ten seconds on two device planes: chip 0 computes
    nine of them in three launches, chip 1 six in two, the first of
    which began a second before the window; the host's spans lie where
    the loop that drives both would put them."""
    module = "jit_pallas_batch_search(123)"
    op = "%pallas_batch_search.1"

    def plane(runs):
        return [[line, name, s, d] for s, d in runs
                for line, name in (("XLA Modules", module),
                                   ("XLA Ops", op))]

    host = [["python3", tracereduce.WINDOW_SPAN, 100.0, 10.0]]
    for k in range(5):
        host.append(["python3", "pow.launch", 100.0 + 2 * k, 0.004])
        host.append(["python3", "pow.harvest", 101.0 + 2 * k, 0.006])
    for k in range(20):
        host.append(["python3", "sender.sign", 100.2 + 0.4 * k, 0.001])
        host.append(["python3", "sender.encrypt", 100.3 + 0.4 * k, 0.002])
    return {"device": {
        "/device:TPU:0": plane([(100.0, 3.0), (103.5, 3.0), (107.0, 3.0)]),
        "/device:TPU:1": plane([(99.0, 4.0), (105.0, 3.0)]),
        "/device:TPU:2": []}, "host": host}


def _window(*, traced: bool, counted: bool, cell: str = CELL):
    raw = _recorded()
    before = {NEEDED: 1e9, WAIT: (10.0, 4), LOWERED: 9.0} if counted else {}
    after = {NEEDED: 1e9 + 6e9, WAIT: (250.0, 84),
             (LAUNCHES, ("batch",)): 5.0, (LAUNCHES, ("slab",)): 0.0,
             (BY_DEVICE, ("0",)): 3.0, (BY_DEVICE, ("1",)): 2.0,
             (AHEAD, ("batch", "launched")): 1.0,
             (AHEAD, ("batch", "withheld")): 3.0,
             (REFILLS, ("batch",)): 36.0, LOWERED: 9.0,
             (SLOTS, ("batch", "live")): 160.0,
             (SLOTS, ("batch", "idle")): 160.0} if counted else {}
    # the window began 50 s before its last send was seen published
    sent = [types.SimpleNamespace(t_done=40.0 + i) for i in range(20)]
    launches = [{"program": "batch", "t": 10.0 + k, "trials": 1.5e9}
                for k in range(5)]
    window = harness.Window(
        bench=harness.load(REPO, cell), seconds=50.0, setup_s=60.0,
        sent=sent, counters=probes.Counters(before, after),
        launches=launches, verdict={"needed_trials": 10**12,
                                    "off_device_solves": 0},
        notes={"lowerings": 0})
    if traced:
        window.trace = tracereduce.reduce_trace(
            raw, {"batch": "pallas_batch_search"})
        window.notes["recorded_trace"] = raw
        window.notes["span_reduction"] = spanreduce.reduce_spans(
            raw, spanreduce.load_spans(REPO))
    return window


EXPECTED = {
    # the least busy plane: chip 1, 3 + 3 of the window's 10 seconds
    "chip_busy_share_min": 60.0,
    "chip_launch_share_max": 60.0,
    # 7.5e9 trials over the planes' mean kernel time, (9 + 6) / 2
    "kernel_mhash_per_s.pod4": 7.5e9 / 7.5 / 1e6,
    "useful_trial_share.pod4": 100.0 * 6e9 / 7.5e9,
    "live_slot_share.pod4": 50.0,
    "pow_wait_ms.pod4": 240.0 / 80 * 1e3,
    "pipeline_host_ms_per_launch.pod4": 5 * (4.0 + 6.0) / 5,
    "sender_host_ms_per_msg.pod4": 20 * (1.0 + 2.0) / 20,
    # one launch of five went ahead of an unread one of its group
    "speculated_launch_share.pod4": 20.0,
    # 36 objects entered through freed slots, 20 sends were published
    "slot_refills_per_msg.pod4": 1.8,
    "program_lowerings_in_window.pod4": 0.0,
}


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_a_new_reader_on_a_window_with_two_device_planes(name):
    assert set(EXPECTED) == set(NEW_LAYERS)
    assert _read(name, _window(traced=True, counted=True)) \
        == pytest.approx(EXPECTED[name])
    # without a trace the readers of the trace have nothing to read,
    untraced = _read(name, _window(traced=False, counted=True))
    if name in NEED_TRACE:
        assert untraced is None
    else:
        assert untraced == pytest.approx(EXPECTED[name])
    # and a program that has none of the series (the parent's, whose
    # pod loop counts no launch of the pipeline's) leaves all but the
    # device's own reading out of the line, and raises nothing
    parent = _read(name, _window(traced=True, counted=False))
    if name == "chip_busy_share_min":
        assert parent == pytest.approx(60.0)
    elif name == "sender_host_ms_per_msg.pod4":
        assert parent == pytest.approx(3.0)
    elif name == "kernel_mhash_per_s.pod4":
        assert parent == pytest.approx(EXPECTED[name])  # the launch log
    else:
        assert parent is None
    assert _read(name, _window(traced=False, counted=False)) is None


def test_the_program_has_the_series_and_spans_the_readers_read():
    from pybitmessage_tpu.observability import REGISTRY
    from pybitmessage_tpu.pow import pipeline, service     # noqa: F401
    from pybitmessage_tpu.workers import sender             # noqa: F401
    names = {fam.name for fam in REGISTRY.families()}
    assert {BY_DEVICE, LAUNCHES, SLOTS, NEEDED[0], WAIT[0]} <= names
    assert REGISTRY.get(BY_DEVICE).labelnames == ("device",)
