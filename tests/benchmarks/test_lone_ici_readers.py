"""The readers of the first-hit flag (ISSUE 49), on the CPU:
``executed_useful_share.lone4``, ``cancel_lag_steps.lone4`` and
``cancelled_lane_share.lone4``, and behind them
``kernel_mhash_per_s.ici4`` (the review's: the rate of the kernel that
now does the cell's device work, which ``kernel_mhash_per_s.lone4``'s
launch log cannot see; the executed trials over the trace's seconds of
the operation ``ici_search``), appended to ``BENCHMARK.json`` behind
everything it had, for ``pod4_single_send`` alone.  Each is read on a
hand-made recorded window of the counters ``pow/pipeline.py`` keeps for
a lone object whose lanes are ONE program over the chips
(``pow_pipeline_lone_lanes_total{outcome}``,
``pow_pipeline_lone_cancel_lag_steps``, the needed and the executed
trials of kind ``slab``), on the window of a program that has none of
them (the parent's: the line leaves the metric out and nothing raises),
and on a window in which no such launch was read.
"""

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import harness, probes  # noqa: E402

CELL = "pod4_single_send"
#: name: (unit, better, layer, source), in the order of the entries
READERS = {
    "executed_useful_share.lone4": ("%", "higher", "kernels",
                                    "program_counter"),
    "cancel_lag_steps.lone4": ("steps", "lower", "kernels",
                               "program_counter"),
    "cancelled_lane_share.lone4": ("%", "higher", "planner/pipeline",
                                   "program_counter"),
    "kernel_mhash_per_s.ici4": ("MH/s", "higher", "kernels",
                                "device_trace"),
}
LANES = "pow_pipeline_lone_lanes_total"
LAG = ("pow_pipeline_lone_cancel_lag_steps", ())
NEEDED = "pow_pipeline_needed_trials_total"
EXECUTED = "pow_pipeline_executed_trials_total"


#: a traced window's device operations, the planes' mean seconds: the
#: one program's kernel (two programs of it, as after a reload), its
#: all-gather, a copy
DEVICE_OPS = [["%ici_search.1", 3.0], ["%ici_search.2", 1.0],
              ["%all-gather", 0.01], ["%copy.3", 0.001]]


def _window(before: dict, after: dict, device_ops=DEVICE_OPS):
    return harness.Window(
        bench=harness.load(REPO, CELL), seconds=50.0, setup_s=30.0,
        sent=[], counters=probes.Counters(before, after), launches=[],
        verdict={}, notes={},
        trace=None if device_ops is None else {"device_ops": device_ops})


def _counted():
    """Forty solves of four lanes: 40 won, 110 cancelled (100 at the
    winner's step, 10 a step late), 6 that hit by themselves, 4 that
    ran out; the chips computed 5.0e9 trials and the searches needed
    4.6e9.  A queue's trials, of kind ``batch``, are nobody's here."""
    before = {(LANES, ("won",)): 5.0, (LANES, ("cancelled",)): 14.0,
              LAG: (3.0, 14), (NEEDED, ("slab",)): 1e9,
              (EXECUTED, ("slab",)): 2e9, (NEEDED, ("batch",)): 7e9,
              (EXECUTED, ("batch",)): 7e9}
    after = {(LANES, ("won",)): 45.0, (LANES, ("cancelled",)): 124.0,
             (LANES, ("own_hit",)): 6.0, (LANES, ("ran_out",)): 4.0,
             LAG: (13.0, 124), (NEEDED, ("slab",)): 1e9 + 4.6e9,
             (EXECUTED, ("slab",)): 2e9 + 5.0e9,
             (NEEDED, ("batch",)): 9e9, (EXECUTED, ("batch",)): 9.5e9}
    return _window(before, after)


EXPECTED = {
    "executed_useful_share.lone4": 92.0,
    # ten steps over 110 cancelled lanes
    "cancel_lag_steps.lone4": 10.0 / 110,
    # 110 of the 120 lanes that did not win
    "cancelled_lane_share.lone4": 100.0 * 110 / 120,
    # 5.0e9 trials in the kernel's 4.0 s a plane
    "kernel_mhash_per_s.ici4": 1250.0,
}


def _read(name, window):
    return harness.load_module(REPO, "layers", name).read(window)


def test_the_entries_are_appended_behind_all_the_benchmark_had():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index(list(READERS)[0])
    assert names[first:first + len(READERS)] == list(READERS)
    # behind every entry PR 48 left, found by name wherever later
    # appends leave them
    assert first > names.index("setup_trace_lower_s")
    assert len(set(names)) == len(names)
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("name", list(READERS))
def test_an_entry_lists_the_cell_alone_under_a_layer_that_is_there(name):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    unit, better, layer, source = READERS[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "sent_msgs_per_s", "workloads": [CELL]}
    assert layer in {m["layer"] for m in spec["per_layer"]
                     if m["name"] not in READERS}
    assert (REPO / "benchmarks" / "layers" / (name + ".py")).exists()
    # the cell reports it, and no other cell does
    for cell in spec["workloads"]:
        theirs = {m["name"] for m in harness.load(
            REPO, cell["name"]).metrics("per_layer")}
        assert (name in theirs) is (cell["name"] == CELL), cell["name"]


@pytest.mark.parametrize("name", list(READERS))
def test_a_reader_on_a_recorded_window(name):
    assert set(EXPECTED) == set(READERS)
    assert _read(name, _counted()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(READERS))
def test_a_program_without_the_series_leaves_the_metric_out(name):
    """The parent: the sender's series and none of these; and a program
    that has the families but read no such launch in the window."""
    wait = ("worker_pow_wait_seconds", ())
    parent = _window({wait: (10.0, 4)}, {wait: (10.6, 44)})
    assert _read(name, parent) is None
    idle = _counted()
    still = _window(idle.counters.after, idle.counters.after)
    assert _read(name, still) is None


def test_the_kernel_s_rate_needs_a_trace_that_holds_the_kernel():
    name = "kernel_mhash_per_s.ici4"
    counted = _counted().counters
    # an untraced run
    assert _read(name, _window(counted.before, counted.after,
                               device_ops=None)) is None
    # the parent's trace with this PR's files laid over: a launch a
    # lane, whose trials of kind ``slab`` are counted all the same
    lane = [["%pallas_search.1", 41.0], ["%copy.3", 0.001]]
    assert _read(name, _window(counted.before, counted.after,
                               device_ops=lane)) is None
    # the CPU's rehearsal: a trace with no device plane
    assert _read(name, _window(counted.before, counted.after,
                               device_ops=[])) is None
    # the kernel alone is read, not the gather beside it
    only = [["%all-gather", 9.0], ["%ici_search.1", 2.5]]
    assert _read(name, _window(counted.before, counted.after,
                               device_ops=only)) == pytest.approx(2000.0)


def test_a_window_of_winners_alone_has_no_share_of_the_losers():
    # every lane hit in the winner's step: nobody to cancel
    window = _window({}, {(LANES, ("won",)): 3.0})
    assert _read("cancelled_lane_share.lone4", window) is None
    window = _window({}, {(LANES, ("won",)): 3.0,
                          (LANES, ("own_hit",)): 9.0})
    assert _read("cancelled_lane_share.lone4", window) == 0.0


def test_the_parent_s_lay_out_reads_what_its_harvests_counted():
    """A launch a lane: the needed and executed trials of the launches
    that were read, the losers' unread ones in neither."""
    window = _window({}, {(NEEDED, ("slab",)): 3e9,
                          (EXECUTED, ("slab",)): 4e9})
    assert _read("executed_useful_share.lone4", window) \
        == pytest.approx(75.0)
    assert _read("cancel_lag_steps.lone4", window) is None


def test_the_program_has_the_series_the_readers_read():
    from pybitmessage_tpu.observability import REGISTRY
    from pybitmessage_tpu.pow import pipeline
    assert REGISTRY.get(LANES).labelnames == ("outcome",)
    assert REGISTRY.get(LAG[0]).labelnames == ()
    assert REGISTRY.get(NEEDED).labelnames \
        == REGISTRY.get(EXECUTED).labelnames == ("kind",)
    assert set(pipeline.LONE_OUTCOMES) \
        == {"won", "cancelled", "own_hit", "ran_out"}
