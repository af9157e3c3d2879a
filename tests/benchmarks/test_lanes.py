"""The pipeline driver's lane states in the benchmark (CPU only).

What is held here: the reduction of ``benchmarks/lanereduce.py`` by
hand on a two-plane trace in which one chip starves while the other
runs (each plane's idle goes to ITS lane's interval, not to the
other's); on the trace recorded on the chip (``recorded_lanes.json``,
the first 16 s of a ``pod4_burst_64`` window, four planes) against its
expected file, the three states adding up to ``spanreduce``'s idle
inside a solve on the same trace; ``lanes.json`` and the
``interval("...")`` literals of ``pow/`` name the same intervals, none
of which is a span of ``spans.json``; the three ``per_layer`` entries
have readers and list ``chan_storm_256`` alone, and a reader returns
None for a program without the counter; and a traced run of a
rehearsal cell reports the three metrics, adding up to
``idle_in_solve_share``.
"""

import ast
import asyncio
import importlib.util
import json
import pathlib
import shutil
import sys
import time
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import (check, harness, lanereduce,  # noqa: E402
                        spanreduce, tracereduce)

LANE_METRICS = ("lane_inflight_idle_share", "lane_turn_idle_share",
                "lane_starved_idle_share")
STATES = ("inflight", "turn", "starved")
CELL = "rehearse_lanes"
DATA = REPO / "benchmarks" / "testdata"


def _rehearsal():
    """The sibling test module's helpers (``_add_cell``, ``NEW_LAYER``),
    loaded by path: this directory is not a package."""
    spec = importlib.util.spec_from_file_location(
        "bench_rehearsal_helpers_lanes",
        pathlib.Path(__file__).with_name("test_benchmarks.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the reduction ------------------------------------------------------


def _two_planes():
    """Ten seconds of two chips inside one solve (11..19).  Chip 0 runs
    12..18 but for 15..15.5, when its one launch had come in and the
    host had not launched again.  Chip 1 runs 12..14, its launch is
    read at 14.25, the host comes round to it at 14.5 and finds
    nothing: starved until the solve ends."""
    return {
        "device": {
            "/device:TPU:0": [["XLA Ops", "k", 12.0, 3.0],
                              ["XLA Ops", "k", 15.5, 2.5],
                              ["XLA Modules", "k", 12.0, 6.0]],
            "/device:TPU:1": [["XLA Ops", "k", 12.0, 2.0]]},
        "host": [["loop", "bench.window", 10.0, 10.0],
                 ["loop", "bench.wait_published", 10.0, 10.0],
                 ["pool-0", "pow.solve_batch", 11.0, 8.0],
                 ["pool-0", "pow.fetch", 12.0, 2.25]],
        "lanes": [
            # run()'s start: both lanes wait for their first launch
            [0, "pow.lane.turn", 11.0, 1.0],
            [1, "pow.lane.turn", 11.0, 1.0],
            # chip 0: read at 15.25, launched again at 15.5; the
            # solve's last read at 18.5, nothing left
            [0, "pow.lane.turn", 15.25, 0.25],
            [0, "pow.lane.turn", 18.5, 0.25],
            [0, "pow.lane.starved", 18.75, 0.25],
            # chip 1: read at 14.25, found empty-handed at 14.5
            [1, "pow.lane.turn", 14.25, 0.25],
            [1, "pow.lane.starved", 14.5, 4.5]]}


def test_lane_reduction_by_hand():
    spec = lanereduce.load_lanes(REPO)
    trace = _two_planes()
    got = lanereduce.reduce_lanes(trace, spec)
    assert got["window_s"] == pytest.approx(10.0)
    chip0, chip1 = (got["chips"]["/device:TPU:%d" % k] for k in (0, 1))
    assert (chip0["device"], chip1["device"]) == (0, 1)
    # chip 0 idles 10..12, 15..15.5, 18..20
    assert chip0["idle_s"] == pytest.approx(4.5)
    assert chip0["between_solves"] == pytest.approx(2.0)
    assert chip0["turn"] == pytest.approx(1.0 + 0.25 + 0.25)
    assert chip0["starved"] == pytest.approx(0.25)
    # 15..15.25 and 18..18.5: a launch out, done, and not read yet
    assert chip0["inflight"] == pytest.approx(0.25 + 0.5)
    # chip 1 idles 10..12 and 14..20: chip 0's short turns and its
    # quarter of a second of starvation are not chip 1's
    assert chip1["idle_s"] == pytest.approx(8.0)
    assert chip1["between_solves"] == pytest.approx(2.0)
    assert chip1["turn"] == pytest.approx(1.0 + 0.25)
    assert chip1["starved"] == pytest.approx(4.5)
    assert chip1["inflight"] == pytest.approx(0.25)
    # the planes' mean, and spanreduce's halves of the same trace
    assert got["idle_by_state"] == {
        "inflight": pytest.approx(0.5), "turn": pytest.approx(1.375),
        "starved": pytest.approx(2.375)}
    spans = spanreduce.reduce_spans(
        trace, {"solve": spec["solve"],
                "spans": {"pow.solve_batch": {}, "pow.fetch": {}}})
    assert sum(got["idle_by_state"].values()) == pytest.approx(
        spans["idle_in_solve_s"])
    assert got["idle_between_solves_s"] == pytest.approx(
        spans["idle_between_solves_s"])
    assert got["idle_s"] == pytest.approx(spans["idle_s"])
    # which is what spanreduce cannot say: all of it under pow.fetch or
    # the solve, whichever chip idled
    assert {n for n, _s in spans["idle_by_span"]} \
        == {"pow.fetch", "pow.solve_batch", "bench.wait_published"}
    assert got["lane_n"] == {"pow.lane.turn": 5, "pow.lane.starved": 2}
    assert got["lane_s"]["pow.lane.starved"] == pytest.approx(4.75)
    assert got["lane_intervals"] == 7
    assert lanereduce.table(got)[0] == [
        "mean", 6.25, 2.0, 0.5, 1.375, 2.375]


def test_a_lane_of_another_device_takes_nothing_from_a_plane():
    spec = lanereduce.load_lanes(REPO)
    trace = _two_planes()
    # the same intervals, all said to be device 3's: no plane's
    trace["lanes"] = [[3] + ev[1:] for ev in trace["lanes"]]
    got = lanereduce.reduce_lanes(trace, spec)
    for row in got["chips"].values():
        assert row["turn"] == row["starved"] == 0.0
    assert got["idle_by_state"]["inflight"] == pytest.approx(
        (2.5 + 6.0) / 2)
    # no device plane at all (the CPU rehearsal): every device a lane
    # names was idle throughout
    bare = dict(_two_planes(), device={"/device:TPU:0": []})
    got = lanereduce.reduce_lanes(bare, spec)
    assert sorted(r["device"] for r in got["chips"].values()) == [0, 1]
    assert got["idle_s"] == pytest.approx(10.0)
    assert sum(got["idle_by_state"].values()) == pytest.approx(8.0)
    assert got["idle_by_state"]["starved"] == pytest.approx(
        (0.25 + 4.5) / 2)
    # and a trace of a program that opens no interval
    got = lanereduce.reduce_lanes(dict(_two_planes(), lanes=[]), spec)
    assert got["lane_intervals"] == 0
    assert got["idle_by_state"]["inflight"] == pytest.approx(
        (2.5 + 6.0) / 2)


def test_plane_names_give_the_device_id():
    assert lanereduce.device_of("/device:TPU:2") == 2
    assert lanereduce.device_of("/device:TPU:10") == 10
    assert lanereduce.device_of("/device:none") is None
    assert lanereduce.device_of("/device:TPU:0 SparseCore") is None


def test_overlap_of_disjoint_interval_lists():
    merged = lanereduce._merged([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    assert merged == [[0, 2], [3, 4]]
    assert lanereduce._overlap(merged, [[1.5, 3.5], [3.75, 9]]) \
        == pytest.approx(0.5 + 0.5 + 0.25)
    assert lanereduce._overlap(merged, []) == 0.0


def test_lane_reduction_on_the_recorded_trace():
    trace = json.loads((DATA / "recorded_lanes.json").read_text())
    expect = json.loads((DATA / "recorded_lanes.expected.json")
                        .read_text())
    spec = lanereduce.load_lanes(REPO)
    got = lanereduce.reduce_lanes(trace, spec)
    # a four-chip host, every plane's lane in the trace
    assert len(trace["device"]) == 4
    assert sorted(r["device"] for r in got["chips"].values()) \
        == [0, 1, 2, 3]
    assert {ev[0] for ev in trace["lanes"]} == {0, 1, 2, 3}
    assert {ev[1] for ev in trace["lanes"]} == set(spec["intervals"])
    spans = spanreduce.reduce_spans(
        trace, {"solve": spec["solve"],
                "spans": {n: {} for n in spec["solve"]}})
    assert sum(got["idle_by_state"].values()) == pytest.approx(
        spans["idle_in_solve_s"], abs=1e-6)
    assert got["idle_between_solves_s"] == pytest.approx(
        spans["idle_between_solves_s"], abs=1e-6)
    assert got["idle_s"] == pytest.approx(spans["idle_s"], abs=1e-6)
    assert got["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    for state in STATES:
        assert got["idle_by_state"][state] == pytest.approx(
            expect["idle_by_state"][state], abs=1e-6), state
        assert got["idle_by_state"][state] >= 0.0
    assert sorted(got["chips"]) == sorted(expect["chips"])
    for plane, row in expect["chips"].items():
        for key, value in row.items():
            assert got["chips"][plane][key] == pytest.approx(
                value, abs=1e-6), (plane, key)
    assert got["lane_n"] == expect["lane_n"]
    # kept small: near recorded_spans.json's size
    assert (DATA / "recorded_lanes.json").stat().st_size < 200_000


def test_clip_keeps_the_head_of_the_window_and_the_lanes_device():
    trace = {"device": {"/device:TPU:1": [
                 ["XLA Ops", "op", 11.0, 5.0],
                 ["XLA Ops", "copy", 10.5, 3e-7],   # thinned away
                 ["XLA Modules", "op", 11.0, 5.0],
                 ["XLA Ops", "op", 30.0, 1.0]]},
             "host": [["t", "bench.window", 10.0, 20.0],
                      ["t", "pow.solve_batch", 9.0, 2.0]],
             "lanes": [[1, "pow.lane.turn", 9.5, 1.5],
                       [1, "pow.lane.starved", 13.0, 4.0],
                       [1, "pow.lane.turn", 40.0, 1.0]]}
    cut = lanereduce.clip(trace, 4.0)
    assert cut == {
        "device": {"/device:TPU:1": [["XLA Ops", "op", 1.0, 3.0]]},
        "host": [["t", "bench.window", 0.0, 4.0],
                 ["t", "pow.solve_batch", 0.0, 1.0]],
        "lanes": [[1, "pow.lane.turn", 0.0, 1.0],
                  [1, "pow.lane.starved", 3.0, 1.0]]}


# -- lanes.json against the program and the benchmark -------------------


def _interval_literals(path: pathlib.Path) -> set:
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Call) and node.args \
                and getattr(node.func, "id",
                            getattr(node.func, "attr", "")) == "interval" \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            found.add(node.args[0].value)
    return found


def test_lanes_json_names_the_intervals_the_driver_opens():
    package = REPO / "pybitmessage_tpu"
    files = [package / "workers" / "sender.py",
             package / "ops" / "sha512_pallas.py",
             *sorted((package / "pow").glob("*.py"))]
    in_code = set().union(*(_interval_literals(f) for f in files))
    spec = lanereduce.load_lanes(REPO)
    assert in_code == set(spec["intervals"]) and in_code
    # a state is no step: none of them is in spanreduce's table
    spans = spanreduce.load_spans(REPO)
    assert not in_code & set(spans["spans"])
    assert spec["solve"] == spans["solve"]
    from pybitmessage_tpu.pow.pipeline import LANE_STATES
    assert tuple(spec["states"]) == LANE_STATES == STATES
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    metrics = {m["name"]: m for m in bench["per_layer"]}
    # the two intervals' states, and the one that is their absence
    assert {row["state"] for row in spec["intervals"].values()} \
        == set(STATES) - {"inflight"}
    for name, row in spec["intervals"].items():
        assert row["metric"] == "lane_%s_idle_share" % row["state"]
        assert metrics[row["metric"]]["layer"] == row["layer"], name


def test_the_three_entries_are_appended_and_list_the_storm_alone():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    # found by name, in their order, wherever later appends left them
    mine = [m for m in bench["per_layer"] if m["name"] in LANE_METRICS]
    assert tuple(m["name"] for m in mine) == LANE_METRICS
    for m in mine:
        assert m == {"name": m["name"], "unit": "%", "better": "lower",
                     "source": "device_trace",
                     "layer": "planner/pipeline",
                     "moves": "sent_msgs_per_s",
                     "workloads": ["chan_storm_256"]}
        assert (REPO / "benchmarks" / "layers"
                / (m["name"] + ".py")).exists()
    # no other cell's set of metrics grew
    for cell in bench["workloads"]:
        names = {m["name"] for m in
                 harness.load(REPO, cell["name"]).metrics("per_layer")}
        assert (set(LANE_METRICS) <= names) \
            == (cell["name"] == "chan_storm_256"), cell["name"]


def _window(families, reduction, traced=True):
    """A window whose registry held ``families`` when it closed and
    whose lane reduction is ``reduction`` already."""
    counters = types.SimpleNamespace(
        after={(fam, ("0", "turn")): 1.0 for fam in families},
        delta=lambda fam: {("0", "turn"): 1.0})
    return types.SimpleNamespace(
        trace={"window_s": 50.0} if traced else None, counters=counters,
        notes={"lane_reduction": reduction})


@pytest.mark.parametrize("name,state", zip(LANE_METRICS, STATES))
def test_a_reader_gives_a_share_of_the_window_or_nothing(name, state):
    read = harness.load_module(REPO, "layers", name).read
    red = {"window_s": 50.0,
           "idle_by_state": {"inflight": 0.5, "turn": 1.0, "starved": 4.0}}
    has = ["pow_pipeline_lane_seconds_total"]
    assert read(_window(has, red)) == pytest.approx(
        100.0 * red["idle_by_state"][state] / 50.0)
    # an untraced run; a program older than the counter (the parent:
    # nothing is read, nothing raises); a window of no length
    assert read(_window(has, red, traced=False)) is None
    assert read(_window(["pow_pipeline_launches_total"], red)) is None
    assert read(_window(has, dict(red, window_s=0.0))) is None


# -- a traced rehearsal -------------------------------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with one chan cell added as new files
    and listed under the lane metrics and the idle split."""
    helpers = _rehearsal()
    root = tmp_path_factory.mktemp("lane_tree")
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "benchmarks" / "layers" / "published_count.py").write_text(
        helpers.NEW_LAYER)
    helpers._add_cell(
        root, CELL, "chan_broadcaster",
        {"warm_verify_batches": [], "warm_quiet_sweeps": 1,
         "warm_max_sweeps": 4, "send": "broadcast", "sweep": 5,
         "body_bytes": [[1.0, 40, 120]]}, "closed_loop")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for metric in spec["per_layer"]:
        if metric["name"] in LANE_METRICS + (
                "idle_in_solve_share", "idle_between_solves_share"):
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_traced_rehearsal_reports_the_lane_metrics(tree, monkeypatch,
                                                   tmp_path, capsys):
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    from pybitmessage_tpu.pow import PowDispatcher
    monkeypatch.setattr(check, "STALL_SECONDS", 4.0)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(PowDispatcher, "_batch_topology",
                        lambda self: (1, True))
    setup_jax()
    lines = []
    # three seconds: whole sweeps of five lie inside the window, so
    # whole solves do, each with its lanes' first turn and last hunger
    result = asyncio.run(harness.run_cell(
        harness.load(tree, CELL), 2**31 + 41, 3.0, True, lines.append,
        t_start=time.monotonic()))
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(LANE_METRICS) <= set(metrics), sorted(metrics)
    lanes = [metrics[name] for name in LANE_METRICS]
    assert all(value >= 0.0 for value in lanes), lanes
    # the identity the chip run holds too
    assert sum(lanes) == pytest.approx(metrics["idle_in_solve_share"],
                                       abs=0.05)
    assert sum(lanes) + metrics["idle_between_solves_share"] \
        == pytest.approx(metrics["device_idle_share"], abs=0.05)
    window = result["window"]
    red = window.notes["lane_reduction"]
    # the intervals came back from the trace with their device: one
    # chip, id 0, at least a first turn a solve
    assert red["lane_n"]["pow.lane.turn"] >= 1
    assert [row["device"] for row in red["chips"].values()] == [0]
    assert sum(red["idle_by_state"].values()) == pytest.approx(
        window.notes["span_reduction"]["idle_in_solve_s"], abs=1e-6)
    # no chip ran anything here, so a lane's idle is its interval: the
    # counter's seconds are the trace's, on two clocks
    assert metrics["lane_turn_idle_share"] > 0
    grown = window.counters.delta("pow_pipeline_lane_seconds_total")
    for name, state in (("pow.lane.turn", "turn"),
                        ("pow.lane.starved", "starved")):
        counted = sum(v for (_dev, st), v in grown.items() if st == state)
        assert red["lane_s"][name] == pytest.approx(counted, abs=0.25)
    assert "[lanes] idle seconds of the window by lane state" \
        in capsys.readouterr().out
    # the same trace by hand: the CLI's read gives the same reduction,
    # and a clipped record of it reduces
    spec = lanereduce.load_lanes(tree)
    trace = lanereduce.read_xplane(lanereduce.newest(tree, CELL), spec)
    again = lanereduce.reduce_lanes(trace, spec)
    assert again["idle_by_state"] == pytest.approx(red["idle_by_state"])
    cut = lanereduce.clip(trace, 2.0)
    assert lanereduce.reduce_lanes(cut, spec)["window_s"] \
        == pytest.approx(2.0, abs=1e-6)
    assert tracereduce.window_of(cut)[0] == 0.0
