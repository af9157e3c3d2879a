"""The program's own spans in the benchmark (CPU only).

What is held here: a traced run of a rehearsal cell finds every span
of ``benchmarks/spans.json`` in its trace and reports the eight
per-layer metrics that read them and the counters beside them, with
the two idle shares adding up to ``device_idle_share`` and the
program's count of lowerings equal to the benchmark's; the span
reduction gives the expected table on a recorded trace with program
spans in it, overlapping ``worker.pow`` intervals included, and by
hand; and ``spans.json`` and the ``trace("...")`` literals of the send
path name the same spans.

On the CPU the dispatcher never takes its single-chip rung, so the
rehearsal tells it that it has one accelerator: the pipeline then runs
its XLA stand-in kernel, as its own tests do.
"""

import ast
import asyncio
import importlib.util
import json
import pathlib
import shutil
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import check, harness, spanreduce, tracereduce  # noqa: E402

NEW_METRICS = ("idle_in_solve_share", "idle_between_solves_share",
               "sender_host_ms_per_msg", "pipeline_host_ms_per_launch",
               "abandoned_launch_share", "program_lowerings_in_window",
               "setup_compile_s", "setup_backend_init_s")
CELL = "rehearse_spans"


def _rehearsal():
    """The sibling test module's helpers (``_add_cell``, ``NEW_LAYER``),
    loaded by path: this directory is not a package."""
    spec = importlib.util.spec_from_file_location(
        "bench_rehearsal_helpers",
        pathlib.Path(__file__).with_name("test_benchmarks.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with one chan cell added as new files
    and listed under the metrics this PR added."""
    helpers = _rehearsal()
    root = tmp_path_factory.mktemp("span_tree")
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
        if metric["name"] in NEW_METRICS:
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_traced_rehearsal_reports_the_span_metrics(tree, monkeypatch,
                                                   tmp_path):
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    from pybitmessage_tpu.pow import PowDispatcher
    monkeypatch.setattr(check, "STALL_SECONDS", 4.0)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(PowDispatcher, "_batch_topology",
                        lambda self: (1, True))
    setup_jax()         # as run.py does: JAX's compile events are heard
    lines = []
    result = asyncio.run(harness.run_cell(
        harness.load(tree, CELL), 2**31 + 25, 1.0, True, lines.append,
        t_start=time.monotonic()))
    assert result["correct"] is True, lines
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(metrics), sorted(metrics)
    assert metrics["idle_in_solve_share"] \
        + metrics["idle_between_solves_share"] \
        == pytest.approx(metrics["device_idle_share"], abs=0.1)
    assert metrics["program_lowerings_in_window"] \
        == metrics["compiles_in_window"] == 0
    assert metrics["off_device_solves"] == 0
    assert 0 <= metrics["abandoned_launch_share"] <= 100
    assert metrics["sender_host_ms_per_msg"] > 0
    assert metrics["pipeline_host_ms_per_launch"] > 0
    assert metrics["setup_compile_s"] > 0       # warm-up compiled
    assert metrics["setup_backend_init_s"] >= 0
    window = result["window"]
    red = window.notes["span_reduction"]
    spans = spanreduce.load_spans(tree)["spans"]
    # a sender with a PowService never solves one object by itself
    missing = {n for n in spans if not red["span_n"][n]} - {"pow.solve"}
    assert not missing, "spans not in the trace: %s" % sorted(missing)
    assert red["span_n"]["worker.pow"] == len(window.published)
    assert red["span_n"]["pow.launch"] == window.counters.total(
        "pow_pipeline_launches_total")
    assert sum(s for _n, s in red["idle_by_span"]) \
        == pytest.approx(red["idle_s"], rel=1e-6)


# -- the span reduction -------------------------------------------------


def test_span_reduction_on_the_recorded_trace():
    data = REPO / "benchmarks" / "testdata"
    trace = json.loads((data / "recorded_spans.json").read_text())
    expect = json.loads((data / "recorded_spans.expected.json")
                        .read_text())
    spec = spanreduce.load_spans(REPO)
    got = spanreduce.reduce_spans(trace, spec)
    kernels = json.loads((REPO / "benchmarks" / "kernels.json").read_text())
    outside = tracereduce.reduce_trace(
        trace, {k: v["trace_match"] for k, v in kernels.items()})
    # the same gaps as the reduction that names them from outside
    assert got["idle_s"] == pytest.approx(
        outside["window_s"] - outside["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert got["idle_s"] == pytest.approx(expect["idle_s"], rel=1e-9)
    assert got["idle_in_solve_s"] + got["idle_between_solves_s"] \
        == pytest.approx(got["idle_s"], rel=1e-9)
    assert got["idle_in_solve_s"] == pytest.approx(
        expect["idle_in_solve_s"], rel=1e-6)
    table = dict(got["idle_by_span"])
    assert sum(table.values()) == pytest.approx(got["idle_s"], rel=1e-6)
    for name, secs in expect["idle_by_span"].items():
        assert table[name] == pytest.approx(secs, rel=1e-6), name
    assert sorted(table) == sorted(expect["idle_by_span"])
    assert got["span_n"] == expect["span_n"]
    # hundreds of worker.pow intervals are open at once on one thread
    pows = sorted((s, s + d) for _t, n, s, d in trace["host"]
                  if n == "worker.pow")
    assert len(pows) >= 200
    assert sum(1 for (_s0, e0), (s1, _e1) in zip(pows, pows[1:])
               if s1 < e0) >= 200


def test_span_reduction_by_hand():
    spec = {"solve": ["pow.solve_batch"],
            "spans": {n: {} for n in (
                "sender.sweep", "sender.sign", "worker.pow",
                "pow.solve_batch", "pow.launch", "pow.fetch")}}
    trace = {
        "device": {"/device:TPU:0": [
            ["XLA Ops", "pallas_call", 14.0, 2.0],
            ["XLA Ops", "pallas_call", 16.5, 3.5]]},
        "host": [
            ["loop", "bench.window", 10.0, 10.0],
            ["loop", "bench.submit", 10.0, 0.5],
            ["loop", "bench.wait_published", 10.5, 9.5],
            ["loop", "sender.sweep", 11.0, 9.0],
            # two signatures inside the sweep, on two threads
            ["pool-0", "sender.sign", 11.0, 0.5],
            ["pool-1", "sender.sign", 11.25, 0.5],
            # two requests waiting, overlapping without nesting
            ["loop", "worker.pow", 12.0, 8.0],
            ["loop", "worker.pow", 12.5, 7.4],
            ["pool-0", "pow.solve_batch", 13.0, 7.0],
            ["pool-0", "pow.launch", 13.5, 0.5],
            ["pool-0", "pow.fetch", 14.0, 2.25],
            ["pool-0", "not.in.spans.json", 13.0, 7.0]]}
    got = spanreduce.reduce_spans(trace, spec)
    assert got["window_s"] == pytest.approx(10.0)
    assert got["idle_s"] == pytest.approx(4.0 + 0.5)
    # 13..14 and 16..16.5 lie inside the solve
    assert got["idle_in_solve_s"] == pytest.approx(1.5)
    assert got["idle_between_solves_s"] == pytest.approx(3.0)
    assert dict(got["idle_by_span"]) == {
        "bench.submit": pytest.approx(0.5),         # 10.0..10.5
        "bench.wait_published": pytest.approx(0.5),     # 10.5..11.0
        "sender.sign": pytest.approx(0.75),         # 11.0..11.75
        "sender.sweep": pytest.approx(0.25),        # 11.75..12.0
        # 12.0..12.5 only the longer request is open, then the shorter
        "worker.pow": pytest.approx(1.0),           # 12.0..13.0
        # the solve is shorter than the requests that wait for it
        "pow.solve_batch": pytest.approx(0.5 + 0.25),   # 13.0..13.5,
        "pow.launch": pytest.approx(0.5),           # 13.5..14.0   16.25..
        "pow.fetch": pytest.approx(0.25)}           # 16.0..16.25
    assert got["span_n"]["sender.sign"] == 2
    assert got["span_s"]["pow.fetch"] == pytest.approx(2.25)
    assert got["program_spans"] == 8
    # a trace with no program span in it: all idle is the harness's
    bare = {"device": trace["device"],
            "host": [e for e in trace["host"] if e[1].startswith("bench.")]}
    got = spanreduce.reduce_spans(bare, spec)
    assert got["program_spans"] == 0
    assert got["idle_between_solves_s"] == pytest.approx(4.5)
    assert dict(got["idle_by_span"]) == {
        "bench.submit": pytest.approx(0.5),
        "bench.wait_published": pytest.approx(4.0)}


def test_clip_keeps_the_head_of_the_window():
    trace = {"device": {"d": [["XLA Ops", "op", 11.0, 5.0],
                              ["XLA Ops", "op", 30.0, 1.0]]},
             "host": [["t", "bench.window", 10.0, 20.0],
                      ["t", "pow.launch", 9.0, 2.0]]}
    cut = spanreduce.clip(trace, 4.0)
    assert cut["device"]["d"] == [["XLA Ops", "op", 1.0, 3.0]]
    assert cut["host"] == [["t", "bench.window", 0.0, 4.0],
                           ["t", "pow.launch", 0.0, 1.0]]


# -- spans.json against the program -------------------------------------


def _trace_literals(path: pathlib.Path) -> set:
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Call) and node.args \
                and getattr(node.func, "id",
                            getattr(node.func, "attr", "")) == "trace" \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            found.add(node.args[0].value)
    return found


def test_spans_json_names_the_spans_the_send_path_opens():
    package = REPO / "pybitmessage_tpu"
    files = [package / "workers" / "sender.py",
             package / "ops" / "sha512_pallas.py",
             *sorted((package / "pow").glob("*.py"))]
    in_code = set().union(*(_trace_literals(f) for f in files))
    spec = spanreduce.load_spans(REPO)
    assert in_code == set(spec["spans"])
    assert set(spec["solve"]) <= set(spec["spans"])
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    metrics = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for name, row in spec["spans"].items():
        assert row["metric"] in metrics, name
        assert row["layer"] in layers, name
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == ["chan_storm_256"]
