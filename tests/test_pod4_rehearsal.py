"""The cell ``pod4_queue_1k`` (ISSUE 37) rehearsed on the CPU, as
``tests/benchmarks/test_queue_1k.py`` rehearses ``queue_1k``: in a
scratch copy of the benchmark at test difficulty, with the dispatcher
told that it has FOUR accelerator chips (four of the suite's virtual
devices).  The queue streams through the pipeline placed over them and
a lone object is laid out over its lanes (ISSUE 43).  A second cell on the
same configuration sends bursts (``pod4_burst_64``'s generator) whose
chips run out unevenly: the ones that have take nonce-range copies of
the stragglers (ISSUE 42).

The entries and the readers are held in
``tests/benchmarks/test_pod4_queue_1k.py``.  This file is outside that
directory because it compiles for four devices for most of a minute:
the files there are collected first and run side by side, a process
each, and each times a one-second window.
"""

import asyncio
import collections
import functools
import json
import pathlib
import shutil
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
for _path in (REPO, REPO / "tests" / "benchmarks"):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from benchmarks import harness  # noqa: E402
from test_pod4_queue_1k import (BY_DEVICE, CONFIG,  # noqa: E402
                                NEW_LAYERS)
from test_queue_1k import one_chip  # noqa: E402,F401  (the fixture)

REHEARSAL = "rehearse_pod4"
BURST = "rehearse_pod4_burst"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the new cell's configuration at
    test difficulty and a short backlog, added as new files."""
    root = tmp_path_factory.mktemp("pod4_tree")
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = root / "benchmarks"
    cfg = json.loads((bdir / "configs" / (CONFIG + ".json")).read_text())
    cfg.update(name=REHEARSAL + "_cfg", test_mode=True, ntpb=10, extra=10)
    (bdir / "configs" / (REHEARSAL + "_cfg.json")).write_text(
        json.dumps(cfg))
    (bdir / "traffic" / (REHEARSAL + "_mix.json")).write_text(json.dumps({
        "generator": "backlog", "send": "message", "backlog": 24,
        "report": 6, "body_bytes": [[1.0, 40, 300]],
        "warm_verify_batches": [], "warm_quiet_sweeps": 1,
        "warm_max_sweeps": 4}))
    # bursts of twelve, a third of them ten times as long as the rest
    (bdir / "traffic" / (BURST + "_mix.json")).write_text(json.dumps({
        "generator": "closed_loop", "send": "message", "sweep": 12,
        "body_bytes": [[0.7, 40, 300], [0.3, 2000, 4000]],
        "warm_verify_batches": [], "warm_quiet_sweeps": 1,
        "warm_max_sweeps": 4}))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": REHEARSAL + "_cfg", "source": "test", "reduced": [],
        "file": "benchmarks/configs/%s_cfg.json" % REHEARSAL,
        "why": "test"})
    spec["workloads"].append({
        "name": REHEARSAL, "config": REHEARSAL + "_cfg",
        "traffic": REHEARSAL + "_mix", "chips": 4, "why": "test"})
    spec["workloads"].append({
        "name": BURST, "config": REHEARSAL + "_cfg",
        "traffic": BURST + "_mix", "chips": 4, "why": "test"})
    for metric in spec["per_layer"]:
        if metric["name"] in NEW_LAYERS:
            metric["workloads"] += [REHEARSAL, BURST]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def four_chips(one_chip, monkeypatch):  # noqa: F811
    """``test_queue_1k``'s stand-in for the chip, told that it has four
    of them: the suite's first four virtual devices."""
    from pybitmessage_tpu.pow import pipeline
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher
    monkeypatch.setattr(PowDispatcher, "_device_count", lambda self: 4)
    # a lone object's four lanes launch one grid step each at this tile
    monkeypatch.setattr(pipeline, "LONE_LANES_CHUNKS", 4)


def test_the_cell_streams_over_four_devices_and_is_correct(tree,
                                                           four_chips):
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    from pybitmessage_tpu.observability import TRACER
    setup_jax()
    TRACER.clear()
    lines = []
    result = asyncio.run(harness.run_cell(
        harness.load(tree, REHEARSAL), 2**31 + 37, 1.0, True,
        lines.append, t_start=time.monotonic()))
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 6
    verdict = result["window"].verdict
    assert {k: v["value"] for k, v in verdict["compared"].items()} \
        == {"invalid_nonces": 0, "undelivered": 0, "off_tier": 0}
    # the queue on the pipeline, and a lone object too
    assert set(verdict["attempts_by_backend"]) <= {
        "tpu-pallas-batch", "tpu-pallas"}
    assert "tpu-pallas-batch" in verdict["attempts_by_backend"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # every new metric but the two that need a device's planes
    assert set(NEW_LAYERS) - set(metrics) == {
        "kernel_mhash_per_s.pod4", "chip_busy_share_min"}
    assert metrics["off_device_solves"] == 0
    # a lone object's slab is no shape the warm-up need run: an object
    # left alone for the first time inside the window compiles it
    # there (PERF.md section 7, row 13)
    if "tpu-pallas" not in verdict["attempts_by_backend"]:
        assert metrics["compiles_in_window"] == 0
    assert 0 < metrics["chip_launch_share_max"] < 100
    assert 0 < metrics["live_slot_share.pod4"] <= 100
    assert metrics["useful_trial_share.pod4"] > 0
    assert metrics["pow_wait_ms.pod4"] > 0
    assert metrics["pipeline_host_ms_per_launch.pod4"] > 0
    assert metrics["sender_host_ms_per_msg.pod4"] > 0
    # every solve was laid out over the four, every launch of the
    # window is counted on one of them, and every one of them launched:
    # even at this load (two dozen sends for 64 slots) what arrives goes
    # to the chip with the fewest launches in flight and the least to do
    groups = TRACER.recent(50, name="pow.groups")
    assert groups and all(s.attrs["devices"] == 4 for s in groups)
    counters = result["window"].counters
    grown = counters.delta(BY_DEVICE)
    assert set(grown) == {("0",), ("1",), ("2",), ("3",)}, grown
    assert all(grown.values()), grown
    assert sum(grown.values()) \
        == counters.delta("pow_pipeline_launches_total")[("batch",)]


def test_a_burst_whose_chips_run_out_unevenly_is_searched_in_copies(
        tree, four_chips, monkeypatch):
    """Bursts of twelve messages with a few long ones: the chips whose
    objects solved first take copies of the stragglers; every object
    is resolved once, the cell is ``correct`` and a copy is no refill."""
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher
    setup_jax()
    solve_batch = PowDispatcher.solve_batch
    solves = []

    @functools.wraps(solve_batch)
    def counting(self, items, **kwargs):
        on_solved = kwargs.get("on_solved")
        if on_solved is not None:
            seen = collections.Counter()
            solves.append(seen)

            def once(i, result):
                seen[i] += 1
                on_solved(i, result)
            kwargs["on_solved"] = once
        return solve_batch(self, items, **kwargs)

    monkeypatch.setattr(PowDispatcher, "solve_batch", counting)
    lines = []
    result = asyncio.run(harness.run_cell(
        harness.load(tree, BURST), 2**31 + 42, 1.0, True,
        lines.append, t_start=time.monotonic()))
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 12
    verdict = result["window"].verdict
    assert {k: v["value"] for k, v in verdict["compared"].items()} \
        == {"invalid_nonces": 0, "undelivered": 0, "off_tier": 0}
    assert solves and all(n == 1 for seen in solves
                          for n in seen.values())
    counters = result["window"].counters
    copies = counters.delta("pow_pipeline_copies_total")
    assert sum(copies.values()) > 0, copies
    assert set(copies) <= {("batch", "won"), ("batch", "cancelled")}
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # an ack and a message a send, each through a freed slot or the
    # solve's start: copies are not among the refills
    assert metrics["slot_refills_per_msg.pod4"] <= 2.0
    assert metrics["off_device_solves"] == 0
    assert 0 < metrics["useful_trial_share.pod4"] <= 100
