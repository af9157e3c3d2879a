"""The cell ``pod4_single_send`` (ISSUE 43) rehearsed on the CPU, beside
``tests/test_pod4_rehearsal.py`` and for its reason outside
``tests/benchmarks``: it compiles for four devices.  A scratch copy of
the benchmark holds the cell's configuration at test difficulty; the
dispatcher is told that it has FOUR accelerator chips (four of the
suite's virtual devices) and XLA programs stand where the kernels are.
Every send is two lone objects, and each is laid out over the four
lanes of the pipeline driver: the rung is the pipeline's on every
attempt, and nothing lowers the ``shard_map`` partition.

The entry, the configuration and the readers are held in
``tests/benchmarks/test_pod4_single_send.py``.
"""

import asyncio
import json
import pathlib
import shutil
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
for _path in (REPO, REPO / "tests" / "benchmarks", REPO / "tests"):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from benchmarks import harness  # noqa: E402
from test_pod4_rehearsal import four_chips  # noqa: E402,F401  (fixtures)
from test_queue_1k import one_chip  # noqa: E402,F401

CELL = "pod4_single_send"
REHEARSAL = "rehearse_lone4"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the cell's configuration at test
    difficulty, added as new files; the traffic file is the cell's."""
    root = tmp_path_factory.mktemp("lone4_tree")
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load(REPO, CELL)
    cfg = dict(bench.config, name=REHEARSAL + "_cfg", test_mode=True,
               ntpb=10, extra=10)
    (root / "benchmarks" / "configs" / (REHEARSAL + "_cfg.json")
     ).write_text(json.dumps(cfg))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": REHEARSAL + "_cfg", "source": "test", "reduced": [],
        "file": "benchmarks/configs/%s_cfg.json" % REHEARSAL,
        "why": "test"})
    spec["workloads"].append(dict(bench.cell, name=REHEARSAL,
                                  config=REHEARSAL + "_cfg"))
    for metric in spec["per_layer"]:
        if metric.get("workloads") == [CELL]:
            metric["workloads"] = [CELL, REHEARSAL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.mark.parametrize("one_program", [False, True],
                         ids=["a_launch_a_lane", "one_program"])
def test_every_send_is_two_lone_objects_on_the_pipeline_s_lanes(
        one_program, tree, four_chips, monkeypatch):  # noqa: F811
    """As the virtual devices get it (the Mosaic kernels' stand-ins, a
    launch a lane), and as an accelerator's chips do since ISSUE 49:
    the lanes ONE program that stops at the first hit, its XLA
    equivalent standing where ``ici_search`` is."""
    from pybitmessage_tpu import parallel
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    from pybitmessage_tpu.observability import TRACER
    from pybitmessage_tpu.ops import sha512_ici
    from pybitmessage_tpu.pow import pipeline

    def never(*_a, **_kw):
        raise AssertionError("the node called the shard_map partition")

    monkeypatch.setattr(parallel, "pallas_sharded_solve", never)
    monkeypatch.setattr(parallel, "pallas_sharded_solve_batch", never)
    if one_program:
        def ici_search(operands, devices, rows, chunks, unroll, interpret):
            return pipeline._ici_search_xla(
                operands, lanes=rows * 128 * unroll, chunks=chunks)

        monkeypatch.setattr(sha512_ici, "ici_search", ici_search)
        monkeypatch.setattr(pipeline, "_one_program",
                            lambda impl, devices: len(devices) > 1)
    setup_jax()
    TRACER.clear()
    lines = []
    bench = harness.load(tree, REHEARSAL)
    assert bench.traffic == harness.load(REPO, "single_send").traffic
    result = asyncio.run(harness.run_cell(
        bench, 2**31 + 43, 1.0, True, lines.append,
        t_start=time.monotonic()))
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 2
    window = result["window"]
    verdict = window.verdict
    assert {k: v["value"] for k, v in verdict["compared"].items()} \
        == {"invalid_nonces": 0, "undelivered": 0, "off_tier": 0}
    # two attempts a send, all on the pipeline's rung for one object
    sends = len(window.published)
    assert verdict["attempts_by_backend"] == {"tpu-pallas": 2 * sends}
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    lone4 = {m["name"] for m in bench.metrics("per_layer")
             if m["name"].endswith(".lone4")}
    assert len(lone4) == 15
    # every metric of the cell but the two that need a device's planes
    # and, a launch a lane, the two of the first-hit flag (ISSUE 49);
    # under the one program the launch log sees no slab, so the share
    # that reads it is left out unless a tiny ack went lane by lane
    absent = lone4 - set(metrics)
    if one_program:
        assert {"kernel_mhash_per_s.lone4", "chip_busy_share_min.lone4"} \
            <= absent <= {"kernel_mhash_per_s.lone4",
                          "chip_busy_share_min.lone4",
                          "useful_trial_share.lone4"}
        assert 0 <= metrics["cancel_lag_steps.lone4"] <= 1
        assert 0 <= metrics["cancelled_lane_share.lone4"] <= 100
        lanes = window.counters.delta("pow_pipeline_lone_lanes_total")
        # four rows a harvested launch, a winner a solve (at this
        # tile most launches run out)
        assert sum(lanes.values()) % 4 == 0
        assert lanes[("won",)] == sum(window.counters.delta(
            "pow_pipeline_lone_wins_total").values()) > 0
    else:
        assert absent == {"kernel_mhash_per_s.lone4",
                          "chip_busy_share_min.lone4",
                          "cancel_lag_steps.lone4",
                          "cancelled_lane_share.lone4"}
        assert 0 < metrics["useful_trial_share.lone4"] <= 100
    # the kernel's rate under the one program needs a device's planes
    assert "kernel_mhash_per_s.ici4" not in metrics
    assert 0 < metrics["executed_useful_share.lone4"] <= 100
    assert metrics["solves_per_msg.lone4"] == 2.0
    assert metrics["off_device_solves"] == 0
    assert metrics["compiles_in_window"] == 0
    assert metrics["program_lowerings_in_window.lone4"] == 0
    assert 0 <= metrics["partition_win_share.lone4"] <= 100
    assert metrics["pow_wait_ms.lone4"] > 0
    assert metrics["pipeline_host_ms_per_launch.lone4"] > 0
    assert metrics["sender_host_ms_per_msg.lone4"] > 0
    # every solve was one object; all but the tiniest (an ack at test
    # difficulty may be expected inside eight grid steps: one small
    # launch at a time on one lane) were laid out over the four lanes
    # in mode ``slab``, and each of those credited its winner's lane
    plans = TRACER.recent(4 * sends, name="pow.plan")
    modes = [s.attrs["mode"] for s in plans]
    assert plans and all(s.attrs["objects"] == 1 for s in plans)
    assert set(modes) <= {"slab", "single-sync"} and "slab" in modes
    groups = TRACER.recent(4 * sends, name="pow.groups")
    assert sorted(s.attrs["devices"] for s in groups) \
        == sorted(4 if m == "slab" else 1 for m in modes)
    counters = window.counters
    wins = counters.delta("pow_pipeline_lone_wins_total")
    by_mode = counters.delta("pow_pipeline_mode_total")
    assert sum(wins.values()) == by_mode[("slab",)] > 0
    assert sum(by_mode.values()) == 2 * sends
    assert set(wins) <= {("0",), ("1",), ("2",), ("3",)}
    launched = counters.delta("pow_pipeline_device_launches_total")
    assert set(launched) == {("0",), ("1",), ("2",), ("3",)}, launched
    # the launch log saw every launch, abandoned ones too, through the
    # entries kernels.json names; the one program is none of them
    assert {r["program"] for r in window.launches} <= (
        {"packed"} if one_program else {"slab", "packed"})
    if one_program:
        programs = {s.attrs["program"] for s in TRACER.recent(
            8 * sends, name="pow.launch")}
        assert "ici_slab" in programs and "pallas_slab" not in programs
    else:
        assert len(window.launches) == sum(
            counters.delta("pow_pipeline_launches_total").values())
