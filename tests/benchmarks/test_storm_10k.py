"""The cell ``storm_10k`` (ISSUE 38), on the CPU.

BASELINE.json config 4 at its stated size: ``chan_broadcaster`` with
``queue_objects`` 10,000 (no longer under ``reduced``) and a traffic
file of its own for the ``backlog`` generator, which already sent
broadcasts.  The entries are held to what the issue names; every
per-layer metric that lists the cell is read on the hand-made window
``test_pod4_queue_1k.py`` keeps; and the generator is rehearsed with
broadcasts in a scratch copy of the benchmark, at test difficulty and
with a backlog of 24, as ``test_queue_1k.py`` rehearses it with
messages: the sends still outstanding when the window closes are
nobody's loss.
"""

import asyncio
import json
import pathlib
import random
import shutil
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for _path in (REPO, pathlib.Path(__file__).resolve().parent):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from benchmarks import harness  # noqa: E402
from test_pod4_queue_1k import EXPECTED, _window  # noqa: E402
from test_queue_1k import one_chip  # noqa: E402,F401  (the fixture)

CELL = "storm_10k"
REHEARSAL = "rehearse_storm"
#: the readers of a solve that is a stream which read no ack, and the
#: four-chip twin of each, whose expected value on the hand-made window
#: ``test_pod4_queue_1k.py`` holds
STREAM_LAYERS = {
    "kernel_mhash_per_s.queue": "kernel_mhash_per_s.pod4",
    "useful_trial_share.queue": "useful_trial_share.pod4",
    "live_slot_share": "live_slot_share.pod4",
    "slot_refills_per_msg": "slot_refills_per_msg.pod4",
    "speculated_launch_share": "speculated_launch_share.pod4",
    "pow_wait_ms": "pow_wait_ms.pod4",
    "pipeline_host_ms_per_launch.queue":
        "pipeline_host_ms_per_launch.pod4",
    "sender_host_ms_per_msg.queue": "sender_host_ms_per_msg.pod4",
}
EVERY_CELL = {"off_device_solves", "compiles_in_window",
              "device_idle_share"}
TRAFFIC = {"generator": "backlog", "send": "broadcast", "backlog": 10000,
           "report": 256, "body_bytes": [[1.0, 100, 300]],
           "warm_verify_batches": [], "warm_quiet_sweeps": 1,
           "warm_max_sweeps": 4}


# -- the entries --------------------------------------------------------


def test_the_cell_is_the_one_the_issue_names():
    bench = harness.load(REPO, CELL)
    assert bench.cell == {
        "name": CELL, "config": "chan_broadcaster",
        "traffic": "storm_backlog_10k", "chips": 1,
        "why": bench.cell["why"]}
    assert 0 < len(bench.cell["why"]) <= 200
    assert bench.traffic == TRAFFIC
    # storm_256's bodies, backlog_1k's warm-up
    storm = harness.load(REPO, "chan_storm_256").traffic
    queue = harness.load(REPO, "queue_1k").traffic
    assert bench.traffic["body_bytes"] == storm["body_bytes"]
    for key in ("generator", "warm_quiet_sweeps", "warm_max_sweeps"):
        assert bench.traffic[key] == queue[key]


def test_the_configuration_states_config_4s_size_and_cuts_nothing():
    bench = harness.load(REPO, CELL)
    cfg = bench.config
    assert cfg["queue_objects"] == 10000 == bench.traffic["backlog"]
    assert cfg["reduced"] == {}
    entry = [c for c in bench.spec["configs"]
             if c["name"] == "chan_broadcaster"][0]
    assert entry["reduced"] == []
    assert entry["file"] == "benchmarks/configs/chan_broadcaster.json"
    assert "config 4" in entry["source"] and "10k" in entry["source"]
    assert (cfg["topology"], cfg["acks"], cfg["test_mode"]) \
        == ("single", False, False)
    assert (cfg["ntpb"], cfg["extra"], cfg["ttl"]) == (1000, 1000, 345600)
    assert set(cfg["guarantees"]) == {"pow", "delivery", "tier"}
    # chan_storm_256 is the same deployment; its sweeps of 256 are its
    # traffic file's, which is as it was
    storm = harness.load(REPO, "chan_storm_256")
    assert storm.config == cfg
    assert (storm.traffic["generator"], storm.traffic["sweep"]) \
        == ("closed_loop", 256)


def test_the_cell_reports_the_stream_metrics_that_read_no_ack():
    bench = harness.load(REPO, CELL)
    assert set(STREAM_LAYERS) | EVERY_CELL \
        <= {m["name"] for m in bench.metrics("per_layer")}
    # at least these, so that a later append turns nothing red; its
    # outbox is filled before the window, so submit-to-sent is a place
    # in the queue and no latency (ISSUE 47)
    ends = {m["name"] for m in bench.metrics("end_to_end")}
    assert ends >= {"sent_msgs_per_s", "setup_s"}
    assert not ends & {"send_p50_ms", "send_p90_ms"}
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"]: m["workloads"] for m in spec["per_layer"]
              if "workloads" in m}
    for name in STREAM_LAYERS:
        assert listed[name] == ["burst_send_64", "queue_1k", CELL], name
    # a chan broadcast has no ack to come back
    assert CELL not in listed["ack_verify_on_device_share"]


@pytest.mark.parametrize("name", sorted(set(STREAM_LAYERS) | EVERY_CELL))
def test_a_metric_that_lists_the_cell_has_a_reader_with_a_value(name):
    window = _window(traced=True, counted=True, cell=CELL)
    value = harness.load_module(REPO, "layers", name).read(window)
    assert value is not None
    if name in STREAM_LAYERS:
        assert value == pytest.approx(EXPECTED[STREAM_LAYERS[name]])


def test_the_outbox_is_10000_bodies_of_the_storms_sizes():
    backlog = harness.load_module(REPO, "generators", "backlog")
    closed = harness.load_module(REPO, "generators", "closed_loop")

    def sizes(seed):
        gen = backlog.make(TRAFFIC, random.Random(seed))
        return [len(gen._body()) for _ in range(10000)]

    a, b = sizes(2**31 + 11), sizes(2**31 + 12)
    # every seed the same 10,000 sizes, in another order
    assert a != b and sorted(a) == sorted(b) \
        == sorted(closed.sweep_sizes(TRAFFIC["body_bytes"], 10000))
    assert (min(a), max(a)) == (100, 300)


# -- the rehearsal: the backlog generator sending broadcasts ------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the cell's configuration at test
    difficulty and a short backlog of broadcasts, added as new files."""
    root = tmp_path_factory.mktemp("storm_tree")
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = root / "benchmarks"
    cfg = json.loads((bdir / "configs" / "chan_broadcaster.json")
                     .read_text())
    cfg.update(name=REHEARSAL + "_cfg", test_mode=True, ntpb=10, extra=10)
    (bdir / "configs" / (REHEARSAL + "_cfg.json")).write_text(
        json.dumps(cfg))
    (bdir / "traffic" / (REHEARSAL + "_mix.json")).write_text(json.dumps(
        dict(TRAFFIC, backlog=24, report=6, body_bytes=[[1.0, 40, 120]])))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": REHEARSAL + "_cfg", "source": "test", "reduced": [],
        "file": "benchmarks/configs/%s_cfg.json" % REHEARSAL,
        "why": "test"})
    spec["workloads"].append({
        "name": REHEARSAL, "config": REHEARSAL + "_cfg",
        "traffic": REHEARSAL + "_mix", "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(REHEARSAL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_backlog_of_broadcasts_streams_and_the_rest_is_not_lost(
        tree, one_chip):  # noqa: F811
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    setup_jax()
    lines = []
    result = asyncio.run(harness.run_cell(
        harness.load(tree, REHEARSAL), 2**31 + 38, 1.0, True,
        lines.append, t_start=time.monotonic()))
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
    assert verdict["objects"] >= len(window.sent)
    assert set(verdict["attempts_by_backend"]) <= {
        "tpu-pallas-batch", "tpu-pallas"}
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # every metric that lists the cell but the one that needs a
    # device's kernel time
    assert (set(STREAM_LAYERS) | EVERY_CELL) - set(metrics) \
        == {"kernel_mhash_per_s.queue"}
    assert metrics["off_device_solves"] == 0
    assert metrics["compiles_in_window"] == 0
    assert 0 < metrics["live_slot_share"] <= 100
    assert metrics["useful_trial_share.queue"] > 0
    # one proof of work a broadcast, each through a freed slot
    assert 0 < metrics["slot_refills_per_msg"]
    assert metrics["speculated_launch_share"] >= 0
    assert metrics["pow_wait_ms"] > 0
    assert metrics["pipeline_host_ms_per_launch.queue"] > 0
    assert metrics["sender_host_ms_per_msg.queue"] > 0


def test_a_nonce_altered_where_it_is_produced_is_not_correct(
        tree, one_chip):  # noqa: F811
    """The rest of a run with the timed path broken underneath: every
    nonce of the window comes back one too high, and the plain
    reference refuses what the outbox published."""
    from benchmarks import controls
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    setup_jax()
    lines = []
    result = asyncio.run(harness.run_cell(
        harness.load(tree, REHEARSAL), 2**31 + 39, 1.0, False,
        lines.append, t_start=time.monotonic(),
        wrap_solver=controls.SpoiledNonces))
    assert result["correct"] is False, lines
    compared = result["window"].verdict["compared"]
    assert compared["invalid_nonces"]["value"] > 0
    assert compared["invalid_nonces"]["limit"] == 0
