"""The cell ``single_send`` (ISSUE 27), on the CPU.

The real entries of ``BENCHMARK.json`` — the cell, its traffic file
``one_at_a_time``, every per-layer metric it lists — are rehearsed
from a copied tree whose copy of ``configs/sender_default.json`` is
rewritten to test difficulty (a configuration FILE, never an option of
``run.py``): ``correct`` true, two objects a send re-checked by the
plain reference, the control not correct.  Each reader this cell
brought is then read on hand-made windows.
"""

import asyncio
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

from benchmarks import (check, controls, harness, probes,  # noqa: E402
                        stats)

CELL = "single_send"
NEW_LAYERS = {
    # name: (unit, better, source, layer)
    "kernel_mhash_per_s.slab": ("MH/s", "higher", "device_trace",
                                "kernels"),
    "useful_trial_share.slab": ("%", "higher", "program_counter",
                                "kernels"),
    "queue_wait_ms": ("ms", "lower", "program_counter", "send queue"),
    "slab_abandoned_share": ("%", "lower", "program_counter",
                             "planner/pipeline"),
    "solves_per_msg": ("solves/msg", "lower", "program_counter",
                       "send queue"),
    "tuner_shape_changes_in_window": ("count", "lower",
                                      "program_counter",
                                      "planner/pipeline"),
}


# -- the entries --------------------------------------------------------


def test_the_cell_is_the_deployment_the_issue_names():
    bench = harness.load(REPO, CELL)
    assert bench.cell == {
        "name": CELL, "config": "sender_default",
        "traffic": "one_at_a_time", "chips": 1, "why": bench.cell["why"]}
    cfg = bench.config
    assert (cfg["topology"], cfg["acks"], cfg["recipient_on_host"]) \
        == ("pair", True, True)
    assert (cfg["ntpb"], cfg["extra"], cfg["ttl"], cfg["test_mode"]) \
        == (1000, 1000, 345600, False)
    assert cfg["solve_backends"] == ["tpu-pallas"]
    assert set(cfg["guarantees"]) == {"pow", "delivery", "tier"}
    entry = [c for c in bench.spec["configs"]
             if c["name"] == "sender_default"][0]
    assert entry["file"] == "benchmarks/configs/sender_default.json"
    assert entry["reduced"] == sorted(cfg["reduced"]) == ["queue_objects"]
    assert (bench.traffic["sweep"], bench.traffic["send"],
            bench.traffic["body_bytes"]) == (1, "message",
                                             [[1.0, 1000, 1000]])
    # at least these, so that a later append turns nothing red: a
    # closed-loop cell times every send by itself (ISSUE 47)
    assert {m["name"] for m in bench.metrics("end_to_end")} \
        >= {"sent_msgs_per_s", "setup_s", "send_p50_ms", "send_p90_ms"}


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_a_layer_metric_of_the_cell_is_entered_as_the_issue_says(name):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = [m for m in spec["per_layer"] if m["name"] == name]
    unit, better, source, layer = NEW_LAYERS[name]
    assert entry == [{"name": name, "unit": unit, "better": better,
                      "source": source, "layer": layer,
                      "moves": "sent_msgs_per_s", "workloads": [CELL]}]
    assert (REPO / "benchmarks" / "layers" / (name + ".py")).exists()


def test_the_cell_reports_the_metrics_that_list_no_cells():
    names = {m["name"] for m in harness.load(REPO, CELL)
             .metrics("per_layer")}
    assert names >= set(NEW_LAYERS) | {
        "off_device_solves", "compiles_in_window", "device_idle_share"}


# -- the rehearsal ------------------------------------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark whose ``sender_default`` is at test
    difficulty; on the CPU the ladder's first rung is the XLA tier."""
    root = tmp_path_factory.mktemp("single_send_tree")
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "benchmarks" / "configs" / "sender_default.json"
    cfg = json.loads(path.read_text())
    cfg.update(test_mode=True, ntpb=10, extra=10, solve_backends=["tpu"])
    path.write_text(json.dumps(cfg))
    return root


@pytest.fixture(autouse=True)
def _quick_stall(monkeypatch, tmp_path):
    # test mode announces within two seconds, so a refused message is
    # known to be lost much sooner than on the network's ten
    monkeypatch.setattr(check, "STALL_SECONDS", 4.0)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def _run(tree, *, trace, wrap=None, seed=2**31 + 27, seconds=1.0):
    lines = []
    result = asyncio.run(harness.run_cell(
        harness.load(tree, CELL), seed, seconds, trace, lines.append,
        t_start=time.monotonic(), wrap_solver=wrap))
    result["lines"] = lines
    return result


def test_the_cell_runs_and_is_correct_with_two_objects_a_send(tree):
    result = _run(tree, trace=True)
    assert result["correct"] is True, result["lines"]
    sends = result["attempted"]
    assert sends >= 2 and result["failed"] == 0
    verdict = result["window"].verdict
    # the message and its pre-solved ack, each re-checked by the
    # plain reference against the configuration's difficulty
    assert verdict["objects"] == 2 * sends
    assert verdict["missing_objects"] == 0
    assert {k: v["value"] for k, v in verdict["compared"].items()} \
        == {"invalid_nonces": 0, "undelivered": 0, "off_tier": 0}
    assert 0 < verdict["worst_value_over_target"] <= 1
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # one message at a time: the ack and the message are each solved
    # alone, in a batch of their own
    assert metrics["solves_per_msg"] == 2.0
    assert metrics["queue_wait_ms"] > 0
    assert metrics["off_device_solves"] == 0
    # the program has the counter, so the line has the metric
    assert metrics["tuner_shape_changes_in_window"] >= 0
    # nothing ran through pallas_search off a chip: nothing to read
    for name in ("kernel_mhash_per_s.slab", "useful_trial_share.slab",
                 "slab_abandoned_share"):
        assert name not in metrics
    assert any(l.startswith("compared: invalid_nonces = 0 (limit 0)")
               for l in result["lines"])


def test_untraced_the_cell_reports_its_end_to_end_metrics(tree):
    result = _run(tree, trace=False, seed=2**31 + 28)
    assert result["correct"] is True, result["lines"]
    assert set(result["metrics"]) >= {"sent_msgs_per_s", "setup_s",
                                      "send_p50_ms", "send_p90_ms"}
    window = result["window"]
    assert result["metrics"]["sent_msgs_per_s"]["value"] \
        == pytest.approx(len(window.published) / window.seconds)
    # one send at a time: each is timed on its own handle, and 1,000
    # over the mean of those times is the rate but for the harness's
    # own gap between two sends
    times = [(s.t_done - s.t_submit) * 1e3 for s in window.published]
    for name, q in (("send_p50_ms", 50), ("send_p90_ms", 90)):
        assert result["metrics"][name] == {
            "value": stats.percentile(times, q), "unit": "ms"}
    assert sum(times) / 1e3 <= window.seconds


def test_the_control_is_not_correct_in_this_cell(tree):
    # targets twice as easy: about half of the nonces miss the real
    # target, and a send is two objects, so a window of n sends passes
    # by luck once in 4**n: a window long enough for n to be safe
    result = _run(tree, trace=False, wrap=controls.EasierTargets,
                  seed=2**31 + 29, seconds=4.0)
    assert result["attempted"] >= 4
    assert result["correct"] is False, result["lines"]
    compared = result["window"].verdict["compared"]
    assert compared["invalid_nonces"]["value"] > 0
    assert compared["off_tier"]["value"] == 0


# -- the readers, on hand-made windows ----------------------------------


def _window(before: dict, after: dict, published: int = 0):
    sent = [types.SimpleNamespace(t_done=1.0 + i) for i in range(published)]
    sent.append(types.SimpleNamespace(t_done=None))     # one never sent
    return harness.Window(
        bench=None, seconds=51.0, setup_s=70.0, sent=sent,
        counters=probes.Counters(before, after), launches=[], verdict={})


def _read(name: str, window):
    return harness.load_module(REPO, "layers", name).read(window)


LAUNCHES = "pow_pipeline_launches_total"
ABANDONED = "pow_pipeline_abandoned_launches_total"


@pytest.mark.parametrize("before, after, expected", [
    # no slab launched in the window: nothing to read
    ({(LAUNCHES, ("slab",)): 6.0}, {(LAUNCHES, ("slab",)): 6.0}, None),
    ({}, {(LAUNCHES, ("batch",)): 40.0, (ABANDONED, ("batch",)): 2.0},
     None),
    # every solve left one slab behind: half of all slabs
    ({(LAUNCHES, ("slab",)): 6.0, (ABANDONED, ("slab",)): 3.0},
     {(LAUNCHES, ("slab",)): 14.0, (ABANDONED, ("slab",)): 7.0,
      (LAUNCHES, ("batch",)): 40.0, (ABANDONED, ("batch",)): 9.0}, 50.0),
    # slabs launched, none abandoned
    ({}, {(LAUNCHES, ("slab",)): 5.0}, 0.0),
])
def test_slab_abandoned_share(before, after, expected):
    assert _read("slab_abandoned_share", _window(before, after)) \
        == expected


@pytest.mark.parametrize("batches, published, expected", [
    (4, 2, 2.0),        # the ack and the message, each alone
    (3, 3, 1.0),        # they shared a batch
    (0, 2, None),       # no batch launched
    (4, 0, None),       # nothing published
])
def test_solves_per_msg(batches, published, expected):
    key = ("pow_batch_size", ())
    window = _window({key: (3.0, 3)}, {key: (3.0 + batches, 3 + batches)},
                     published)
    assert _read("solves_per_msg", window) == expected


@pytest.mark.parametrize("before, after, expected", [
    # a program older than the counter: the metric is left out
    ({}, {(LAUNCHES, ("slab",)): 5.0}, None),
    # the counter is there and did not move
    ({("pow_autotune_shape_changes_total", ("xla",)): 2.0},
     {("pow_autotune_shape_changes_total", ("xla",)): 2.0}, 0),
    # a change of any kind is counted
    ({("pow_autotune_shape_changes_total", ("xla",)): 0.0},
     {("pow_autotune_shape_changes_total", ("xla",)): 1.0,
      ("pow_autotune_shape_changes_total", ("batch",)): 2.0}, 3),
])
def test_tuner_shape_changes_in_window(before, after, expected):
    assert _read("tuner_shape_changes_in_window",
                 _window(before, after)) == expected


def test_the_program_has_the_series_the_new_readers_read():
    from pybitmessage_tpu.pow import pipeline, service     # noqa: F401
    snap = probes.registry_snapshot()
    assert ("pow_autotune_shape_changes_total", ("xla",)) in snap
    window = _window(snap, snap)
    assert _read("tuner_shape_changes_in_window", window) == 0
