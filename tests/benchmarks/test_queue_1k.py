"""The cells ``queue_1k`` and ``burst_send_64`` (ISSUE 32), on the CPU.

The entries of ``BENCHMARK.json`` are held to what the issue names.
The ``backlog`` generator is rehearsed in a scratch copy of the
benchmark (as ``test_benchmarks.py::tree`` makes one): a cell added as
files only, ``sender_queue_1k`` rewritten to test difficulty in a
configuration FILE of its own, ``backlog`` 12 and ``report`` 4.  The
rehearsal tells the dispatcher that it has one accelerator and puts
XLA stand-ins where the Mosaic kernels are (as ``tests/test_chip_smoke``
does), so that the solve really streams: objects leave as they solve,
freed slots refill, and the launch log has launches to read.  Each
reader this PR brought is then read on hand-made windows.
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

from benchmarks import check, harness, probes  # noqa: E402

CELLS = ["burst_send_64", "queue_1k"]
CELL = "rehearse_queue"
NEW_LAYERS = {
    # name: (unit, better, source, layer)
    "kernel_mhash_per_s.queue": ("MH/s", "higher", "device_trace",
                                 "kernels"),
    "useful_trial_share.queue": ("%", "higher", "program_counter",
                                 "kernels"),
    "live_slot_share": ("%", "higher", "program_counter",
                        "planner/pipeline"),
    "slot_refills_per_msg": ("refills/msg", "higher", "program_counter",
                             "planner/pipeline"),
    "speculated_launch_share": ("%", "lower", "program_counter",
                                "planner/pipeline"),
    "pow_wait_ms": ("ms", "lower", "program_counter", "send queue"),
    "ack_verify_on_device_share": ("%", "higher", "program_counter",
                                   "receive verify"),
    # the review's two: the host's share of a launch and of a send,
    # where a harvest resolves its hits and sends roll in
    "pipeline_host_ms_per_launch.queue": ("ms/launch", "lower",
                                          "program_span",
                                          "planner/pipeline"),
    "sender_host_ms_per_msg.queue": ("ms/msg", "lower", "program_span",
                                     "sender"),
}
MIX = [[0.60, 200, 800], [0.35, 800, 3000], [0.05, 3000, 8000]]


# -- the entries --------------------------------------------------------


def test_queue_1k_is_the_deployment_the_issue_names():
    bench = harness.load(REPO, "queue_1k")
    assert bench.cell == {
        "name": "queue_1k", "config": "sender_queue_1k",
        "traffic": "backlog_1k", "chips": 1, "why": bench.cell["why"]}
    cfg = bench.config
    default = harness.load(REPO, "single_send").config
    for key in ("topology", "test_mode", "ntpb", "extra", "ttl", "acks",
                "recipient_on_host", "solve_backends", "guarantees"):
        assert cfg[key] == default[key], key
    assert cfg["queue_objects"] == 1000
    entry = [c for c in bench.spec["configs"]
             if c["name"] == "sender_queue_1k"][0]
    assert entry["file"] == "benchmarks/configs/sender_queue_1k.json"
    # the stated size is run: what is cut is what the harness cuts
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == ["mixed_extra_bytes", "object_kinds"]
    assert "config 2" in entry["source"] and "sendMsg" in entry["source"]
    assert bench.traffic == {
        "generator": "backlog", "send": "message", "backlog": 1000,
        "report": 64, "body_bytes": MIX, "warm_verify_batches": [64],
        "warm_quiet_sweeps": 1, "warm_max_sweeps": 4}


def test_burst_send_64_runs_the_traffic_file_that_was_there():
    bench = harness.load(REPO, "burst_send_64")
    assert bench.cell == {
        "name": "burst_send_64", "config": "sender_default",
        "traffic": "burst_64", "chips": 1, "why": bench.cell["why"]}
    assert (bench.traffic["generator"], bench.traffic["sweep"],
            bench.traffic["body_bytes"]) == ("closed_loop", 64, MIX)


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_a_layer_metric_lists_the_two_cells(name):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    unit, better, source, layer = NEW_LAYERS[name]
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "sent_msgs_per_s"}
    # the two cells first; cells that joined later (``storm_10k``,
    # ISSUE 38) are one-chip cells in which a solve is a stream too
    assert listed[:2] == CELLS
    chips = {c["name"]: c["chips"] for c in spec["workloads"]}
    assert all(chips[cell] == 1 for cell in listed), listed
    assert (REPO / "benchmarks" / "layers" / (name + ".py")).exists()


@pytest.mark.parametrize("cell", CELLS)
def test_a_new_cell_reports_the_metrics_that_list_no_cells(cell):
    bench = harness.load(REPO, cell)
    assert set(NEW_LAYERS) | {"off_device_solves", "compiles_in_window",
                              "device_idle_share"} \
        <= {m["name"] for m in bench.metrics("per_layer")}
    # at least these, so that a later append turns nothing red; the
    # closed-loop cell times every send by itself, and the backlog's
    # outbox is filled before the window, so submit-to-sent there is a
    # place in the queue and no latency (ISSUE 47)
    ends = {m["name"] for m in bench.metrics("end_to_end")}
    assert ends >= {"sent_msgs_per_s", "setup_s"}
    assert (ends >= {"send_p50_ms", "send_p90_ms"}) \
        == (bench.traffic["generator"] == "closed_loop")
    assert (not ends & {"send_p50_ms", "send_p90_ms"}) \
        == (bench.traffic["generator"] == "backlog")


# -- the rehearsal ------------------------------------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with a rehearsal cell of the ``backlog``
    generator added as new files."""
    root = tmp_path_factory.mktemp("queue_tree")
    shutil.copytree(REPO / "benchmarks", root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    bdir = root / "benchmarks"
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}
    cfg = json.loads((bdir / "configs" / "sender_queue_1k.json")
                     .read_text())
    cfg.update(name=CELL + "_cfg", test_mode=True, ntpb=10, extra=10)
    (bdir / "configs" / (CELL + "_cfg.json")).write_text(json.dumps(cfg))
    (bdir / "traffic" / (CELL + "_mix.json")).write_text(json.dumps({
        "generator": "backlog", "send": "message", "backlog": 12,
        "report": 4, "body_bytes": [[1.0, 40, 300]],
        "warm_verify_batches": [], "warm_quiet_sweeps": 1,
        "warm_max_sweeps": 4}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": CELL + "_cfg", "source": "test", "reduced": [],
        "file": "benchmarks/configs/%s_cfg.json" % CELL, "why": "test"})
    spec["workloads"].append({
        "name": CELL, "config": CELL + "_cfg", "traffic": CELL + "_mix",
        "chips": 1, "why": "test"})
    for metric in spec["per_layer"]:
        if metric["name"] in NEW_LAYERS:
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert {p: p.read_bytes() for p in before} == before, \
        "adding the cell edited an existing file"
    return root


@pytest.fixture
def one_chip(monkeypatch, tmp_path):
    """The CPU stand-in for the chip: the dispatcher takes its
    single-chip rungs, the pipeline calls the kernels' entry points at
    a tile of 8 rows and 4 chunks, and XLA programs with the kernels'
    output contract stand where they are."""
    import jax

    from pybitmessage_tpu.ops import sha512_pallas
    from pybitmessage_tpu.parallel.pow_pallas_sharded import _xla_slab
    from pybitmessage_tpu.pow import pipeline
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher

    monkeypatch.setattr(check, "STALL_SECONDS", 4.0)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: True)
    monkeypatch.setattr(PowDispatcher, "_device_count", lambda self: 1)
    for key, value in (("rows", 8), ("impl", "pallas")):
        monkeypatch.setitem(pipeline.solve_batch_pipelined.__kwdefaults__,
                            key, value)
    # a queue at test difficulty would be packed sixteen to a tile:
    # whole tiles are what refills, so the plan has no pack to choose
    monkeypatch.setattr(pipeline, "PACK_CHOICES", ())
    for name, value in (("DEFAULT_CHUNKS", 4), ("DEFAULT_UNROLL", 1),
                        ("DEFAULT_BATCH_CHUNKS", 4), ("BATCH_UNROLL", 1),
                        ("BATCH_OBJS", 8)):
        monkeypatch.setattr(pipeline, name, value)
    slab = jax.jit(_xla_slab, static_argnames=("rows", "chunks"))

    def search(ih_words, base, target, rows, chunks, unroll, interpret):
        return slab(ih_words, base, target, rows=rows,
                    chunks=chunks * unroll)

    def batch(ih_words, bases, targets, rows, chunks, unroll, interpret,
              pack=1):
        return pipeline._packed_search_xla(
            ih_words, bases, targets,
            lanes=(rows // pack) * 128 * unroll, chunks=chunks)

    monkeypatch.setattr(sha512_pallas, "pallas_search", search)
    monkeypatch.setattr(sha512_pallas, "pallas_batch_search", batch)
    monkeypatch.setattr(sha512_pallas, "pallas_packed_search", batch)
    monkeypatch.setattr(pipeline, "pallas_packed_search", batch)


def _run(tree, *, trace, seed=2**31 + 32, seconds=1.0):
    lines = []
    result = asyncio.run(harness.run_cell(
        harness.load(tree, CELL), seed, seconds, trace, lines.append,
        t_start=time.monotonic()))
    result["lines"] = lines
    return result


def test_the_backlog_cell_runs_streams_and_is_correct(tree, one_chip):
    from pybitmessage_tpu.core.jaxsetup import setup_jax
    setup_jax()
    result = _run(tree, trace=True)
    assert result["correct"] is True, result["lines"]
    assert result["failed"] == 0 and result["attempted"] >= 4
    verdict = result["window"].verdict
    assert {k: v["value"] for k, v in verdict["compared"].items()} \
        == {"invalid_nonces": 0, "undelivered": 0, "off_tier": 0}
    assert set(verdict["attempts_by_backend"]) <= {
        "tpu-pallas-batch", "tpu-pallas"}
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # every new metric but the one that needs a device's kernel time
    assert set(NEW_LAYERS) - set(metrics) == {"kernel_mhash_per_s.queue"}
    assert metrics["off_device_solves"] == 0
    assert metrics["compiles_in_window"] == 0
    assert 0 < metrics["live_slot_share"] <= 100
    # a window of a second begins and ends inside launches: the share
    # is a reading here, and settles only over a window of many
    assert metrics["useful_trial_share.queue"] > 0
    assert metrics["slot_refills_per_msg"] > 0      # the solve took in
    assert metrics["speculated_launch_share"] >= 0
    assert metrics["pow_wait_ms"] > 0
    assert metrics["ack_verify_on_device_share"] == 0    # host, off a chip
    assert metrics["pipeline_host_ms_per_launch.queue"] > 0
    assert metrics["sender_host_ms_per_msg.queue"] > 0
    # needed trials are credited a harvest: no launch needs more than
    # it computed, whatever the window cuts off at its two ends
    assert metrics["useful_trial_share.queue"] <= 100 + 100.0 * 2 / max(
        1, len(result["window"].launches))


def test_the_backlog_is_kept_and_every_ended_send_returned(tree, one_chip):
    import random

    from benchmarks.deployments import build
    bench = harness.load(tree, CELL)

    async def drive():
        gen = harness.load_module(tree, "generators", "backlog").make(
            bench.traffic, random.Random(2**31 + 33))
        dep = await build(bench.config)
        try:
            seen, outstanding = [], []
            for i in range(3):
                ended = await gen.sweep(dep, "t%d" % i)
                outstanding.append(len(gen._outstanding) + len(ended))
                seen.extend(ended)
            return seen, outstanding
        finally:
            await dep.stop()

    seen, outstanding = asyncio.run(drive())
    # topped up to the backlog at the start of every call
    assert outstanding == [12, 12, 12]
    assert len(seen) >= 12 and all(s.t_done is not None for s in seen)
    assert len({s.subject for s in seen}) == len(seen)     # each once
    sizes = sorted(len(s.body) for s in seen[:12])
    assert sizes[0] >= 40 and sizes[-1] <= 300


def test_a_failed_or_a_lost_send_comes_back_without_t_done(monkeypatch):
    import random
    backlog = harness.load_module(REPO, "generators", "backlog")
    gen = backlog.make({"send": "message", "backlog": 3, "report": 2,
                        "body_bytes": [[1.0, 10, 20]]}, random.Random(5))
    statuses = {}

    class Node:
        db = types.SimpleNamespace(
            query=lambda sql, params: list(statuses.items()))

        async def send_message(self, to, frm, subject, body, ttl):
            handle = subject.encode()
            statuses[handle] = "msgqueued"
            return handle

    dep = types.SimpleNamespace(
        sender=Node(), config={"ttl": 600}, to_address="to",
        from_address="from")

    async def drive():
        task = asyncio.ensure_future(gen.sweep(dep, "x"))
        await asyncio.sleep(0.15)
        statuses[b"x-1"] = "toodifficult"
        statuses[b"x-2"] = "msgsent"
        first = await task
        # the third is never sent: after LOST_AFTER it is given up
        monkeypatch.setattr(backlog, "LOST_AFTER", 0.0)
        statuses[b"x-4"] = "ackreceived"
        return first, await gen.sweep(dep, "x")

    first, second = asyncio.run(drive())
    assert {s.subject: s.t_done is not None for s in first} \
        == {"x-1": False, "x-2": True}
    assert [s.status for s in first if s.t_done is None] == ["toodifficult"]
    assert all(s.t_done is None for s in second if s.subject == "x-3")
    assert "x-3" in {s.subject for s in second}


def test_what_was_solved_between_two_calls_is_no_calls():
    """What the node solved while the harness stood between two calls,
    and publishes as the next begins, was not this call's work."""
    import random
    backlog = harness.load_module(REPO, "generators", "backlog")
    gen = backlog.make({"send": "message", "backlog": 5, "report": 2,
                        "body_bytes": [[1.0, 10, 20]]}, random.Random(5))
    statuses = {}

    class Node:
        db = types.SimpleNamespace(
            query=lambda sql, params: list(statuses.items()))

        async def send_message(self, to, frm, subject, body, ttl):
            statuses[subject.encode()] = "msgqueued"
            return subject.encode()

    dep = types.SimpleNamespace(
        sender=Node(), config={"ttl": 600}, to_address="to",
        from_address="from")

    async def drive():
        task = asyncio.ensure_future(gen.sweep(dep, "x"))
        await asyncio.sleep(0.15)
        statuses[b"x-1"] = statuses[b"x-2"] = "msgsent"
        first = await task
        # the harness stands between the calls and holds the loop; what
        # the node solved meanwhile it publishes as the next call begins
        # (one send; another fails)
        time.sleep(0.15)
        task = asyncio.ensure_future(gen.sweep(dep, "x"))
        await asyncio.sleep(0)
        statuses[b"x-3"] = "msgsent"
        statuses[b"x-4"] = "badkey"
        await asyncio.sleep(0.3)
        statuses[b"x-5"] = "ackreceived"
        second = await task
        # a call that follows at once takes nothing out
        statuses[b"x-6"] = statuses[b"x-7"] = "msgsent"
        return first, second, await gen.sweep(dep, "x")

    first, second, third = asyncio.run(drive())
    assert sorted(s.subject for s in first) == ["x-1", "x-2"]
    # the failed one fails the run; the published one is counted nowhere
    assert {s.subject: s.t_done is not None for s in second} \
        == {"x-4": False, "x-5": True}
    assert sorted(s.subject for s in third) == ["x-6", "x-7"]
    assert all(s.t_done is not None for s in third)
    # topped up to five at every call, after what had been taken out
    assert len(gen._outstanding) == 3


@pytest.mark.parametrize("every, raises", [(None, True), (0.2, False)])
def test_an_outbox_that_stands_still_ends_the_run(monkeypatch, every,
                                                  raises):
    """A node that ends no send for STALLED_AFTER seconds is not
    draining its outbox as a stream: the call raises, so the run ends
    non-zero and soon; sends that end one by one, however slowly, keep
    the call waiting for its ``report``."""
    import random
    backlog = harness.load_module(REPO, "generators", "backlog")
    monkeypatch.setattr(backlog, "STALLED_AFTER", 0.35)
    gen = backlog.make({"send": "message", "backlog": 4, "report": 3,
                        "body_bytes": [[1.0, 10, 20]]}, random.Random(5))
    statuses = {}

    class Node:
        db = types.SimpleNamespace(
            query=lambda sql, params: list(statuses.items()))

        async def send_message(self, to, frm, subject, body, ttl):
            statuses[subject.encode()] = "msgqueued"
            return subject.encode()

    dep = types.SimpleNamespace(
        sender=Node(), config={"ttl": 600}, to_address="to",
        from_address="from")

    async def drive():
        task = asyncio.ensure_future(gen.sweep(dep, "x"))
        for i in range(1, 4):
            if every is not None:
                await asyncio.sleep(every)
                statuses[b"x-%d" % i] = "msgsent"
        return await task

    if raises:
        with pytest.raises(RuntimeError, match="not drained as a stream"):
            asyncio.run(drive())
        assert len(gen._outstanding) == 4
    else:
        # 0.6 s in all, longer than the limit, and never 0.35 s still
        assert sorted(s.subject for s in asyncio.run(drive())) \
            == ["x-1", "x-2", "x-3"]


def test_backlog_sizes_are_the_mix_quantiles_in_a_seeded_order():
    import random
    backlog = harness.load_module(REPO, "generators", "backlog")
    closed = harness.load_module(REPO, "generators", "closed_loop")
    params = {"send": "message", "backlog": 1000, "report": 64,
              "body_bytes": MIX}

    def sizes(seed):
        gen = backlog.make(params, random.Random(seed))
        return [len(gen._body()) for _ in range(1000)]

    a, b = sizes(2**31 + 1), sizes(2**31 + 2)
    assert a != b and sorted(a) == sorted(b) \
        == sorted(closed.sweep_sizes(MIX, 1000))


# -- the readers, on hand-made windows ----------------------------------


def _window(before, after, published=0, launches=(), trace=None,
            seconds=50.0):
    sent = [types.SimpleNamespace(t_done=100.0 + i)
            for i in range(published)]
    return harness.Window(
        bench=None, seconds=seconds, setup_s=40.0, sent=sent,
        counters=probes.Counters(before, after), launches=list(launches),
        verdict={"needed_trials": 10**12}, trace=trace)


def _read(name, window):
    return harness.load_module(REPO, "layers", name).read(window)


SLOTS = "pow_pipeline_slots_total"
SPEC = "pow_pipeline_speculation_total"
LAUNCHES = "pow_pipeline_launches_total"


@pytest.mark.parametrize("name, before, after, published, expected", [
    ("live_slot_share", {}, {(LAUNCHES, ("batch",)): 4.0}, 1, None),
    ("live_slot_share", {(SLOTS, ("batch", "live")): 10.0},
     {(SLOTS, ("batch", "live")): 100.0, (SLOTS, ("batch", "idle")): 10.0,
      (SLOTS, ("slab", "live")): 0.0}, 1, 90.0),
    ("slot_refills_per_msg", {}, {}, 3, None),
    ("slot_refills_per_msg", {},
     {("pow_pipeline_refills_total", ("batch",)): 6.0}, 3, 2.0),
    ("slot_refills_per_msg", {},
     {("pow_pipeline_refills_total", ("batch",)): 0.0}, 3, 0.0),
    ("speculated_launch_share", {}, {}, 1, None),
    ("speculated_launch_share", {},
     {(LAUNCHES, ("batch",)): 40.0, (SPEC, ("batch", "launched")): 10.0,
      (SPEC, ("batch", "withheld")): 7.0}, 1, 25.0),
    ("speculated_launch_share", {},
     {(LAUNCHES, ("batch",)): 40.0, (SPEC, ("batch", "withheld")): 7.0},
     1, 0.0),
    ("pow_wait_ms", {("worker_pow_wait_seconds", ()): (1.0, 2)},
     {("worker_pow_wait_seconds", ()): (4.0, 8)}, 1, 500.0),
    ("pow_wait_ms", {}, {}, 1, None),
    ("ack_verify_on_device_share", {},
     {("pow_verify_total", ("device",)): 64.0,
      ("pow_verify_total", ("host",)): 192.0}, 1, 25.0),
    ("ack_verify_on_device_share", {},
     {("pow_verify_total", ("host",)): 12.0}, 1, 0.0),
    ("ack_verify_on_device_share", {}, {}, 1, None),
])
def test_a_counter_reader(name, before, after, published, expected):
    assert _read(name, _window(before, after, published)) == expected


def test_the_kernel_twins_read_the_launches_of_the_traced_window():
    # the window began at 100 + 9 - 50 = 59 and its trace ran 52 s, to
    # 111: the launch dispatched at 112, while the profiler stopped,
    # is not the window's
    launches = [{"program": "batch", "t": t, "trials": trials}
                for t, trials in ((60.0, 4e9), (100.0, 6e9), (112.0, 9e9))]
    launches.append({"program": "slab", "t": 70.0, "trials": 1e9})
    trace = {"window_s": 52.0, "kernel_s": {"batch": 40.0, "slab": 4.0}}
    trials = ("pow_pipeline_needed_trials_total", ("batch",))
    window = _window({trials: 1e9}, {trials: 1e9 + 9.9e9}, 10, launches,
                     trace)
    assert _read("kernel_mhash_per_s.queue", window) \
        == pytest.approx(1e10 / 40.0 / 1e6)
    assert _read("useful_trial_share.queue", window) \
        == pytest.approx(100.0 * 9.9e9 / 1.1e10)
    # untraced, or with nothing published, there is nothing to cut by
    for other in (_window({}, {trials: 1e9}, 10, launches, None),
                  _window({}, {trials: 1e9}, 0, launches, trace)):
        assert _read("kernel_mhash_per_s.queue", other) is None
        assert _read("useful_trial_share.queue", other) is None


def test_the_program_has_the_series_the_new_readers_read():
    from pybitmessage_tpu.pow import pipeline, service     # noqa: F401
    from pybitmessage_tpu.observability import REGISTRY
    names = {fam.name for fam in REGISTRY.families()}
    assert {"pow_pipeline_refills_total", "pow_pipeline_slots_total",
            "pow_pipeline_needed_trials_total",
            "pow_resolve_lag_seconds", "pow_pipeline_speculation_total",
            "worker_pow_wait_seconds"} <= names
