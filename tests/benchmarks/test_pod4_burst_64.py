"""The cell ``pod4_burst_64`` (ISSUE 38), on the CPU.

``burst_send_64``'s sweeps on ``pod4_queue_1k``'s configuration: a
``workloads`` entry, the cell's name in the lists of the per-layer
metrics that read chips, and a traffic file that is ``burst_64`` key
for key plus ``warm_lone_sends`` 1: on four chips a sweep's last
straggler now and then asks for its proof of work alone and takes the
nonce-range partition, a program that no sweep of 64 need ever run and
that takes tens of seconds to trace and lower, so set-up sends one
message alone (``generators/closed_loop.py::send_alone``).  The entry
is held to what the issue names; every metric that lists the cell is
read on the hand-made window of two device planes that
``test_pod4_queue_1k.py`` keeps.

The hand-over is rehearsed on four of the suite's virtual devices: a
sweep's solve starts with its first member, laid out for 64, and the
other 63 are handed over in ONE ``feed`` call, as the 64 acks of a
burst ask for their proof of work within a millisecond.  How many
launch groups and devices took them is recorded and not asserted
(today one group of one device: ``pow/pipeline.py::take_in`` gives the
group whose turn it is everything that has arrived): the PR that deals
one hand-over over the chips turns the record into an assertion.
"""

import asyncio
import hashlib
import json
import pathlib
import random
import sys
import threading
import types

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for _path in (REPO, pathlib.Path(__file__).resolve().parent):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from benchmarks import harness  # noqa: E402
from test_pod4_queue_1k import (EXPECTED, NEW_LAYERS,  # noqa: E402
                                _window)

CELL = "pod4_burst_64"
#: the metrics that list no cells, which every cell reports
EVERY_CELL = {"off_device_solves", "compiles_in_window",
              "device_idle_share"}


# -- the entry ----------------------------------------------------------


def test_the_cell_is_the_one_the_issue_names():
    bench = harness.load(REPO, CELL)
    assert bench.cell == {
        "name": CELL, "config": "sender_queue_1k_pod4",
        "traffic": "burst_64_lone_warm", "chips": 4,
        "why": bench.cell["why"]}
    assert 0 < len(bench.cell["why"]) <= 200
    assert "burst_send_64" in bench.cell["why"]
    # pod4_queue_1k's configuration; burst_send_64's traffic key for
    # key, and one send alone in set-up
    assert bench.config == harness.load(REPO, "pod4_queue_1k").config
    burst = harness.load(REPO, "burst_send_64").traffic
    assert "warm_lone_sends" not in burst
    assert bench.traffic == dict(burst, warm_lone_sends=1)
    assert burst == {
        "generator": "closed_loop", "send": "message", "sweep": 64,
        "body_bytes": [[0.60, 200, 800], [0.35, 800, 3000],
                       [0.05, 3000, 8000]],
        "warm_verify_batches": [4, 8, 16, 32, 64],
        "warm_quiet_sweeps": 2, "warm_max_sweeps": 6}
    cells = bench.spec["workloads"]
    four = [c["name"] for c in cells if c["chips"] == 4]
    assert CELL in four and len(four) <= len(cells) // 2


def test_the_cell_reports_the_chip_metrics_and_those_of_every_cell():
    bench = harness.load(REPO, CELL)
    assert set(NEW_LAYERS) | EVERY_CELL \
        <= {m["name"] for m in bench.metrics("per_layer")}
    # at least these, so that a later append turns nothing red: a
    # closed-loop cell times every send by itself (ISSUE 47)
    assert {m["name"] for m in bench.metrics("end_to_end")} \
        >= {"sent_msgs_per_s", "setup_s", "send_p50_ms", "send_p90_ms"}
    # only the lists grew: the entries are pod4_queue_1k's, the cell
    # after it
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        if m["name"] in NEW_LAYERS:
            assert m["workloads"] == ["pod4_queue_1k", CELL], m["name"]


@pytest.mark.parametrize("name", sorted(set(NEW_LAYERS) | EVERY_CELL))
def test_a_metric_that_lists_the_cell_has_a_reader_with_a_value(name):
    window = _window(traced=True, counted=True, cell=CELL)
    value = harness.load_module(REPO, "layers", name).read(window)
    assert value is not None
    if name in EXPECTED:
        assert value == pytest.approx(EXPECTED[name])


# -- one send alone in set-up -------------------------------------------


class _Node:
    """Publishes a message two polls after it was submitted, and keeps
    how many were unpublished at each submission."""

    def __init__(self):
        self.status, self.polls, self.beside = {}, {}, []

    async def send_message(self, to, frm, subject, body, ttl):
        self.beside.append(sum(1 for v in self.status.values()
                               if v != "msgsent"))
        handle = subject.encode()
        self.status[handle], self.polls[handle] = "msgqueued", 0
        return handle

    def message_status(self, handle):
        self.polls[handle] += 1
        if self.polls[handle] >= 2:
            self.status[handle] = "msgsent"
        return self.status[handle]


@pytest.mark.parametrize("generator", ["closed_loop", "backlog"])
@pytest.mark.parametrize("lone", [None, 0, 2])
def test_warm_lone_sends_are_made_one_at_a_time(generator, lone):
    params = dict(harness.load(REPO, CELL).traffic, generator=generator,
                  backlog=8, report=2, warm_verify_batches=[])
    if lone is None:
        del params["warm_lone_sends"]       # burst_64 as it was
    else:
        params["warm_lone_sends"] = lone
    gen = harness.load_module(REPO, "generators", generator).make(
        params, random.Random(2**31 + 5))
    node = _Node()
    dep = types.SimpleNamespace(
        sender=node, config={"ttl": 600}, to_address="to",
        from_address="from")
    asyncio.run(gen.warm_receive_shapes(dep))
    # each was submitted with nothing unpublished beside it
    assert node.beside == [0] * (lone or 0)
    assert sorted(node.status) == [b"lone%d-%d" % (k, k + 1)
                                   for k in range(lone or 0)]


# -- the hand-over, on four virtual devices -----------------------------


def _items(tag: str, n: int):
    return [(hashlib.sha512(b"%s %d" % (tag.encode(), i)).digest(),
             2 ** 64 // 6000) for i in range(n)]


class Kernel:
    """Stands where ``pallas_batch_search`` is: item ``i`` misses until
    its ``after[i]``-th launch and hits in that one.  Keeps, for every
    launch, the device its arrays were on and the live items it held
    (slots are told apart by their hash words)."""

    def __init__(self, items, after, monkeypatch):
        from pybitmessage_tpu.ops import sha512_pallas
        from pybitmessage_tpu.pow import pipeline
        self.index = {
            np.array(pipeline._hash_words(ih), np.uint32).tobytes(): i
            for i, (ih, _t) in enumerate(items)}
        self.left = dict(enumerate(after))
        self.launches = []          # (device, [live items])
        self._lock = threading.Lock()
        monkeypatch.setattr(sha512_pallas, "pallas_batch_search", self)
        monkeypatch.setattr(pipeline, "_checked_nonce",
                            lambda nonce, initial_hash, target: nonce)

    def __call__(self, ih_words, bases, targets, rows, chunks, unroll,
                 interpret):
        (device,) = ih_words.devices()
        words, targets = np.asarray(ih_words), np.asarray(targets)
        out = np.zeros((len(words), 3), np.uint32)
        live = []
        with self._lock:
            for k in range(len(words)):
                if tuple(targets[k]) == (2 ** 32 - 1,) * 2:
                    out[k] = (1, 0, 0)      # pad or solved: always hits
                    continue
                i = self.index[words[k].tobytes()]
                live.append(i)
                self.left[i] -= 1
                if self.left[i] <= 0:
                    out[k] = (1, 0, i)
            self.launches.append((device, live))
        return out


def test_a_burst_handed_over_in_one_feed_call_is_recorded(
        monkeypatch, record_property):
    """The first ack starts the solve alone, laid out for 64 over four
    devices (eight groups of 64 slots, two a device); the next turn's
    ``feed`` brings the other 63 at once.  Recorded: the groups and the
    devices whose first launch after the hand-over held them."""
    import jax

    from pybitmessage_tpu.pow import pipeline
    devices = jax.devices()[:4]
    assert len(devices) == 4
    first, rest = _items("burst first", 1), _items("burst rest", 63)
    # every object misses twice and hits in its third launch: the burst
    # is still searching when every chip has had its turn
    kernel = Kernel(first + rest, [3] * 64, monkeypatch)
    waiting, asked = [list(rest)], []

    def feed(room):
        asked.append(room)
        if not waiting or room < len(waiting[-1]):
            return []
        return [(ih, target, 0) for ih, target in waiting.pop()]

    stats, solved = {}, []
    results = pipeline.solve_batch_pipelined(
        first, rows=8, impl="pallas",
        plan=pipeline.BatchPlan("batched", 1, 4, [0]), feed=feed,
        expect=64, devices=devices, stats=stats, stall_timeout=30.0,
        on_solved=lambda i, r: solved.append(i))
    # what holds today and after: all 64 resolve, once each, in a
    # layout of two groups a device
    assert len(results) == 64 and all(r is not None for r in results)
    assert sorted(solved) == list(range(64)) and not waiting
    assert (stats["mode"], stats["groups"], stats["devices"]) \
        == ("batched", 8, 4)
    # the record: for each handed-over object, the launch that first
    # held it; one launch is one group's
    took = {}
    for n, (device, live) in enumerate(kernel.launches):
        for i in live:
            if i >= 1:
                took.setdefault(i, (n, device))
    assert sorted(took) == list(range(1, 64))
    groups = sorted({n for n, _device in took.values()})
    chips = sorted({devices.index(d) for _n, d in took.values()})
    largest = max(sum(1 for n, _d in took.values() if n == g)
                  for g in groups)
    record_property("handover_groups", len(groups))
    record_property("handover_devices", len(chips))
    record_property("handover_largest_group", largest)
    print("hand-over of 63 in one feed call: %d group(s) on %d "
          "device(s) %s took them, the largest %d; feed was asked for "
          "%s" % (len(groups), len(chips), chips, largest, asked[:8]))
    assert 1 <= len(chips) <= len(groups) <= 8
    assert 63 / 8 <= largest <= 63
