"""A send's own latency (ISSUE 47), on the CPU.

``send_p50_ms`` and ``send_p90_ms`` are end-to-end metrics of the five
closed-loop cells.  Held here: the two entries, found by name, list
those cells and no ``backlog`` cell; the readers give
``stats.percentile`` of the window's own times under ``closed_loop``
and nothing under ``backlog``; and the generator times every send of a
sweep by itself.  The last on a node whose eight sends are published
in the REVERSE of their submission, 30 ms apart, on a clock the test
owns: the loop as it stood before this issue (kept below, it watched
the head of the line) gives all eight the last one's time, the
generator gives each its own to within one poll.  A failed send leaves
without a time, and a sweep of one still polls its one handle every
2 ms and never reads the table.
"""

import asyncio
import json
import pathlib
import random
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import harness, stats  # noqa: E402

#: name: (percentile, bound)
LATENCY = {"send_p50_ms": (50, 0.25), "send_p90_ms": (90, 0.25)}
CLOSED = ["chan_storm_256", "single_send", "burst_send_64",
          "pod4_burst_64", "pod4_single_send"]
BACKLOG = ["queue_1k", "pod4_queue_1k", "storm_10k", "pod4_storm_10k"]


# -- the entries --------------------------------------------------------


def _spec():
    return json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", sorted(LATENCY))
def test_the_entry_lists_the_closed_loop_cells(name):
    spec = _spec()
    (entry,) = [m for m in spec["end_to_end"] if m["name"] == name]
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "bound": LATENCY[name][1], "source": "host_clock"}
    assert entry["bound"] <= 0.25
    # the five it was entered with; a later closed-loop cell may follow
    assert listed[:len(CLOSED)] == CLOSED
    # what was there is as it was, found by name
    older = {m["name"]: m for m in spec["end_to_end"]}
    assert older["sent_msgs_per_s"] == {
        "name": "sent_msgs_per_s", "unit": "msgs/s", "better": "higher",
        "bound": 0.15, "source": "host_clock"}
    assert older["setup_s"] == {
        "name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
        "source": "host_clock"}
    assert spec["run_seconds"] == 51


@pytest.mark.parametrize("cell", CLOSED + BACKLOG)
def test_a_cell_reports_a_latency_only_where_every_send_is_timed(cell):
    bench = harness.load(REPO, cell)
    ends = {m["name"] for m in bench.metrics("end_to_end")}
    assert ends >= {"sent_msgs_per_s", "setup_s"}
    generator = bench.traffic["generator"]
    assert generator == ("closed_loop" if cell in CLOSED else "backlog")
    if cell in CLOSED:
        assert ends >= set(LATENCY)
    else:
        assert not ends & set(LATENCY)


# -- the readers, on a synthetic window ---------------------------------


def _window(cell, times_ms, unpublished=0):
    sent = [types.SimpleNamespace(t_submit=100.0 + i,
                                  t_done=100.0 + i + t / 1e3)
            for i, t in enumerate(times_ms)]
    sent += [types.SimpleNamespace(t_submit=1.0, t_done=None)
             for _ in range(unpublished)]
    return harness.Window(
        bench=harness.load(REPO, cell), seconds=51.0, setup_s=40.0,
        sent=sent, counters=None, launches=[], verdict={})


def _read(name, window):
    return harness.load_module(REPO, "end_to_end", name).read(window)


TIMES = [1850.0, 95.5, 410.25, 2600.0, 33.0, 705.0, 1210.0, 88.0, 960.0,
         1500.0, 51.0]


@pytest.mark.parametrize("name", sorted(LATENCY))
@pytest.mark.parametrize("cell", CLOSED)
def test_a_reader_gives_the_percentile_of_the_window_s_own_times(
        name, cell):
    q = LATENCY[name][0]
    # a send that was never published has no time (and fails the run)
    window = _window(cell, TIMES, unpublished=2)
    assert _read(name, window) \
        == pytest.approx(stats.percentile(TIMES, q), abs=1e-6)
    assert _read(name, _window(cell, [])) is None
    assert _read("sent_msgs_per_s", window) \
        == pytest.approx(len(TIMES) / 51.0)


@pytest.mark.parametrize("name", sorted(LATENCY))
@pytest.mark.parametrize("cell", BACKLOG)
def test_a_reader_gives_nothing_under_a_backlog(name, cell):
    # listed by mistake, the cell would print no queue position
    assert _read(name, _window(cell, TIMES)) is None


def test_the_line_of_a_backlog_cell_has_no_latency():
    window = _window("queue_1k", TIMES)
    line = harness.read_metrics(window.bench, "end_to_end", "end_to_end",
                                window)
    assert set(line) == {"sent_msgs_per_s", "setup_s"}
    window = _window("burst_send_64", TIMES)
    line = harness.read_metrics(window.bench, "end_to_end", "end_to_end",
                                window)
    assert set(line) >= {"sent_msgs_per_s", "setup_s"} | set(LATENCY)
    assert line["send_p90_ms"]["unit"] == "ms"


# -- the generator, on a clock the test owns ----------------------------


class _Clock:
    """Stands where ``time`` and ``asyncio`` are in the generator's
    module: ``sleep`` moves the clock and nothing else does."""

    def __init__(self):
        self.now = 5000.25
        self.slept = []

    def monotonic(self):
        return self.now

    def time(self):
        return 1.7e9 + self.now

    async def sleep(self, seconds):
        self.slept.append(seconds)
        self.now += seconds


class _Node:
    """Send ``k`` of a sweep reaches ``final[k]`` at ``due[k]`` seconds
    after the sweep's last submission.  ``db.query`` answers the
    generator's pass as the sent table does: the rows stamped in the
    given second or later."""

    def __init__(self, clock, due, final=None):
        self.clock, self.due = clock, due
        self.final = final or ["msgsent"] * len(due)
        self.handles, self.t_last = [], None
        self.status_reads, self.passes = 0, 0
        self.db = types.SimpleNamespace(query=self._query)

    async def send_message(self, to, frm, subject, body, ttl):
        self.handles.append(subject.encode())
        self.t_last = self.clock.monotonic()
        self.clock.now += 0.001             # a submission takes a while
        return self.handles[-1]

    def at(self, k):
        return self.t_last + self.due[k]

    def _row(self, k):
        """(status, the wall-clock second it was stamped in)."""
        wall = self.clock.time() - self.clock.monotonic()
        if self.clock.monotonic() >= self.at(k):
            return self.final[k], int(wall + self.at(k))
        return "doingmsgpow", int(wall + self.t_last)

    def message_status(self, handle):
        self.status_reads += 1
        return self._row(self.handles.index(handle))[0]

    def _query(self, sql, args):
        assert sql.startswith("SELECT ackdata, status FROM sent")
        self.passes += 1
        rows = [(self.handles[k],) + self._row(k)
                for k in range(len(self.handles))]
        return [(h, status) for h, status, second in rows
                if second >= args[0]]


def _generator(clock, sweep):
    mod = harness.load_module(REPO, "generators", "closed_loop")
    mod.time = clock
    mod.asyncio = types.SimpleNamespace(sleep=clock.sleep)
    gen = mod.make({"send": "message", "sweep": sweep,
                    "body_bytes": [[1.0, 40, 120]]},
                   random.Random(2**31 + 47))
    return mod, gen


def _dep(node):
    return types.SimpleNamespace(sender=node, config={"ttl": 600},
                                 to_address="to", from_address="from")


async def _head_of_the_line(mod, clock, node, sent):
    """The wait loop as it stood before ISSUE 47 (e63defa), word for
    word but for the clock: one pending send is watched."""
    pending = list(sent)
    while pending:
        head = pending[0]
        head.status = node.message_status(head.handle)
        if head.status in mod.SENT_STATES["message"]:
            head.t_done = clock.monotonic()
            pending.pop(0)
            continue
        if head.status in mod.FAILED_STATES:
            pending.pop(0)
            continue
        await clock.sleep(0.02)


#: published in the reverse of their submission, 30 ms apart
REVERSED = [0.03 * (8 - k) for k in range(8)]


def test_every_send_of_a_sweep_gets_its_own_time():
    clock = _Clock()
    mod, gen = _generator(clock, 8)
    node = _Node(clock, REVERSED)
    sent = asyncio.run(gen.sweep(_dep(node), "x"))
    assert [s.handle for s in sent] == node.handles and len(sent) == 8
    for k, s in enumerate(sent):
        # seen by the first pass after it was published
        assert 0 <= s.t_done - node.at(k) < mod.POLL_SWEEP, k
        assert s.status == "msgsent"
    # so the last submitted, published first, has the shortest time
    times = [s.t_done - s.t_submit for s in sent]
    assert times == sorted(times, reverse=True)
    assert times[0] - times[-1] > 0.03 * 7 - mod.POLL_SWEEP
    # one pass a poll, every poll of a sweep's length, and no handle
    # read by itself
    assert set(clock.slept) == {mod.POLL_SWEEP}
    assert node.passes == len(clock.slept) + 1
    assert node.status_reads == 0


def test_the_loop_that_was_gave_them_the_head_of_the_line_s():
    clock = _Clock()
    mod, gen = _generator(clock, 8)
    node = _Node(clock, REVERSED)

    async def wait_as_it_was(_node, sent):
        await _head_of_the_line(mod, clock, node, sent)
    gen._wait_each = wait_as_it_was
    sent = asyncio.run(gen.sweep(_dep(node), "x"))
    # the first submitted is published last, and nobody behind it is
    # looked at before: all eight carry its time
    assert all(s.t_done is not None for s in sent)
    assert min(s.t_done for s in sent) >= node.at(0)
    assert max(s.t_done for s in sent) - min(s.t_done for s in sent) \
        < 1e-9
    # the send published 30 ms after the sweep reads 240 ms
    assert sent[7].t_done - node.at(7) >= 0.03 * 7


def test_a_failed_send_leaves_without_a_time():
    clock = _Clock()
    mod, gen = _generator(clock, 8)
    final = ["msgsent"] * 8
    final[2], final[5] = "toodifficult", "badkey"
    node = _Node(clock, REVERSED, final)
    sent = asyncio.run(gen.sweep(_dep(node), "x"))
    assert [s.t_done is None for s in sent] \
        == [k in (2, 5) for k in range(8)]
    assert (sent[2].status, sent[5].status) == ("toodifficult", "badkey")
    # the sweep ended with its last send, not at SWEEP_TIMEOUT
    assert clock.monotonic() - node.t_last < 0.03 * 8 + mod.POLL_SWEEP
    # and the window counts them failed: the run is not correct
    window = harness.Window(
        bench=harness.load(REPO, "burst_send_64"), seconds=51.0,
        setup_s=40.0, sent=sent, counters=None, launches=[], verdict={})
    assert len(window.published) == 6
    assert _read("send_p50_ms", window) == pytest.approx(stats.percentile(
        [(s.t_done - s.t_submit) * 1e3 for s in window.published], 50))


def test_a_send_that_never_ends_is_given_up_at_the_timeout():
    clock = _Clock()
    mod, gen = _generator(clock, 2)
    node = _Node(clock, [0.05, 1e9])
    sent = asyncio.run(gen.sweep(_dep(node), "x"))
    assert sent[0].t_done is not None and sent[1].t_done is None
    assert 0 <= clock.monotonic() - node.t_last - mod.SWEEP_TIMEOUT \
        < 2 * mod.POLL_SWEEP


def test_a_sweep_of_one_polls_its_one_handle():
    clock = _Clock()
    mod, gen = _generator(clock, 1)
    node = _Node(clock, [0.0371])
    (s,) = asyncio.run(gen.sweep(_dep(node), "x"))
    assert 0 <= s.t_done - node.at(0) < mod.POLL_ONE
    assert set(clock.slept) == {mod.POLL_ONE} == {0.002}
    assert node.status_reads == len(clock.slept) + 1
    assert node.passes == 0         # the table is never read


def test_a_pass_reads_from_the_second_before_the_last_pass_began_in():
    """A row is stamped before it is written: one that waits for the
    table across a second's end and a pass is still read."""
    clock = _Clock()
    mod, gen = _generator(clock, 2)
    asked = []

    class Late(_Node):
        def _query(self, sql, args):
            asked.append((int(self.clock.time()), args[0]))
            return super()._query(sql, args)
    node = Late(clock, [0.9, 2.4])
    asyncio.run(gen.sweep(_dep(node), "x"))
    # the first pass of a generator reads the whole table
    assert asked[0][1] < 0
    for (_, _), (second, read_from) in zip(asked, asked[1:]):
        assert second - 2 <= read_from <= second - 1
