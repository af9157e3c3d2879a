"""One dispatch-ahead loop for every single-chip solve (ISSUE 29).

``pow.pipeline.solve_batch_pipelined`` serves a lone object and a queue
alike: ``plan_batch`` names the kernel and its shape, and
``_PipelineDriver.run`` is the only loop that launches it.  Held here,
on the CPU, with the kernels' entry points replaced on the module
attributes by stand-ins that follow a script (launch k misses, or hits
at grid step s): the launches each of the four plan modes makes, at
which shape, how many it leaves behind unfetched, what it reports as
progress and how many trials it credits.  Since ISSUE 30 a group's next
launch goes ahead of its unread ones only while those are unlikely to
finish it (``pipeline.worth_speculating``): the cases hold which solves
still leave a launch behind, and the rule is held alone on the
benchmark cells' own numbers.
"""

import ast
import hashlib
import math
import pathlib
import sys

import numpy as np
import pytest

from pybitmessage_tpu.observability import REGISTRY
from pybitmessage_tpu.ops import sha512_pallas
from pybitmessage_tpu.ops.pow_search import PowInterrupted
from pybitmessage_tpu.pow import pipeline
from pybitmessage_tpu.pow.dispatcher import python_solve

REPO = pathlib.Path(__file__).resolve().parents[1]

#: trials of one grid step of each kernel at the node's own geometry
SLAB_STEP = 128 * 128 * 5
SLAB = 512 * SLAB_STEP
_WINNERS: dict = {}


#: expected trials above which no host finds a nonce inside a test
SOLVABLE = 200000
#: a lone object hard enough for a second slab to go ahead of the first
HARD = 64 * SLAB


def _item(tag: bytes, expected_trials: int):
    """An object and a nonce that really solves it (found once).  Past
    ``SOLVABLE`` the nonce is made up: see :func:`_skip_recheck`."""
    ih = hashlib.sha512(b"one loop " + tag).digest()
    target = 2 ** 64 // expected_trials
    if expected_trials > SOLVABLE:
        return (ih, target), int.from_bytes(ih[:8], "big")
    if (ih, target) not in _WINNERS:
        _WINNERS[ih, target] = python_solve(ih, target)[0]
    return (ih, target), _WINNERS[ih, target]


def _skip_recheck(monkeypatch):
    """An object hard enough to be speculated on is too hard to solve
    here, so its scripted winner is not one: the hashlib re-check is
    taken out for it (the solvable cases keep it)."""
    monkeypatch.setattr(pipeline, "_checked_nonce",
                        lambda nonce, initial_hash, target: nonce)


def _hard_item(monkeypatch, tag: bytes = b"hard 0"):
    _skip_recheck(monkeypatch)
    return _item(tag, HARD)


def _counted(name: str, kind: str) -> float:
    return REGISTRY.sample(name, {"kind": kind})


def _of(step, k: int):
    """What a script's entry says of object ``k``."""
    return step[k] if isinstance(step, tuple) else step


class Scripted:
    """Stand-ins for the three kernel entry points.  ``script[k]`` says
    what launch ``k`` reports for every live object: None (no hit) or
    the grid step, counted from 1, at which each hits; a tuple says it
    object by object."""

    def __init__(self, script, winners, items=None):
        self.script, self.winners = list(script), list(winners)
        self.launched = []          # (entry point, static shape, bases)
        #: hash words -> item: a batch lays its objects out over groups
        self.index = None if items is None else {
            np.array(pipeline._hash_words(ih), np.uint32).tobytes(): i
            for i, (ih, _t) in enumerate(items)}

    def _step(self, entry, shape, bases):
        step = self.script[len(self.launched)]
        self.launched.append((entry, shape, bases))
        return step

    def search(self, ih_words, base, target, rows, chunks,
               interpret=False, unroll=1):
        assert np.shape(ih_words) == (8, 2) and np.shape(target) == (2,)
        base = (int(base[0]) << 32) | int(base[1])
        step = self._step("pallas_search", (rows, chunks, unroll), [base])
        found = np.zeros(chunks, np.int32)
        nonce = np.zeros((chunks, 2), np.uint32)
        if step:
            found[step - 1] = 1
            nonce[step - 1] = (self.winners[0] >> 32,
                               self.winners[0] & 0xFFFFFFFF)
        return found, nonce

    def _rows(self, entry, shape, ih_words, bases, targets):
        bases = [(int(hi) << 32) | int(lo) for hi, lo in np.asarray(bases)]
        step = self._step(entry, shape, bases)
        words = np.asarray(ih_words)
        out = np.zeros((len(bases), 3), np.uint32)
        for k, (t_hi, t_lo) in enumerate(np.asarray(targets)):
            i = k if self.index is None \
                else self.index.get(words[k].tobytes(), len(self.winners))
            if i >= len(self.winners) or (t_hi, t_lo) == (2 ** 32 - 1,) * 2:
                out[k] = (1, 0, 0)          # pad or solved: always hits
            elif _of(step, i):
                out[k] = (_of(step, i), self.winners[i] >> 32,
                          self.winners[i] & 0xFFFFFFFF)
        return out

    def batch(self, ih_words, bases, targets, rows, chunks,
              interpret=False, unroll=1):
        return self._rows("pallas_batch_search", (rows, chunks, unroll),
                          ih_words, bases, targets)

    def packed(self, ih_words, bases, targets, rows, chunks, pack,
               unroll=1, interpret=False):
        return self._rows("pallas_packed_search",
                          (rows, chunks, pack, unroll), ih_words, bases,
                          targets)

    def install(self, monkeypatch):
        monkeypatch.setattr(sha512_pallas, "pallas_search", self.search)
        monkeypatch.setattr(sha512_pallas, "pallas_batch_search",
                            self.batch)
        monkeypatch.setattr(pipeline, "pallas_packed_search", self.packed)
        return self


#: trials an object a launch of the batch kernel at 8 rows
BATCH8 = 1024 * 8 * 128

# mode, objects (n, expected trials each), rows, the script, and what
# the loop does with it: entry point, static shape, width, trials of a
# grid step, launches, abandoned; for a queue laid out over two groups,
# the group each launch belongs to
CASES = {
    "slab hits in its first slab": (
        "slab", (1, 200000), 128, [3],
        "pallas_search", (128, 512, 5), 1, SLAB_STEP, 1, 0, None),
    "slab misses, then hits": (
        "slab", (1, 200000), 128, [None, 7],
        "pallas_search", (128, 512, 5), 1, SLAB_STEP, 2, 0, None),
    # a queue one launch holds is two groups, launched in turn
    "batched queue": (
        "batched", (2, 50000), 8, [2, 2],
        "pallas_batch_search", (8, 1024, 1), 64, 8 * 128, 2, 0, [0, 1]),
    "packed storm": (
        "packed", (4, 16), 128, [1],
        "pallas_packed_search", (128, 64, 4, 1), 4, 32 * 128, 1, 0, None),
    "single-sync misses, then hits": (
        "single-sync", (1, 16), 128, [None, 5],
        "pallas_packed_search", (128, 8, 1, 1), 1, 128 * 128, 2, 0, None),
    # expected to need 64 slabs: the second goes ahead of the first,
    "hard slab keeps two in flight, hits in its first": (
        "slab", (1, HARD), 128, [3, None],
        "pallas_search", (128, 512, 5), 1, SLAB_STEP, 2, 1, None),
    "hard slab misses, then hits": (
        "slab", (1, HARD), 128, [None, 7, None],
        "pallas_search", (128, 512, 5), 1, SLAB_STEP, 3, 1, None),
    # 32 objects that each need about one launch, left alone when the
    # other group has finished: all 32 finishing in the launch in flight
    # is out of the question, so the next goes ahead
    "batched group left alone with 32 live is speculated on": (
        "batched", (64, BATCH8), 8, [2, 2, None],
        "pallas_batch_search", (8, 1024, 1), 64, 8 * 128, 3, 1,
        [0, 1, 1]),
    # the same group once 31 have hit: the launch that went ahead while
    # 32 were live is read, and nothing goes ahead of it for the last
    "batched group with one live is not": (
        "batched", (64, BATCH8), 8,
        [2, (None,) * 32 + (2,) * 31 + (None,), 5],
        "pallas_batch_search", (8, 1024, 1), 64, 8 * 128, 3, 0,
        [0, 1, 1]),
}


#: launches that went out ahead of an unread one of their group and
#: were read all the same: for each launch of the case, how many had
#: been read when it was dispatched (every other case: all before it)
SENT_AFTER = {"batched group with one live is not": [0, 0, 1]}


def _foreseen(script, shares, turns, width, slab, step_trials,
              sent_after=None):
    """What reading ``script``'s launches in order does, object by
    object: trials credited, ends of miss-free slabs reported, trials
    executed (a slot that was solved or pad when its launch went out
    runs one always-hit step; one that was resolved while the launch
    was in flight ran what the launch reports, credited to nobody).
    ``shares`` are the items of each group, ``turns`` the group of each
    launch."""
    n = sum(len(share) for share in shares)
    credit, reported, executed = [0] * n, [], 0
    live = set(range(n))
    live_after = [set(live)]        # after 0, 1, ... launches read
    turn = [0] * len(shares)
    for k, (step, g) in enumerate(zip(script, turns)):
        turn[g] += 1
        executed += (width - len(shares[g])) * step_trials      # pad
        sent_live = live_after[sent_after[k] if sent_after else k]
        for i in shares[g]:
            if i not in live:
                executed += ((_of(step, i) or slab // step_trials)
                             if i in sent_live else 1) * step_trials
            elif _of(step, i):
                credit[i] += _of(step, i) * step_trials
                executed += _of(step, i) * step_trials
                live.discard(i)
            else:
                credit[i] += slab
                executed += slab
                reported.append((i, turn[g] * slab))
        live_after.append(set(live))
    return credit, reported, executed


@pytest.mark.parametrize("case", sorted(CASES))
def test_each_mode_launches_what_its_targets_call_for(case, monkeypatch):
    (mode, (n, expected), rows, script, entry, shape, width, step_trials,
     launches, abandoned, turns) = CASES[case]
    if expected > SOLVABLE:
        _skip_recheck(monkeypatch)
    items, winners = zip(*(_item(b"%s %d" % (mode.encode(), i), expected)
                           for i in range(n)))
    kernels = Scripted(script, winners, items).install(monkeypatch)
    kind = pipeline._KIND[mode]
    before = {name: _counted(name, kind) for name in (
        "pow_pipeline_launches_total",
        "pow_pipeline_abandoned_launches_total",
        "pow_pipeline_executed_trials_total")}
    reported, stats = [], {}
    results = pipeline.solve_batch_pipelined(
        list(items), rows=rows, impl="pallas", stats=stats,
        progress=lambda i, nxt: reported.append((i, nxt)))

    assert stats["mode"] == mode and stats["width"] == width
    assert [(e, s) for e, s, _b in kernels.launched] \
        == [(entry, shape)] * launches
    chunks = shape[1]
    slab = chunks * step_trials
    if turns is None:
        shares, turns = [list(range(n))], [0] * launches
    else:
        shares = [list(range(n // 2)), list(range(n // 2, n))]
    # every launch of a group starts where its last one ended
    assert [b[0] for _e, _s, b in kernels.launched] \
        == [turns[:k].count(g) * slab for k, g in enumerate(turns)]
    grown = {name: _counted(name, kind) - v for name, v in before.items()}
    assert grown["pow_pipeline_launches_total"] == launches
    assert grown["pow_pipeline_abandoned_launches_total"] == abandoned
    # of the launches read: a miss-free slab's end is the checkpoint,
    # once a slab and object, and trials are credited by the grid steps
    # a search really ran
    read = launches - abandoned
    credit, checkpoints, executed = _foreseen(
        script[:read], shares, turns[:read], width, slab, step_trials,
        SENT_AFTER.get(case))
    assert reported == checkpoints
    assert results == list(zip(winners, credit))
    assert grown["pow_pipeline_executed_trials_total"] == executed


#: a batch launch at the node's geometry: 1,024 steps of one tile of 64
#: rows x 128 lanes (128 steps of four tiles of 128 rows until PR 40:
#: the same trials)
BATCH_LAUNCH = 1024 * 64 * 128 * 1

# the benchmark cells' own numbers (PERF.md section 4): trials the
# unread launch covers, live objects, expected trials each, and the
# chance that the unread launch finishes them all, to two digits
RULE = {
    "single_send's ack against a slab": (SLAB, 1, 1.08e7, 0.98, False),
    "single_send's message against a slab": (SLAB, 1, 1.56e7, 0.93, False),
    "the storm's 64 live against a batch launch": (
        BATCH_LAUNCH, 64, 8.8e6, 2.8e-14, True),
    "the storm's last 8": (BATCH_LAUNCH, 8, 8.8e6, 0.020, True),
    "the storm's last 4": (BATCH_LAUNCH, 4, 8.8e6, 0.14, False),
    "pod_hard's object on one chip against a slab": (
        SLAB, 1, 4.9e9, 0.0085, True),
    "two slabs unread of an object worth three": (
        2 * SLAB, 1, 3 * SLAB, 0.49, False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_on_the_cells_own_numbers(case):
    covered, live, expected, p_finish, ahead = RULE[case]
    target = int(2 ** 64 / expected)
    # what the rule compares with its threshold, worked out here
    p = (1 - math.exp(-covered / pipeline.expected_trials(target))) ** live
    assert p == pytest.approx(p_finish, rel=0.05)
    assert (p < pipeline.SPECULATE_BELOW) is ahead
    assert pipeline.worth_speculating(covered, [target] * live) is ahead


@pytest.mark.parametrize("expected,decision,speculative", [
    # each slab is launched once the one before it was read,
    (200000, "withheld", [False, False]),
    # the second and the third went ahead of the slab before them
    (HARD, "launched", [False, True, True]),
])
def test_each_decision_is_counted_and_marks_its_launch(
        expected, decision, speculative, monkeypatch):
    """A lone solve that misses, then hits, decides twice, once with
    each of the two slabs in flight: ``pow_pipeline_speculation_total``
    counts the decisions, and the ``pow.launch`` span of a launch that
    went ahead says so."""
    from pybitmessage_tpu.observability import TRACER

    if expected > SOLVABLE:
        _skip_recheck(monkeypatch)
    item, winner = _item(b"decided", expected)
    Scripted([None, 6, None], [winner]).install(monkeypatch)

    def counted():
        return {d: REGISTRY.sample("pow_pipeline_speculation_total",
                                   {"kind": "slab", "decision": d})
                for d in ("launched", "withheld")}

    before = counted()
    TRACER.clear()
    pipeline.solve_batch_pipelined([item], impl="pallas")
    grown = {d: v - before[d] for d, v in counted().items()}
    assert grown == {"launched": 0, "withheld": 0, decision: 2}
    assert [sp.attrs["speculative"]
            for sp in TRACER.recent(50, name="pow.launch")] == speculative


def test_should_stop_returns_the_answer_of_the_slab_in_flight(monkeypatch):
    # hard enough for its second slab to go ahead of the first
    item, winner = _hard_item(monkeypatch)
    kernels = Scripted([None, 9], [winner]).install(monkeypatch)
    polls = []

    def should_stop():
        polls.append(len(kernels.launched))
        return len(polls) > 1

    abandoned0 = _counted("pow_pipeline_abandoned_launches_total", "slab")
    reported = []
    [(nonce, trials)] = pipeline.solve_batch_pipelined(
        [item], impl="pallas", should_stop=should_stop,
        progress=lambda i, nxt: reported.append(nxt))
    # the first slab was read and missed; the stop came with the second
    # in flight, which was read before giving up, and held the answer
    assert polls == [0, 2] and len(kernels.launched) == 2
    assert (nonce, trials) == (winner, SLAB + 9 * SLAB_STEP)
    assert reported == [SLAB]
    assert _counted("pow_pipeline_abandoned_launches_total", "slab") \
        == abandoned0

    # with no answer in flight the stop is an interrupt, after the
    # slab in flight was read and its end reported
    Scripted([None, None], [winner]).install(monkeypatch)
    polls.clear()
    reported.clear()
    with pytest.raises(PowInterrupted):
        pipeline.solve_batch_pipelined(
            [item], impl="pallas", should_stop=should_stop,
            progress=lambda i, nxt: reported.append(nxt))
    assert reported == [SLAB, 2 * SLAB]


def test_the_first_launch_of_a_shape_runs_on_the_drivers_worker_thread(
        monkeypatch):
    """A process's first launch of a shape traces and lowers the kernel,
    and on the chip that took 2.5 times as long from a deep stack
    (PERF.md section 6, PR 29): with the watchdog on it runs where the
    fetches do, on the driver's own worker thread, its span with it;
    every later launch is made in place."""
    import threading

    from pybitmessage_tpu.observability import TRACER, trace

    item, winner = _hard_item(monkeypatch)
    monkeypatch.setattr(pipeline, "_TRACED_SHAPES", set())
    here = threading.current_thread().name
    threads = []

    def install(script):
        search = Scripted(script, [winner]).install(monkeypatch).search

        def named(*args, **kwargs):
            threads.append(threading.current_thread().name)
            return search(*args, **kwargs)
        monkeypatch.setattr(sha512_pallas, "pallas_search", named)

    install([None, 2, None])
    TRACER.clear()
    with trace("t.caller") as caller:
        [(nonce, _trials)] = pipeline.solve_batch_pipelined(
            [item], impl="pallas", stall_timeout=30.0)
    assert nonce == winner
    assert threads[0].startswith("bmtpu-pow-slab-guard")
    assert threads[1:] == [here, here]
    # the launch's span keeps its place under the caller's
    launches = TRACER.recent(50, name="pow.launch")
    assert [s.parent_id for s in launches] == [caller.span_id] * 3
    assert pipeline._TRACED_SHAPES \
        == {(("pallas_slab", (128, 512, 5, False)), 0)}
    # the next solve of the shape launches in place from the start,
    del threads[:]
    install([1, None])
    pipeline.solve_batch_pipelined([item], impl="pallas",
                                   stall_timeout=30.0)
    assert threads == [here, here]
    # and so does a first launch with the watchdog off
    monkeypatch.setattr(pipeline, "_TRACED_SHAPES", set())
    del threads[:]
    install([1, None])
    pipeline.solve_batch_pipelined([item], impl="pallas")
    assert threads == [here, here]


def test_a_resumed_lone_solve_starts_at_its_checkpoint(monkeypatch):
    item, winner = _hard_item(monkeypatch)
    kernels = Scripted([1, None], [winner]).install(monkeypatch)
    start = (1 << 64) - SLAB_STEP           # the next slab wraps
    pipeline.solve_batch_pipelined([item], impl="pallas",
                                   start_nonces=[start])
    assert [b for _e, _s, b in kernels.launched] \
        == [[start], [(start + SLAB) & (2 ** 64 - 1)]]


def test_the_benchmarks_launch_log_sees_every_slab_launch(monkeypatch):
    """``benchmarks/kernels.json`` patches ``pallas_search`` on
    ``ops.sha512_pallas`` only: the loop has to look it up there at
    every launch, or ``kernel_mhash_per_s.slab`` and
    ``useful_trial_share.slab`` vanish from ``single_send``."""
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from benchmarks import probes

    item, winner = _hard_item(monkeypatch)
    kernels = Scripted([None, 4, None], [winner]).install(monkeypatch)
    log = probes.LaunchLog(REPO)
    log.install()
    try:
        pipeline.solve_batch_pipelined([item], impl="pallas")
    finally:
        log.uninstall()
    records = log.resolve()
    assert len(records) == len(kernels.launched) == 3
    assert {r["program"] for r in records} == {"slab"}
    assert log.shapes() == {"slab": [{"rows": 128, "chunks": 512,
                                      "unroll": 5}]}
    # the steps each launch really ran, the one left behind included
    assert [r["trials"] for r in records] \
        == [SLAB, 4 * SLAB_STEP, SLAB]


def test_the_dispatcher_sends_a_lone_object_through_the_pipeline(
        monkeypatch):
    """``__call__`` and a ``solve_batch`` of one take the same rung and
    the same entry; the plan, not the dispatcher, picks the kernel."""
    from pybitmessage_tpu.pow.dispatcher import PowDispatcher
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: True)
    monkeypatch.setattr(PowDispatcher, "_device_count", lambda self: 1)
    monkeypatch.setitem(pipeline.solve_batch_pipelined.__kwdefaults__,
                        "impl", "pallas")
    plans = []
    plan_batch = pipeline.plan_batch
    monkeypatch.setattr(
        pipeline, "plan_batch",
        lambda items, **kw: plans.append(len(items))
        or plan_batch(items, **kw))
    hard, winner = _item(b"slab 0", 200000)
    tiny, tiny_winner = _item(b"single-sync 0", 16)
    d = PowDispatcher(use_native=False)
    attempts0 = REGISTRY.sample("pow_attempts_total",
                                {"backend": "tpu-pallas"})
    for solve in (lambda it: d(*it), lambda it: d.solve_batch([it])[0]):
        for it, won, entry in ((hard, winner, "pallas_search"),
                               (tiny, tiny_winner, "pallas_packed_search")):
            kernels = Scripted([1, None], [won]).install(monkeypatch)
            assert solve(it)[0] == won
            assert d.last_backend == "tpu-pallas"
            assert {e for e, _s, _b in kernels.launched} == {entry}
    # planned once a solve, and by nobody else
    assert plans == [1, 1, 1, 1]
    assert REGISTRY.sample("pow_attempts_total",
                           {"backend": "tpu-pallas"}) == attempts0 + 4
    assert all(b.state == "closed" for b in d.breakers.values())


def test_ops_holds_kernels_only():
    """No file under ``ops/`` imports from ``pybitmessage_tpu.pow``,
    and the SHA-512 kernel module opens no span and records no launch:
    the host loop lives one layer up."""
    ops = REPO / "pybitmessage_tpu" / "ops"
    for path in sorted(ops.glob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                module = node.module or ""
                names = [module] + [module + "." + a.name
                                    for a in node.names]
                if node.level == 2:     # from ..x: x under the package
                    names = ["pybitmessage_tpu." + n.lstrip(".")
                             for n in names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            bad = [n for n in names
                   if n == "pybitmessage_tpu.pow"
                   or n.startswith("pybitmessage_tpu.pow.")]
            assert not bad, "%s imports %s" % (path.name, bad)
    calls = {node.func.id
             for node in ast.walk(ast.parse(
                 (ops / "sha512_pallas.py").read_text()))
             if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Name)}
    assert not calls & {"trace", "record_launch"}
    assert "register_program" in calls
