"""One dispatch-ahead loop for every single-chip solve (ISSUE 29).

``pow.pipeline.solve_batch_pipelined`` serves a lone object and a queue
alike: ``plan_batch`` names the kernel and its shape, and
``_PipelineDriver.run`` is the only loop that launches it.  Held here,
on the CPU, with the kernels' entry points replaced on the module
attributes by stand-ins that follow a script (launch k misses, or hits
at grid step s): the launches each of the four plan modes makes, at
which shape, how many it leaves behind unfetched, what it reports as
progress and how many trials it credits — the sequence the lone-object
loop of ``ops/sha512_pallas.solve`` and ``_solve_single_sync`` produced
before they were folded into the driver.
"""

import ast
import hashlib
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


def _item(tag: bytes, expected_trials: int):
    """An object and a nonce that really solves it (found once)."""
    ih = hashlib.sha512(b"one loop " + tag).digest()
    target = 2 ** 64 // expected_trials
    if (ih, target) not in _WINNERS:
        _WINNERS[ih, target] = python_solve(ih, target)[0]
    return (ih, target), _WINNERS[ih, target]


def _counted(name: str, kind: str) -> float:
    return REGISTRY.sample(name, {"kind": kind})


class Scripted:
    """Stand-ins for the three kernel entry points.  ``script[k]`` says
    what launch ``k`` reports for every live object: None (no hit) or
    the grid step, counted from 1, at which each hits."""

    def __init__(self, script, winners):
        self.script, self.winners = list(script), list(winners)
        self.launched = []          # (entry point, static shape, bases)

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

    def _rows(self, entry, shape, bases, targets):
        bases = [(int(hi) << 32) | int(lo) for hi, lo in np.asarray(bases)]
        step = self._step(entry, shape, bases)
        out = np.zeros((len(bases), 3), np.uint32)
        for k, (t_hi, t_lo) in enumerate(np.asarray(targets)):
            if k >= len(self.winners) or (t_hi, t_lo) == (2 ** 32 - 1,) * 2:
                out[k] = (1, 0, 0)          # pad or solved: always hits
            elif step:
                out[k] = (step, self.winners[k] >> 32,
                          self.winners[k] & 0xFFFFFFFF)
        return out

    def batch(self, ih_words, bases, targets, rows, chunks,
              interpret=False, unroll=1):
        return self._rows("pallas_batch_search", (rows, chunks, unroll),
                          bases, targets)

    def packed(self, ih_words, bases, targets, rows, chunks, pack,
               unroll=1, interpret=False):
        return self._rows("pallas_packed_search",
                          (rows, chunks, pack, unroll), bases, targets)

    def install(self, monkeypatch):
        monkeypatch.setattr(sha512_pallas, "pallas_search", self.search)
        monkeypatch.setattr(sha512_pallas, "pallas_batch_search",
                            self.batch)
        monkeypatch.setattr(pipeline, "pallas_packed_search", self.packed)
        return self


# mode, objects (n, expected trials each), rows, the script, and what
# the parent's loops did with it: entry point, static shape, width,
# trials of a grid step, launches, abandoned, slabs reported miss-free
CASES = {
    "slab hits in its first slab": (
        "slab", (1, 200000), 128, [3, None],
        "pallas_search", (128, 512, 5), 1, SLAB_STEP, 2, 1, 0),
    "slab misses, then hits": (
        "slab", (1, 200000), 128, [None, 7, None],
        "pallas_search", (128, 512, 5), 1, SLAB_STEP, 3, 1, 1),
    "batched queue": (
        "batched", (2, 50000), 8, [2, None],
        "pallas_batch_search", (8, 128, 4), 64, 8 * 128 * 4, 2, 1, 0),
    "packed storm": (
        "packed", (4, 16), 128, [1, None],
        "pallas_packed_search", (128, 64, 4, 1), 4, 32 * 128, 2, 1, 0),
    "single-sync misses, then hits": (
        "single-sync", (1, 16), 128, [None, 5],
        "pallas_packed_search", (128, 8, 1, 1), 1, 128 * 128, 2, 0, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_each_mode_launches_what_the_parent_launched(case, monkeypatch):
    (mode, (n, expected), rows, script, entry, shape, width, step_trials,
     launches, abandoned, misses) = CASES[case]
    items, winners = zip(*(_item(b"%s %d" % (mode.encode(), i), expected)
                           for i in range(n)))
    kernels = Scripted(script, winners).install(monkeypatch)
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
    # every launch starts where the one before it ended
    assert [b[0] for _e, _s, b in kernels.launched] \
        == [k * slab for k in range(launches)]
    grown = {name: _counted(name, kind) - v for name, v in before.items()}
    assert grown["pow_pipeline_launches_total"] == launches
    assert grown["pow_pipeline_abandoned_launches_total"] == abandoned
    # a miss-free slab's end is the checkpoint, once a slab and object
    assert reported == [(i, (m + 1) * slab)
                        for m in range(misses) for i in range(n)]
    # trials are credited by the grid steps a search really ran
    hit_step = next(s for s in script if s)
    credit = misses * slab + hit_step * step_trials
    assert results == [(w, credit) for w in winners]
    pads = width - n
    assert grown["pow_pipeline_executed_trials_total"] \
        == n * credit + pads * step_trials * (misses + 1)


def test_should_stop_returns_the_answer_of_the_slab_in_flight(monkeypatch):
    item, winner = _item(b"slab 0", 200000)
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

    item, winner = _item(b"slab 0", 200000)
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
        == {("pallas_slab", (128, 512, 5, False))}
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
    item, winner = _item(b"slab 0", 200000)
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

    item, winner = _item(b"slab 0", 200000)
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
