"""One static shape a Mosaic kernel (ISSUE 27).

Until PR 27 a live autotuner chose ``chunks`` for the Mosaic kernels
from the wall time of slabs it took to have run whole.  Every search
leaves its slab at the first hit, so it read short solves as a fast
device and, at a node's second single solve, asked ``pallas_search``
for 1024 chunks — a shape a v5e does not compile; the ``tpu-pallas``
breaker opened and every solve left the chip for ten minutes.

Held here, on the CPU with the kernels' entry points replaced by
fakes that refuse any shape but the node's own: a node launches one
shape of each kernel however many solves it has made and whatever the
tuner was fed, its breaker stays closed, and the tuner (still sizing
the XLA tier's slabs) is fed the steps a slab really ran and counts
the shape changes it asks for.
"""

import hashlib

import numpy as np
import pytest

from pybitmessage_tpu.observability import REGISTRY
from pybitmessage_tpu.ops import sha512_pallas
from pybitmessage_tpu.pow import pipeline
from pybitmessage_tpu.pow.dispatcher import PowDispatcher, python_solve
from pybitmessage_tpu.pow.pipeline import (
    DEFAULT_BATCH_CHUNKS, DEFAULT_PACKED_CHUNKS, SYNC_SINGLE_STEPS,
    SlabAutotuner, expected_trials, plan_batch)

_MASK64 = (1 << 64) - 1


class HostileTuner(SlabAutotuner):
    """Asks for a shape no kernel has, and counts how often it is
    asked: what PR 24's tuner did to ``pallas_search``."""

    def __init__(self):
        super().__init__()
        self.asked = 0

    def suggest(self, kind, default, lo=None, hi=None):
        self.asked += 1
        return 1 << 20


@pytest.fixture
def one_chip(monkeypatch):
    """A node that believes it sits on one accelerator, with a tuner
    that would ask every kernel for a shape the chip refuses."""
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: True)
    monkeypatch.setattr(PowDispatcher, "_device_count", lambda self: 1)
    # off a chip the pipeline would take its XLA stand-in; small tiles
    # keep the planner's thresholds within a host-solvable difficulty
    for key, value in (("impl", "pallas"), ("rows", 8)):
        monkeypatch.setitem(
            pipeline.solve_batch_pipelined.__kwdefaults__, key, value)
    tuner = HostileTuner()
    monkeypatch.setattr(pipeline, "AUTOTUNER", tuner)
    return tuner


def _fallbacks() -> float:
    fam = REGISTRY.get("pow_fallback_total")
    return sum(child.value for _labels, child in fam.children())


def _refusing(allowed: int, launched: list, answer):
    """A kernel entry point that compiles ``allowed`` chunks only."""
    def kernel(*args, rows, chunks, unroll, interpret=False):
        launched.append((rows, chunks, unroll))
        if chunks != allowed:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: scoped smem, %d chunks" % chunks)
        return answer(*args, chunks=chunks)
    return kernel


def test_a_thousand_single_solves_launch_one_shape_and_keep_the_breaker_closed(
        one_chip, monkeypatch):
    # the node's own tile: an object of more than 8 x 128 x 128
    # expected trials is planned as whole slabs of pallas_search
    monkeypatch.setitem(pipeline.solve_batch_pipelined.__kwdefaults__,
                        "rows", sha512_pallas.DEFAULT_ROWS)
    ih = hashlib.sha512(b"one at a time").digest()
    target = 2 ** 64 // 200000
    assert expected_trials(target) > SYNC_SINGLE_STEPS * 128 * 128
    winner, _ = python_solve(ih, target)
    launched = []

    def hit_at_once(ih_words, base, target, chunks):
        found = np.zeros(chunks, np.int32)
        found[0] = 1
        nonce = np.zeros((chunks, 2), np.uint32)
        nonce[0] = (winner >> 32, winner & 0xFFFFFFFF)
        return found, nonce

    monkeypatch.setattr(
        sha512_pallas, "pallas_search",
        _refusing(sha512_pallas.DEFAULT_CHUNKS, launched, hit_at_once))
    fallbacks0 = _fallbacks()
    d = PowDispatcher(use_native=False)
    for _ in range(1000):
        nonce, _trials = d.solve(ih, target)
        assert nonce == winner and d.last_backend == "tpu-pallas"
    # each solve dispatched its one slab: an object this easy against
    # a slab is not speculated on (pipeline.worth_speculating)
    assert len(launched) == 1000
    assert set(launched) == {(sha512_pallas.DEFAULT_ROWS,
                              sha512_pallas.DEFAULT_CHUNKS,
                              sha512_pallas.DEFAULT_UNROLL)}
    assert d.breakers["tpu-pallas"].state == "closed"
    assert d.breakers["tpu"].state == "closed"
    assert _fallbacks() == fallbacks0
    assert one_chip.asked == 0


def test_batches_launch_one_shape_whatever_the_tuner_was_fed(
        one_chip, monkeypatch):
    target = 2 ** 64 // 40000
    items = [(hashlib.sha512(b"batched %d" % i).digest(), target)
             for i in range(5)]
    winners = {ih: python_solve(ih, t)[0] for ih, t in items}
    by_words = {tuple(int.from_bytes(ih[j:j + 4], "big")
                      for j in range(0, 64, 4)): n
                for ih, n in winners.items()}
    launched = []

    def first_hit(ih_words, bases, targets, chunks):
        out = np.zeros((len(bases), 3), np.uint32)
        for k, words in enumerate(np.asarray(ih_words).reshape(-1, 16)):
            nonce = by_words.get(tuple(int(w) for w in words), 0)
            out[k] = (1, nonce >> 32, nonce & 0xFFFFFFFF)
        return out

    monkeypatch.setattr(
        sha512_pallas, "pallas_batch_search",
        _refusing(DEFAULT_BATCH_CHUNKS, launched, first_hit))
    fallbacks0 = _fallbacks()
    d = PowDispatcher(use_native=False)
    for _ in range(20):
        results = d.solve_batch(items)
        assert [n for n, _t in results] == [winners[ih]
                                            for ih, _t in items]
        assert d.last_backend == "tpu-pallas-batch"
    assert len(launched) >= 20
    assert {chunks for _r, chunks, _u in launched} \
        == {DEFAULT_BATCH_CHUNKS}
    assert d.breakers["tpu-pallas"].state == "closed"
    assert _fallbacks() == fallbacks0
    assert one_chip.asked == 0


@pytest.mark.parametrize("n, trials, mode, chunks", [
    (1, 100, "single-sync", SYNC_SINGLE_STEPS),
    (64, 1000, "packed", DEFAULT_PACKED_CHUNKS),
    (256, 6.8e6, "batched", DEFAULT_BATCH_CHUNKS),     # chan_storm_256
    (64, 1.3e7, "batched", DEFAULT_BATCH_CHUNKS),      # a burst of 1 kB
    (1, 1.3e7, "slab", sha512_pallas.DEFAULT_CHUNKS),    # single_send
])
def test_each_plan_mode_has_one_chunk_count(n, trials, mode, chunks):
    items = [(bytes(64), int(2 ** 64 / trials))] * n
    plan = plan_batch(items)
    assert (plan.mode, plan.chunks) == (mode, chunks)


def test_the_batch_shape_is_the_one_the_storm_settled_at():
    # PR 24-26: every chip run of chan_storm_256 went 64 -> 128 chunks
    # of four tiles of 128 rows in its first sweep and stayed: 8,388,608
    # trials an object a launch.  PR 40 kept the launch and made the
    # step one tile of 64 rows, which is all that a hit or a dead slot
    # throws away
    step = (sha512_pallas.BATCH_ROWS * sha512_pallas.LANE_COLS
            * sha512_pallas.BATCH_UNROLL)
    assert step == 8_192
    assert DEFAULT_BATCH_CHUNKS * step == 8_388_608
    # a done slot skips 16 grid steps a launch, not 1,023
    assert DEFAULT_BATCH_CHUNKS // sha512_pallas.BATCH_INNER == 16
    assert DEFAULT_BATCH_CHUNKS % sha512_pallas.BATCH_INNER == 0


def test_a_queue_on_the_chip_is_launched_at_the_batch_kernels_rows(
        one_chip, monkeypatch):
    """``rows`` is the slab's (128); a queue's launches take the batch
    kernel's own 64, and a caller's fewer rows (the rehearsals' 8) stay
    as they are."""
    launched = []

    def first_step(ih_words, bases, targets, chunks):
        out = np.zeros((len(bases), 3), np.uint32)
        out[:, 0] = 1
        return out

    monkeypatch.setattr(
        sha512_pallas, "pallas_batch_search",
        _refusing(DEFAULT_BATCH_CHUNKS, launched, first_step))
    from pybitmessage_tpu.pow import pipeline
    monkeypatch.setattr(pipeline, "_checked_nonce",
                        lambda nonce, ih, target: nonce)
    items = [(bytes([i]) * 64, 2 ** 64 // 10 ** 7) for i in range(3)]
    plan = pipeline.BatchPlan("batched", 1, DEFAULT_BATCH_CHUNKS,
                              [0, 1, 2])
    for rows, want in ((sha512_pallas.DEFAULT_ROWS, 64), (8, 8)):
        del launched[:]
        pipeline.solve_batch_pipelined(items, rows=rows, impl="pallas",
                                       plan=plan)
        assert {(r, u) for r, _chunks, u in launched} == {(want, 1)}


def test_the_xla_stand_in_scans_no_more_steps_than_it_did(monkeypatch):
    """The stand-in has no early exit: a host without an accelerator
    is not given the Mosaic kernel's 512 steps to scan."""
    from pybitmessage_tpu.pow import pipeline
    seen = []
    real = pipeline._packed_search_xla

    def scan(ih_words, bases, targets, lanes, chunks):
        seen.append((lanes, chunks))
        return real(ih_words, bases, targets, lanes=lanes, chunks=chunks)

    monkeypatch.setattr(pipeline, "_packed_search_xla", scan)
    items = [(bytes([i]) * 64, 2 ** 64 // 3000) for i in range(3)]
    stats = {}
    pipeline.solve_batch_pipelined(
        items, rows=8, impl="xla", stats=stats,
        plan=pipeline.BatchPlan("batched", 1, DEFAULT_BATCH_CHUNKS,
                                [0, 1, 2]))
    assert set(seen) == {(8 * 128, pipeline.XLA_BATCH_CHUNKS)}
    assert stats["chunks"] == pipeline.XLA_BATCH_CHUNKS == 128


def test_a_slab_that_leaves_at_step_k_feeds_the_tuner_k_steps():
    from pybitmessage_tpu.ops.pow_search import solve

    class Recording(SlabAutotuner):
        def __init__(self):
            super().__init__()
            self.fed = []

        def record(self, kind, steps, seconds):
            self.fed.append((kind, steps))
            super().record(kind, steps, seconds)

    lanes, chunks = 64, 8
    ih = hashlib.sha512(b"leaves early").digest()
    target = 2 ** 64 // 700
    winner, _ = python_solve(ih, target)
    whole, rest = divmod(winner, lanes * chunks)
    steps0 = REGISTRY.sample("pow_autotune_steps_total", {"kind": "xla"})
    tuner = Recording()
    nonce, trials = solve(ih, target, lanes=lanes, chunks_per_call=chunks,
                          tuner=tuner)
    assert nonce == winner
    # the first suggestion is the default (nothing recorded yet), so
    # every slab was launched with 8 chunks; the last left at its hit
    expected = [("xla", chunks)] * whole + [("xla", rest // lanes + 1)]
    assert tuner.fed == expected
    assert whole == 1 and rest // lanes + 1 == 2   # one whole slab, then 2 of 8
    assert trials == sum(steps for _k, steps in expected) * lanes
    assert REGISTRY.sample("pow_autotune_steps_total", {"kind": "xla"}) \
        - steps0 == sum(steps for _k, steps in expected)


def test_early_exits_do_not_read_as_a_fast_device():
    """Slabs of 512 steps that all leave at step 3 after 3 steps'
    time: fed the steps that ran, the tuner keeps its shape; fed the
    whole grid (PR 24) it asked for the upper bound."""
    per_step = 0.5 / 512
    honest, fooled = SlabAutotuner(), SlabAutotuner()
    for _ in range(50):
        honest.record("k", 3, 3 * per_step)
        fooled.record("k", 512, 3 * per_step)
    assert honest.suggest("k", 512, lo=256, hi=1024) == 512
    assert fooled.suggest("k", 512, lo=256, hi=1024) == 1024


def test_suggestions_stay_inside_the_callers_bounds_and_changes_are_counted():
    def changes():
        return REGISTRY.sample("pow_autotune_shape_changes_total",
                               {"kind": "t_shapes"})

    c0 = changes()
    t = SlabAutotuner(target_seconds=0.5)
    assert t.suggest("t_shapes", 64, lo=32, hi=128) == 64   # no data
    assert changes() == c0
    for per_step, want in ((1e-6, 128), (1.0, 32), (0.5 / 64, 64)):
        for _ in range(40):
            t.record("t_shapes", 64, 64 * per_step)
        for _ in range(3):      # asked again: the same shape, no change
            assert t.suggest("t_shapes", 64, lo=32, hi=128) == want
    assert changes() == c0 + 3
    assert REGISTRY.sample("pow_slab_autotune_chunks",
                           {"kind": "t_shapes"}) == 64


def test_the_shape_change_series_exists_before_any_change():
    # the benchmark reads "no change" as 0, and "no such series" as a
    # program without the counter
    fam = REGISTRY.get("pow_autotune_shape_changes_total")
    assert ("xla",) in {tuple(labels) for labels, _c in fam.children()}
