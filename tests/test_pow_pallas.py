"""Pallas/Mosaic PoW kernel — runs only on real accelerator hardware.

The CI suite forces a virtual CPU mesh (conftest), where the Mosaic
kernel cannot execute natively, and interpret mode evaluates the
160-round straight-line kernel too slowly to be usable as a tier-1
test (minutes per 1k-trial slab).  These tests therefore skip on CPU
and are exercised on the real chip, through the host loop the node
itself uses (``pow.pipeline.solve_batch_pipelined``).

The interpret-mode parity checks at the bottom are the exception:
marked ``slow`` (full CI matrix / ``-m slow``), they run the EXACT
kernel body through the Pallas interpreter on one minimal tile and
compare against brute-force host winners — the automated form of the
manual verification done when the kernel landed.
"""

import hashlib

import jax
import pytest

from pybitmessage_tpu.utils.hashes import double_sha512

requires_accelerator = pytest.mark.skipif(
    jax.default_backend() == "cpu",
    reason="Mosaic kernel needs a real TPU; interpret mode is too slow")


@requires_accelerator
@pytest.mark.parametrize("mode, chunks, n, target", [
    ("slab", 32, 1, 2 ** 55),       # pallas_search, one object alone
    ("batched", 64, 3, 2 ** 45),    # pallas_batch_search, a queue
])
def test_pipeline_solves_on_the_chip(mode, chunks, n, target):
    from pybitmessage_tpu.pow.pipeline import (BatchPlan,
                                               solve_batch_pipelined)

    items = [(hashlib.sha512(b"pallas tpu test %d" % i).digest(), target)
             for i in range(n)]
    results = solve_batch_pipelined(
        items, plan=BatchPlan(mode, 1, chunks, list(range(n))))
    for (ih, target), (nonce, trials) in zip(items, results):
        check = double_sha512(nonce.to_bytes(8, "big") + ih)
        assert int.from_bytes(check[:8], "big") <= target
        assert trials > 0


@requires_accelerator
def test_dispatcher_prefers_pallas_on_accelerator():
    from pybitmessage_tpu.pow import PowDispatcher

    d = PowDispatcher(use_native=False)
    ih = hashlib.sha512(b"pallas dispatch").digest()
    nonce, _ = d.solve(ih, 2 ** 55)
    assert d.last_backend == "tpu-pallas"
    check = double_sha512(nonce.to_bytes(8, "big") + ih)
    assert int.from_bytes(check[:8], "big") <= 2 ** 55


@requires_accelerator
def test_pallas_sharded_1dev_mesh_matches_direct():
    """The sharded tier must run the production Mosaic kernel per chip:
    on a 1-device mesh its rate must be within ~2x of the direct
    Pallas solve at the same slab (it IS the same kernel; the margin
    absorbs shard_map dispatch overhead and rate noise through the
    relay).  VERDICT r2 #1's real-chip check."""
    import time

    from pybitmessage_tpu.parallel import make_mesh, pallas_sharded_solve
    from pybitmessage_tpu.pow.pipeline import (BatchPlan,
                                               solve_batch_pipelined)

    ih = hashlib.sha512(b"sharded == direct").digest()
    target = 2 ** 40          # unreachable-ish: forces multiple slabs
    rows, chunks = 128, 128   # production row width (x unroll default)

    def solve(ih, target, rows, chunks_per_call, should_stop):
        return solve_batch_pipelined(
            [(ih, target)], rows=rows, should_stop=should_stop,
            plan=BatchPlan("slab", 1, chunks_per_call, [0]))[0]

    def timed(fn):
        t0 = time.monotonic()
        try:
            fn()
        except Exception:
            raise
        return time.monotonic() - t0

    # warm both compiled paths, then time a fixed trial budget via
    # should_stop after N calls
    calls = {"n": 0}

    def stop_after(n):
        def cb():
            calls["n"] += 1
            return calls["n"] > n
        return cb

    from pybitmessage_tpu.ops.pow_search import PowInterrupted

    mesh = make_mesh(1)
    for warm in range(1):
        calls["n"] = 0
        try:
            solve(ih, target, rows=rows, chunks_per_call=chunks,
                  should_stop=stop_after(2))
        except PowInterrupted:
            pass
        calls["n"] = 0
        try:
            pallas_sharded_solve(ih, target, mesh, rows=rows,
                                 chunks_per_call=chunks,
                                 should_stop=stop_after(2))
        except PowInterrupted:
            pass

    def run_direct():
        calls["n"] = 0
        try:
            solve(ih, target, rows=rows, chunks_per_call=chunks,
                  should_stop=stop_after(8))
        except PowInterrupted:
            pass

    def run_sharded():
        calls["n"] = 0
        try:
            pallas_sharded_solve(ih, target, mesh, rows=rows,
                                 chunks_per_call=chunks,
                                 should_stop=stop_after(8))
        except PowInterrupted:
            pass

    t_direct = timed(run_direct)
    t_sharded = timed(run_sharded)
    assert t_sharded < 2.0 * t_direct, (
        "sharded path %.2fs vs direct %.2fs" % (t_sharded, t_direct))


@requires_accelerator
def test_pallas_sharded_solve_on_chip_finds_nonce():
    from pybitmessage_tpu.parallel import make_mesh, pallas_sharded_solve

    ih = hashlib.sha512(b"sharded pallas on chip").digest()
    target = 2 ** 55
    mesh = make_mesh(1)
    nonce, trials = pallas_sharded_solve(ih, target, mesh,
                                         chunks_per_call=32)
    check = double_sha512(nonce.to_bytes(8, "big") + ih)
    assert int.from_bytes(check[:8], "big") <= target
    assert trials > 0


@requires_accelerator
def test_dispatcher_batches_on_single_chip():
    from pybitmessage_tpu.pow import PowDispatcher

    d = PowDispatcher(use_native=False)
    items = [(hashlib.sha512(b"disp batch %d" % i).digest(), 2 ** 45)
             for i in range(2)]
    results = d.solve_batch(items)
    assert d.last_backend == "tpu-pallas-batch"
    for (ih, target), (nonce, _) in zip(items, results):
        check = double_sha512(nonce.to_bytes(8, "big") + ih)
        assert int.from_bytes(check[:8], "big") <= target


# ---------------------------------------------------------------------------
# interpret-mode kernel parity vs brute-force winners (no TPU needed;
# slow tier — the Pallas interpreter evaluates the 160-round
# straight-line body per lane)
# ---------------------------------------------------------------------------


def _ih_words(ih: bytes):
    import jax.numpy as jnp
    words = [int.from_bytes(ih[i:i + 8], "big") for i in range(0, 64, 8)]
    return jnp.array([[w >> 32, w & 0xFFFFFFFF] for w in words],
                     dtype=jnp.uint32)


def _brute_values(ih: bytes, start: int, n: int) -> list[int]:
    return [int.from_bytes(double_sha512(
        nonce.to_bytes(8, "big") + ih)[:8], "big")
        for nonce in range(start, start + n)]


@pytest.mark.slow
def test_interpret_kernel_parity_single():
    """One (1, 128) interpret-mode tile must report exactly the
    brute-force argmin when the target admits only that nonce."""
    import jax.numpy as jnp
    import numpy as np

    from pybitmessage_tpu.ops.sha512_pallas import pallas_search

    ih = hashlib.sha512(b"interpret parity single").digest()
    values = _brute_values(ih, 0, 128)
    best = min(values)
    winner = values.index(best)

    base = jnp.array([0, 0], dtype=jnp.uint32)
    target = jnp.array([best >> 32, best & 0xFFFFFFFF], dtype=jnp.uint32)
    found, nonce = pallas_search(_ih_words(ih), base, target,
                                 rows=1, chunks=1, unroll=1,
                                 interpret=True)
    found = np.asarray(found)
    nonce = np.asarray(nonce)
    assert found[0], "kernel missed a nonce the target admits"
    got = (int(nonce[0, 0]) << 32) | int(nonce[0, 1])
    assert got == winner, "kernel winner %d != brute-force %d" % (
        got, winner)


@pytest.mark.slow
def test_interpret_kernel_parity_batch():
    """The per-object batch kernel in interpret mode: each object's
    reported winner must match its own brute-force argmin over its
    own (offset) nonce range, and the no-hit flag must be exact."""
    import jax.numpy as jnp
    import numpy as np

    from pybitmessage_tpu.ops.sha512_pallas import pallas_batch_search

    ihs = [hashlib.sha512(b"interpret parity batch %d" % i).digest()
           for i in range(2)]
    bases = [0, 1 << 20]        # distinct per-object ranges
    vals = [_brute_values(ih, b, 128) for ih, b in zip(ihs, bases)]
    # object 0: target == its min (exactly one admissible nonce);
    # object 1: target BELOW its min (kernel must report no hit)
    t0 = min(vals[0])
    t1 = min(vals[1]) - 1
    winner0 = bases[0] + vals[0].index(t0)

    ih_words = jnp.stack([_ih_words(ih) for ih in ihs])
    b_arr = jnp.array([[b >> 32, b & 0xFFFFFFFF] for b in bases],
                      dtype=jnp.uint32)
    t_arr = jnp.array([[t0 >> 32, t0 & 0xFFFFFFFF],
                       [t1 >> 32, t1 & 0xFFFFFFFF]], dtype=jnp.uint32)
    out = np.asarray(pallas_batch_search(ih_words, b_arr, t_arr,
                                         rows=1, chunks=1, unroll=1,
                                         interpret=True))
    assert out[0, 0] == 1       # hit in grid step 0 -> step+1 == 1
    got0 = (int(out[0, 1]) << 32) | int(out[0, 2])
    assert got0 == winner0
    assert out[1, 0] == 0, "false positive below the brute-force min"


# --- the batch kernel's loop, with a hash the interpreter can afford ---
#
# What PR 40 changed in ``_batch_kernel`` is control: a grid step runs
# INNER of an object's steps in a loop that leaves at the first hit.
# The 160 rounds are what make interpret mode unusable on a CPU, and
# the loop does not care what the trial function is, so these cases
# give ``_search_step`` a two-multiply mixer in its place and run in
# tier-1; the real rounds stay with the ``slow`` cases above and with
# the chip, where every nonce is re-checked with hashlib.

ROWS, CHUNKS, INNER = 16, 8, 4          # two outer steps of four
STEP = ROWS * 128


def _toy_trial(xp, key_hi, key_lo, n_hi, n_lo):
    u = xp.uint32
    x = (n_lo ^ key_lo) * u(0x9E3779B1)
    x = (x ^ (x >> u(15))) * u(0x85EBCA77)
    x = x ^ (x >> u(13)) ^ n_hi
    return x ^ key_hi, x * u(0xC2B2AE3D) + key_lo


def _toy_values(key, base, n):
    """64-bit toy trial values of ``n`` nonces from ``base``."""
    import numpy as np
    nonce = base + np.arange(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        hi, lo = _toy_trial(np, np.uint32(key >> 32),
                            np.uint32(key & 0xFFFFFFFF),
                            (nonce >> np.uint64(32)).astype(np.uint32),
                            nonce.astype(np.uint32))
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def _per_step_reference(key, base, target):
    """The plain search: one step of STEP nonces after another, the
    first hit's row and the trials that were run to get there."""
    for step in range(CHUNKS):
        values = _toy_values(key, base + step * STEP, STEP)
        hits = (values <= target).nonzero()[0]
        if len(hits):
            nonce = base + step * STEP + int(hits[0])
            return ([step + 1, nonce >> 32, nonce & 0xFFFFFFFF],
                    (step + 1) * STEP)
    return [0, 0, 0], CHUNKS * STEP


#: where the one admissible nonce lies: (step, lane of the step)
LOOP_CASES = {
    "hit_in_step_0": (0, 5),
    "hit_inside_an_inner_loop": (2, STEP - 1),
    "hit_in_the_last_step_of_an_outer_step": (INNER - 1, 700),
    "hit_in_the_first_step_of_the_next": (INNER, 0),
    "hit_in_the_last_step_of_the_launch": (CHUNKS - 1, STEP - 1),
    "no_hit": None,
    "dead_slot": "always",
}


@pytest.fixture(scope="module")
def loop_launch():
    """One launch of the batch kernel over LOOP_CASES, in interpret
    mode with the toy trial function: ``{case: (row, key, base,
    target)}`` and the whole output."""
    import functools

    import jax.numpy as jnp
    import numpy as np

    from pybitmessage_tpu.ops import sha512_pallas as sp

    def toy_tile(ih_pair, n_hi, n_lo):
        key_hi, key_lo = ih_pair(0)
        return _toy_trial(jnp, key_hi, key_lo, n_hi, n_lo)

    span = CHUNKS * STEP
    args, seed = {}, 0
    for case, where in LOOP_CASES.items():
        # a key whose least value over three launches' nonces lies in
        # the middle one: wherever the launch's base is put below it,
        # that nonce is the only one at or under the target
        while True:
            seed += 1
            key = int.from_bytes(
                hashlib.sha512(b"loop case %d" % seed).digest()[:8], "big")
            values = _toy_values(key, 1 << 32, 3 * span)
            best = int(values.argmin())
            if span <= best < 2 * span:
                break
        least = int(values[best])
        if where is None:
            base, target = (1 << 32) + span, least - 1
        elif where == "always":
            base, target = (1 << 32) - 3, (1 << 64) - 1     # lo wraps
        else:
            step, lane = where
            base, target = (1 << 32) + best - step * STEP - lane, least
        args[case] = (key, base, target)

    def pair(x):
        return [x >> 32, x & 0xFFFFFFFF]

    ih_words = np.zeros((len(args), 8, 2), np.uint32)
    ih_words[:, 0] = [pair(key) for key, _b, _t in args.values()]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sp, "_double_sha512_tile", toy_tile)
        out = np.asarray(jax.jit(functools.partial(
            sp._batch_search, rows=ROWS, chunks=CHUNKS, interpret=True,
            unroll=1, inner=INNER))(
                jnp.asarray(ih_words),
                jnp.array([pair(b) for _k, b, _t in args.values()],
                          jnp.uint32),
                jnp.array([pair(t) for _k, _b, t in args.values()],
                          jnp.uint32)))
    return {case: (out[k].tolist(), *args[case])
            for k, case in enumerate(args)}, out


@pytest.mark.parametrize("case", list(LOOP_CASES))
def test_the_batch_kernels_loop_reports_the_row_of_a_plain_search(
        loop_launch, case):
    row, key, base, target = loop_launch[0][case]
    want, _trials = _per_step_reference(key, base, target)
    assert row == want
    where = LOOP_CASES[case]
    if isinstance(where, tuple):
        assert row[0] == where[0] + 1       # the case is what it says
    else:
        assert row[0] == (0 if where is None else 1)


def test_the_benchmarks_step_count_is_the_trials_the_loop_ran(loop_launch):
    """``benchmarks/kernel_work.batch_steps`` reads a launch's trials
    off its output rows: with a step of one tile it is exact for the
    objects that hit, those that did not, and the dead slots."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import kernel_work

    cases, out = loop_launch
    ran = sum(_per_step_reference(key, base, target)[1]
              for _row, key, base, target in cases.values())
    assert kernel_work.batch_steps(out, CHUNKS) * ROWS * 128 * 1 == ran
    assert kernel_work.launch_trials(
        "batch_steps", out, {"rows": ROWS, "chunks": CHUNKS,
                             "unroll": 1}) == ran
    assert ran == (1 + 3 + 4 + 5 + 8 + 8 + 1) * STEP


@pytest.mark.parametrize("chunks, inner", [(1024, 64), (128, 64), (4, 4),
                                           (6, 2), (1, 1)])
def test_a_grid_step_loops_over_as_many_steps_as_divide_the_launch(
        monkeypatch, chunks, inner):
    """``INNER`` is not an argument of ``pallas_batch_search``: the
    production launch of 1,024 steps is 16 grid steps an object, and a
    short launch (the tests', the pod's) is looped over whole."""
    from pybitmessage_tpu.ops import sha512_pallas as sp
    seen = {}
    monkeypatch.setattr(sp, "_batch_search",
                        lambda *arrays, **static: seen.update(static))
    sp.pallas_batch_search.__wrapped__(None, None, None, rows=8,
                                       chunks=chunks)
    assert (seen["chunks"], seen["inner"]) == (chunks, inner)
