"""Pod-sharded production-kernel PoW path on the virtual CPU mesh.

The per-device slab runs ``impl="xla"`` here (Mosaic doesn't execute on
host CPU; see parallel/pow_pallas_sharded.py docstring) — the sharding,
winner resolution, host loop, per-object masking and dummy padding are
exactly the production code path; only the slab implementation differs.
The real-chip equivalence test (sharded-vs-direct Pallas rate) lives in
tests/test_pow_pallas.py behind the accelerator gate.
"""

import hashlib

import pytest

from pybitmessage_tpu.parallel import (
    make_mesh, pallas_sharded_solve, pallas_sharded_solve_batch,
)
from pybitmessage_tpu.ops.pow_search import PowInterrupted


def _host_trial(nonce: int, initial_hash: bytes) -> int:
    d = hashlib.sha512(hashlib.sha512(
        nonce.to_bytes(8, "big") + initial_hash).digest()).digest()
    return int.from_bytes(d[:8], "big")


@pytest.mark.parametrize("n_devices", [
    pytest.param(1, marks=pytest.mark.slow),
    2,
    pytest.param(8, marks=pytest.mark.slow),
])
def test_pallas_sharded_solve_finds_valid_nonce(n_devices):
    # the 2-device case stays in the tier-1 gate; the 1- and 8-device
    # variants exercise the same code path and run in the full matrix
    mesh = make_mesh(n_devices)
    ih = hashlib.sha512(b"pallas sharded %d" % n_devices).digest()
    target = 2**59
    nonce, trials = pallas_sharded_solve(
        ih, target, mesh, rows=1, chunks_per_call=8, impl="xla")
    assert _host_trial(nonce, ih) <= target
    # trials are accounted in whole pod slabs
    assert trials % (1 * 128 * 8 * n_devices) == 0


def test_pallas_sharded_solve_interrupt():
    mesh = make_mesh(2)
    ih = hashlib.sha512(b"interrupt me").digest()
    with pytest.raises(PowInterrupted):
        pallas_sharded_solve(ih, 1, mesh, rows=1, chunks_per_call=2,
                             impl="xla", should_stop=lambda: True)


@pytest.mark.slow
def test_pallas_sharded_batch_solves_all():
    mesh = make_mesh(8, obj_axis="obj", obj_size=2)
    items = [(hashlib.sha512(b"batch obj %d" % i).digest(), 2**58)
             for i in range(3)]  # 3 objects -> 1 always-hit pad slot
    results = pallas_sharded_solve_batch(
        items, mesh, rows=1, chunks_per_call=8, impl="xla")
    assert len(results) == 3
    for (nonce, trials), (ih, target) in zip(results, items):
        assert _host_trial(nonce, ih) <= target
        assert trials > 0


@pytest.mark.slow
def test_pallas_sharded_batch_easy_object_stops_consuming():
    """VERDICT r2 #8: a solved object must stop accruing work while a
    hard one continues (target swap to always-hit + per-object trial
    accounting), and padding must not duplicate real difficulty."""
    mesh = make_mesh(4, obj_axis="obj", obj_size=2)
    easy = (hashlib.sha512(b"easy").digest(), 2**62)   # ~1 in 4 trials
    hard = (hashlib.sha512(b"hard").digest(), 2**49)   # ~1 in 32k trials
    results = pallas_sharded_solve_batch(
        [easy, hard], mesh, rows=1, chunks_per_call=1, impl="xla")
    (n_easy, t_easy), (n_hard, t_hard) = results
    assert _host_trial(n_easy, easy[0]) <= easy[1]
    assert _host_trial(n_hard, hard[0]) <= hard[1]
    # the easy object solved in its first slab and stopped accruing;
    # the hard object kept launching slabs
    assert t_easy < t_hard


def test_pallas_sharded_1d_mesh_batch_falls_back():
    mesh = make_mesh(2)
    items = [(hashlib.sha512(b"fallback %d" % i).digest(), 2**59)
             for i in range(2)]
    results = pallas_sharded_solve_batch(
        items, mesh, rows=1, chunks_per_call=4, impl="xla")
    for (nonce, _), (ih, target) in zip(results, items):
        assert _host_trial(nonce, ih) <= target


def test_pallas_sharded_batch_resumes_from_start_nonces():
    """ISSUE 4 satellite (ROADMAP known gap): journaled resume offsets
    reach the pod-sharded batch loop — the search starts AT the
    checkpoint instead of re-searching from nonce 0, and miss-free
    harvests report monotonic progress checkpoints beyond it."""
    mesh = make_mesh(2, obj_axis="obj", obj_size=1)
    ih = hashlib.sha512(b"pod resume").digest()
    target = 2**53           # ~1 in 2k trials: a few 256-trial slabs
    offset = 1 << 20
    seen = []
    results = pallas_sharded_solve_batch(
        [(ih, target)], mesh, rows=1, chunks_per_call=1, impl="xla",
        start_nonces=[offset],
        progress=lambda i, nxt: seen.append((i, nxt)))
    nonce, trials = results[0]
    assert _host_trial(nonce, ih) <= target
    assert nonce >= offset, "search must resume at the checkpoint"
    for i, nxt in seen:
        assert i == 0
        assert nxt > offset
    nxts = [n for _, n in seen]
    assert nxts == sorted(nxts), "checkpoints must be monotonic"


def test_pallas_sharded_single_reports_progress():
    mesh = make_mesh(2)
    ih = hashlib.sha512(b"sharded single progress").digest()
    seen = []
    nonce, _ = pallas_sharded_solve(
        ih, 2**53, mesh, rows=1, chunks_per_call=1, impl="xla",
        start_nonce=512, progress=seen.append)
    assert _host_trial(nonce, ih) <= 2**53
    assert nonce >= 512
    assert all(nxt > 512 for nxt in seen)
    assert seen == sorted(seen)


def test_pallas_sharded_batch_spreads_a_small_batch_over_the_obj_axis(
        monkeypatch):
    """A batch smaller than the pod's capacity is dealt round-robin over
    the obj-axis devices' slot blocks — not packed into the first
    device's block with pads on the others (ISSUE 22: what the code did
    while it had only met virtual devices)."""
    import numpy as np

    from pybitmessage_tpu.parallel import pow_pallas_sharded as pod
    monkeypatch.setattr(pod, "POD_BATCH_PER_DEVICE", 2)
    seen = []
    real_get_fn = pod._get_fn

    def recording_get_fn(*args):
        fn = real_get_fn(*args)

        def call(ih_words, bases, targets):
            seen.append(np.asarray(targets))
            return fn(ih_words, bases, targets)
        return call
    monkeypatch.setattr(pod, "_get_fn", recording_get_fn)

    mesh = make_mesh(4, obj_axis="obj", obj_size=4)
    items = [(hashlib.sha512(b"spread %d" % i).digest(), 1 << 58)
             for i in range(5)]
    results = pallas_sharded_solve_batch(items, mesh, rows=1,
                                         chunks_per_call=8, impl="xla")
    for (ih, target), (nonce, _) in zip(items, results):
        assert _host_trial(nonce, ih) <= target
    live = [slot for slot, t in enumerate(seen[0])
            if tuple(t) != (0xFFFFFFFF, 0xFFFFFFFF)]
    # 4 devices x 2 slots: items 0-3 open one block each, item 4 joins
    # device 0's block
    assert live == [0, 1, 2, 4, 6]
