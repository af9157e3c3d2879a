"""Solver-ladder routing on multi-device meshes (pow/dispatcher.py).

The real-pod tiers (the Pallas-sharded single search, the pipeline
placed over the chips) can't execute on the CPU mesh, so these tests
pin the ROUTING contract with stubs: which tier is tried first, what
the fallback order is, and that a Mosaic failure latches the Pallas
tiers off instead of re-paying a failed compile on every solve
(reference resetPoW semantics, proofofwork.py:173-194)."""

import hashlib

import pytest

from pybitmessage_tpu.pow.dispatcher import PowDispatcher


IH = hashlib.sha512(b"routing").digest()


@pytest.fixture
def on_accelerator(monkeypatch):
    """Pretend the CPU mesh is an 8-chip accelerator pod."""
    monkeypatch.setattr(PowDispatcher, "_on_accelerator",
                        lambda self: True)


def test_multidev_solve_takes_the_pipeline_with_every_chip(
        monkeypatch, on_accelerator):
    """A lone object on an accelerator of several chips is the
    pipeline's, given the devices; the rung is the one chip's."""
    import jax

    import pybitmessage_tpu.parallel as par
    from pybitmessage_tpu.pow import pipeline

    calls = {}

    def fake_pipeline(items, *, devices=None, **kw):
        calls["devices"] = devices
        calls["start_nonces"] = kw["start_nonces"]
        return [(1234, 999)]

    def never(*a, **k):
        raise AssertionError("the shard_map partition was called")

    monkeypatch.setattr(pipeline, "solve_batch_pipelined", fake_pipeline)
    monkeypatch.setattr(par, "pallas_sharded_solve", never)
    d = PowDispatcher(use_native=False)
    nonce, trials = d.solve(IH, 2**60)
    assert d.last_backend == "tpu-pallas"
    assert (nonce, trials) == (1234, 999)
    assert calls["devices"] == jax.devices()
    assert len(calls["devices"]) == 8 and calls["start_nonces"] == [0]


def test_multidev_solve_falls_back_and_latches(monkeypatch,
                                               on_accelerator):
    from pybitmessage_tpu.pow import pipeline

    attempts = {"n": 0}

    def broken(*a, **k):
        attempts["n"] += 1
        raise RuntimeError("mosaic compile failed")

    monkeypatch.setattr(pipeline, "solve_batch_pipelined", broken)
    d = PowDispatcher(use_native=False)
    nonce, _ = d.solve(IH, 2**60)          # falls through to XLA sharded
    assert d.last_backend == "tpu-sharded"
    from pybitmessage_tpu.utils.hashes import double_sha512
    check = double_sha512(nonce.to_bytes(8, "big") + IH)
    assert int.from_bytes(check[:8], "big") <= 2**60
    # latched: the broken tier is not retried on the next solve
    d.solve(IH, 2**60)
    assert attempts["n"] == 1
    assert d.last_backend == "tpu-sharded"


def test_multidev_batch_prefers_the_pipeline_over_the_chips(
        monkeypatch, on_accelerator):
    import jax

    from pybitmessage_tpu.pow import pipeline

    def fake_batch(items, *, devices, **kw):
        assert devices == jax.devices()
        return [(100 + i, 50) for i in range(len(items))]

    monkeypatch.setattr(pipeline, "solve_batch_pipelined", fake_batch)
    d = PowDispatcher(use_native=False)
    items = [(hashlib.sha512(b"o%d" % i).digest(), 2**60)
             for i in range(3)]
    results = d.solve_batch(items)
    assert d.last_backend == "tpu-pallas-batch"
    assert results == [(100, 50), (101, 50), (102, 50)]


def test_multidev_batch_falls_back_to_xla_sharded(monkeypatch,
                                                  on_accelerator):
    from pybitmessage_tpu.pow import pipeline

    monkeypatch.setattr(
        pipeline, "solve_batch_pipelined",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    d = PowDispatcher(use_native=False, tpu_kwargs={
        "lanes": 1 << 12, "chunks_per_call": 8})
    items = [(hashlib.sha512(b"fb%d" % i).digest(), 2**60)
             for i in range(2)]
    results = d.solve_batch(items)
    assert d.last_backend == "tpu-batch"
    from pybitmessage_tpu.utils.hashes import double_sha512
    for (ih, target), (nonce, _) in zip(items, results):
        check = double_sha512(nonce.to_bytes(8, "big") + ih)
        assert int.from_bytes(check[:8], "big") <= target


def test_cpu_mesh_multidev_uses_xla_sharded():
    """Without the accelerator pretence the multi-device path routes
    straight to the XLA sharded tier (the real CPU-mesh behavior)."""
    d = PowDispatcher(use_native=False)
    nonce, _ = d.solve(IH, 2**60)
    assert d.last_backend == "tpu-sharded"
