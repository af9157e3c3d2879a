"""Solver-ladder tests: native C++ solver, python fallback, dispatcher."""

import hashlib
import threading

import pytest

from pybitmessage_tpu.ops.pow_search import PowInterrupted
from pybitmessage_tpu.pow import NativeSolver, PowDispatcher, python_solve


def _host_trial(nonce, ih):
    return int.from_bytes(hashlib.sha512(hashlib.sha512(
        nonce.to_bytes(8, "big") + ih).digest()).digest()[:8], "big")


IH = hashlib.sha512(b"ladder test").digest()
EASY = 2**59


def test_native_solver_builds_and_solves():
    solver = NativeSolver(num_threads=2)
    assert solver.available, "C++ solver must build and self-test"
    nonce, trials = solver.solve(IH, EASY)
    assert _host_trial(nonce, IH) <= EASY
    assert trials > 0


def test_native_solver_interruptible():
    solver = NativeSolver(num_threads=2)
    stop = threading.Event()
    threading.Timer(0.3, stop.set).start()
    with pytest.raises(PowInterrupted):
        solver.solve(IH, 0, should_stop=stop.is_set)  # impossible target


def test_python_solver():
    nonce, trials = python_solve(IH, 2**58)
    assert _host_trial(nonce, IH) <= 2**58


def test_python_solver_interruptible():
    calls = []

    def stop():
        calls.append(1)
        return len(calls) > 2

    with pytest.raises(PowInterrupted):
        python_solve(IH, 0, should_stop=stop)


def test_dispatcher_ladder_order_and_fallthrough():
    d = PowDispatcher(use_tpu=False)
    assert d.backends()[0] == "cpp"
    nonce, _ = d(IH, EASY)
    assert _host_trial(nonce, IH) <= EASY
    assert d.last_backend == "cpp"
    assert d.last_rate > 0

    # break the native tier; ladder must fall through to python
    d._native._lib = None
    nonce, _ = d(IH, EASY)
    assert d.last_backend == "python"
    assert _host_trial(nonce, IH) <= EASY


def test_dispatcher_tpu_tier():
    d = PowDispatcher(use_tpu=True,
                      tpu_kwargs={"lanes": 1024, "chunks_per_call": 8})
    nonce, _ = d(IH, EASY)
    # on the 8-virtual-device test mesh the pod-sharded path dispatches
    assert d.last_backend == "tpu-sharded"
    assert _host_trial(nonce, IH) <= EASY


def test_forced_tpu_failure_increments_fallback_counter(monkeypatch):
    """ISSUE 1 satellite: a dead TPU tier must show up as
    pow_fallback_total{from="tpu",to="native"} and land on cpp."""
    from pybitmessage_tpu import ops
    from pybitmessage_tpu.observability import REGISTRY

    d = PowDispatcher(use_tpu=True)
    monkeypatch.setattr(d, "_device_count", lambda: 1)
    monkeypatch.setattr(d, "_on_accelerator", lambda: False)

    def boom(*args, **kwargs):
        raise RuntimeError("forced TPU failure")

    monkeypatch.setattr(ops.pow_search, "solve", boom)
    labels = {"from": "tpu", "to": "native"}
    before = REGISTRY.sample("pow_fallback_total", labels)
    solves_before = REGISTRY.sample("pow_solve_seconds",
                                    {"backend": "cpp"})
    nonce, _ = d(IH, EASY)
    assert d.last_backend == "cpp"
    assert _host_trial(nonce, IH) <= EASY
    assert REGISTRY.sample("pow_fallback_total", labels) == before + 1
    # the rescued solve is attributed to the tier that finished it
    assert REGISTRY.sample("pow_solve_seconds",
                           {"backend": "cpp"}) == solves_before + 1
    # latched off: the dead tier must not be retried
    assert "tpu" not in d.backends()


def test_solve_only_timing_recorded_separately():
    """ISSUE 1 satellite: last_rate stays the wall figure (solve +
    host verify) while last_solve_rate excludes the verify."""
    d = PowDispatcher(use_tpu=False)
    d(IH, EASY)
    assert d.last_solve_seconds > 0
    assert d.last_verify_seconds >= 0
    assert d.last_solve_rate >= d.last_rate > 0


def _errors(site):
    from pybitmessage_tpu.observability import REGISTRY
    return REGISTRY.sample("resilience_errors_total", {"site": site})


@pytest.mark.parametrize("n_items", [1, 3])
def test_failed_jax_probe_is_a_counted_tier_failure(monkeypatch, n_items):
    """A JAX that cannot initialise is not read as "no accelerator":
    the probe raises into the tpu tier's handler (counted, logged,
    breaker opened) and the ladder's documented fall to C++ follows."""
    d = PowDispatcher()

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(d, "_device_count", broken)
    before = _errors("pow.tier.tpu")
    items = [(hashlib.sha512(b"probe %d" % i).digest(), EASY)
             for i in range(n_items)]
    results = d.solve_batch(items)
    for (ih, target), (nonce, _) in zip(items, results):
        assert _host_trial(nonce, ih) <= target
    assert d.last_backend == "cpp"
    assert _errors("pow.tier.tpu") == before + 1
    assert d.breakers["tpu"].state == "open"


@pytest.mark.asyncio
async def test_failed_verify_probe_is_counted_and_stays_on_the_host(
        monkeypatch):
    import asyncio

    from pybitmessage_tpu.pow import verify_service

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(verify_service, "_accelerator_backend", broken)
    before = _errors("pow.verify_probe")
    verifier = verify_service.BatchVerifier()
    verifier.start()
    try:
        for _ in range(100):
            if verifier._device_ok is not None:
                break
            await asyncio.sleep(0.02)
        assert verifier._device_ok is False
        assert _errors("pow.verify_probe") == before + 1
    finally:
        await verifier.stop()
