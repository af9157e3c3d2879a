"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding logic
(mesh partitioning of the PoW nonce space) is exercised without TPU
hardware.  Must run before the first ``import jax`` anywhere.
"""

import os

# PYBM_TEST_PLATFORM=tpu runs the suite against the real chip instead
# (used for the accelerator-gated tests in test_pow_pallas.py, which
# skip themselves on the CPU mesh).
if os.environ.get("PYBM_TEST_PLATFORM", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

# ---------------------------------------------------------------------------
# Minimal async test support (pytest-asyncio is not in the image): any
# coroutine test function runs under asyncio.run().
# ---------------------------------------------------------------------------
import asyncio  # noqa: E402
import inspect  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


# ---------------------------------------------------------------------------
# Deterministic trivial-difficulty PoW for non-PoW-focused e2e tests.
# Two-node journeys that solve at full consensus difficulty swing tens
# of seconds on nonce luck (test_two_nodes_sync_objects ranged
# 60-125 s), which is variance the 870 s tier-1 gate cannot afford.
# Tests whose subject is the NETWORK/storage path solve at ntpb=extra=10
# and point verification at the same knobs; PoW-focused tests keep
# solving at full difficulty.
# ---------------------------------------------------------------------------


class TrivialPow:
    """Helper bundle behind the ``trivial_pow`` fixture."""

    NTPB = 10
    EXTRA = 10

    @classmethod
    def apply(cls, ctx) -> None:
        """Point a NodeContext's PoW verification at the trivial
        difficulty (connections verify with the ctx knobs, clamp-free)."""
        ctx.pow_ntpb = cls.NTPB
        ctx.pow_extra = cls.EXTRA

    @classmethod
    def solved_object(cls, body: bytes, ttl: int = 600, *,
                      object_type: int = 2, version: int = 1,
                      stream: int = 1) -> bytes:
        """A PoW-valid object payload solved at trivial difficulty —
        milliseconds with the pure-python search: no device compile,
        no nonce luck."""
        from pybitmessage_tpu.models.objects import serialize_object
        from pybitmessage_tpu.models.pow_math import (pow_initial_hash,
                                                      pow_target)
        from pybitmessage_tpu.pow.dispatcher import python_solve

        expires = int(time.time()) + ttl
        obj = serialize_object(expires, object_type, version, stream,
                               body)
        # clamp=False: the network minimum would silently raise the
        # 10/10 params back into a minutes-long CPU solve
        target = pow_target(len(obj), ttl, cls.NTPB, cls.EXTRA,
                            clamp=False)
        nonce, _ = python_solve(pow_initial_hash(obj[8:]), target)
        return nonce.to_bytes(8, "big") + obj[8:]


@pytest.fixture
def trivial_pow():
    return TrivialPow


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run test via asyncio.run")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); run "
        "explicitly or in the full CI matrix")


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None
