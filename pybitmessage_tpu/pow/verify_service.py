"""Batched PoW verification of incoming (flooded) objects.

The reference verifies every received object's PoW host-side, one at a
time, inline in the parser thread (src/protocol.py:258-286 called from
network/bmobject.py:71-163).  Under flood traffic that is the #2 hot
loop (SURVEY §3 "hot loops ranked").  Here the checks funnel through a
single drain task: whatever accumulated while the previous batch was
in flight becomes the next batch, so batching emerges from load with
ZERO added latency (``window`` stays 0 in production — a sleep there
would serialize each connection's read loop against it).  Small
batches skip the device — two short SHA-512s on the host beat a
device round-trip (``ops.pow_search.verify``), and every batch size
(padded to a power of two) is a program of its own to trace, lower and
compile when it is first seen.  On a v5e the handful of acks a
one-at-a-time sender gets back in one announcement tick, sent to the
device, cost twelve lowerings inside a 51 s window and milliseconds of
device time for microseconds of hashing (PERF.md section 6, PR 27), so
the floor is that of the crypto rung (``cryptotpubatchmin``): 64.
"""

from __future__ import annotations

import asyncio
import logging
import time

from ..models.constants import (DEFAULT_EXTRA_BYTES,
                                DEFAULT_NONCE_TRIALS_PER_BYTE)
from ..models.pow_math import check_pow, pow_target
from ..observability import DEFAULT_SIZE_BUCKETS, REGISTRY

logger = logging.getLogger("pybitmessage_tpu.pow")

VERIFIED = REGISTRY.counter(
    "pow_verify_total",
    "Incoming-object PoW checks by execution path", ("path",))
VERIFY_BATCHES = REGISTRY.counter(
    "pow_verify_batches_total", "Device verification batches launched")
VERIFY_BATCH_SIZE = REGISTRY.histogram(
    "pow_verify_batch_size",
    "Objects per coalesced verification drain (host or device)",
    buckets=DEFAULT_SIZE_BUCKETS)
VERIFY_REJECTED = REGISTRY.counter(
    "pow_verify_rejected_total",
    "Incoming objects whose embedded PoW failed the target")
VERIFY_SHUTDOWN = REGISTRY.counter(
    "pow_verify_shutdown_unverified_total",
    "Checks still pending at verifier shutdown, settled as unverified "
    "(False) instead of leaking CancelledError into per-connection "
    "verification tasks")


def _accelerator_backend() -> bool:
    """True when the default JAX backend is a real accelerator.  On a
    CPU backend the XLA 'device' batch pays ~100 ms of dispatch per
    drain while two host SHA-512s cost ~2 µs — routing batches to the
    device there CAPPED the whole ingest path at ~25 obj/s (measured,
    ISSUE 14).  Mirrors the ``cryptotpu=auto`` probe semantics.
    Raises when JAX cannot initialise — the caller counts that."""
    import jax
    return jax.default_backend() != "cpu"


class BatchVerifier:
    """Coalesces ``check(object_bytes)`` calls into device batches.

    ``use_device``: ``"auto"`` (default) uses the device only on a
    real accelerator backend — host hashlib wins on CPU; ``True``
    forces the device path (kernel-plumbing tests, hardware runs);
    ``False`` disables it."""

    def __init__(self, *, ntpb: int = 0, extra: int = 0,
                 clamp: bool = True, window: float = 0.0,
                 min_device_batch: int = 64,
                 use_device: "bool | str" = "auto"):
        # Normalize 0 -> network defaults so the device path
        # (pow_target) and the host path (check_pow, which substitutes
        # defaults itself) agree — and never divide by zero.
        self.ntpb = ntpb or DEFAULT_NONCE_TRIALS_PER_BYTE
        self.extra = extra or DEFAULT_EXTRA_BYTES
        self.clamp = clamp
        self.window = window
        self.min_device_batch = min_device_batch
        self.use_device = use_device
        self._device_ok: bool | None = None   # lazy auto probe
        self.queue: asyncio.Queue = asyncio.Queue()
        self._task: asyncio.Task | None = None
        #: observability: how many objects went down each path
        self.host_checked = 0
        self.device_checked = 0
        self.device_batches = 0

    def start(self) -> asyncio.Task:
        if self.use_device == "auto" and self._device_ok is None:
            # resolve the backend probe OFF the event loop: the first
            # jax.default_backend() call initializes the backend
            # (hundreds of ms) and must not freeze mid-ingest.  Until
            # it lands, batches take the host path (always correct).
            import threading

            def probe() -> None:
                try:
                    self._device_ok = _accelerator_backend()
                except Exception:
                    # a broken JAX is a counted failure, not "no
                    # accelerator here"
                    from ..resilience.policy import ERRORS
                    ERRORS.labels(site="pow.verify_probe").inc()
                    logger.exception(
                        "JAX backend probe failed; PoW verification "
                        "stays on the host path")
                    self._device_ok = False
            threading.Thread(target=probe, daemon=True,
                             name="bmtpu-pow-verify-probe").start()
        self._task = asyncio.create_task(self._run())
        return self._task

    async def stop(self) -> None:
        if self._task:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        # settle any still-queued checks DETERMINISTICALLY: a pending
        # future resolves to False (reject-as-unverified, counted)
        # rather than being cancelled — cancellation leaked
        # CancelledError into the per-connection verification tasks,
        # which surfaced as spurious "object acceptance failed" noise
        # at every shutdown
        while not self.queue.empty():
            _, fut = self.queue.get_nowait()
            self._settle_unverified(fut)

    @staticmethod
    def _settle_unverified(fut: asyncio.Future) -> None:
        if not fut.done():
            VERIFY_SHUTDOWN.inc()
            fut.set_result(False)

    async def check(self, object_bytes: bytes) -> bool:
        """True when the object's embedded PoW meets the target."""
        fut = asyncio.get_running_loop().create_future()
        await self.queue.put((object_bytes, fut))
        return await fut

    # -- internals -----------------------------------------------------------

    def _target_for(self, object_bytes: bytes) -> int:
        expires = int.from_bytes(object_bytes[8:16], "big")
        ttl = max(expires - int(time.time()), 300)
        return pow_target(len(object_bytes), ttl, self.ntpb, self.extra,
                          clamp=self.clamp)

    def _host_check(self, object_bytes: bytes) -> bool:
        return check_pow(object_bytes, self.ntpb, self.extra,
                         clamp=self.clamp)

    async def _run(self) -> None:
        while True:
            batch = []
            try:
                batch.append(await self.queue.get())
                if self.window > 0:
                    await asyncio.sleep(self.window)
                while not self.queue.empty():
                    batch.append(self.queue.get_nowait())
                results = None
                VERIFY_BATCH_SIZE.observe(len(batch))
                if self._want_device() and \
                        len(batch) >= self.min_device_batch:
                    try:
                        results = await self._device_verify(
                            [ob for ob, _ in batch])
                        self.device_checked += len(batch)
                        self.device_batches += 1
                        VERIFIED.labels(path="device").inc(len(batch))
                        VERIFY_BATCHES.inc()
                    except asyncio.CancelledError:
                        raise
                    except Exception:
                        from ..resilience.policy import ERRORS
                        ERRORS.labels(site="pow.verify_device").inc()
                        logger.exception(
                            "device PoW verification failed; host "
                            "fallback")
                if results is None:
                    results = [self._host_check(ob) for ob, _ in batch]
                    self.host_checked += len(batch)
                    VERIFIED.labels(path="host").inc(len(batch))
                VERIFY_REJECTED.inc(sum(1 for ok in results if not ok))
                for (_, fut), ok in zip(batch, results):
                    if not fut.done():
                        fut.set_result(bool(ok))
            except asyncio.CancelledError:
                # deterministic settlement for EVERY popped member —
                # cancellation can land at any await above (queue,
                # window sleep, or mid device batch), and a popped
                # future left pending would hang its per-connection
                # verification task forever
                for _, fut in batch:
                    self._settle_unverified(fut)
                raise

    def _want_device(self) -> bool:
        if self.use_device == "auto":
            # None = probe still pending -> host path (never blocks)
            return bool(self._device_ok)
        return bool(self.use_device)

    async def _device_verify(self, objects: list[bytes]) -> list[bool]:
        from ..ops.pow_search import verify
        from ..utils.hashes import sha512

        items = [(int.from_bytes(ob[:8], "big"), sha512(ob[8:]),
                  self._target_for(ob)) for ob in objects]
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, lambda: verify(items))
