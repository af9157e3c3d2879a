"""Sized crypto worker pool — ECDSA/ECIES off the event loop.

The ingest fast path's crypto stage: signature checks and trial
decrypts run on a bounded ``ThreadPoolExecutor`` instead of inline on
the asyncio loop (the reference runs them inline on its parser thread,
class_objectProcessor.py:459-485, which is also what this repo did
before the ingest PR).  ``cryptography``'s OpenSSL-backed primitives
release the GIL, so the fan-out scales across cores.

Batch APIs:

- :meth:`verify_many` fans independent signature checks across the
  pool;
- :meth:`try_decrypt_many` fans ONE object's ECIES trial-decrypt
  across many candidate keys with first-match early-cancel: attempts
  still queued when a key matches never run (a match sets a shared
  event every queued attempt checks before doing work).

When a :class:`~pybitmessage_tpu.crypto.batch.BatchCryptoEngine` is
attached (``self.batch``) and running, ``verify``/``verify_many`` and
``try_decrypt_many`` route through it instead: checks coalesce across
objects and connections into GIL-releasing native batch calls
(docs/ingest.md, "Batched native crypto").  The per-call pool path
below remains the fallback (engine absent, stopped, or bench
baseline).

Parsed key objects are cached in ``crypto.keys`` (lru), so the
per-object scalar multiplication of re-deriving the same identity keys
disappears from the hot loop.

``size=0`` degrades to inline synchronous execution — the pre-PR
behavior, kept callable so ``bench.py ingest_storm`` can measure the
win instead of asserting it.
"""

from __future__ import annotations

import asyncio
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Sequence

from ..observability import DEFAULT_SIZE_BUCKETS, REGISTRY

logger = logging.getLogger("pybitmessage_tpu.cryptopool")

OPS = REGISTRY.counter(
    "crypto_pool_ops_total",
    "Crypto operations executed through the worker pool",
    ("op",))
DECRYPT_FANOUT = REGISTRY.histogram(
    "crypto_decrypt_fanout_size",
    "Candidate keys fanned out per trial-decrypt call",
    buckets=DEFAULT_SIZE_BUCKETS)
DECRYPT_RESULTS = REGISTRY.counter(
    "crypto_decrypt_total",
    "Trial-decrypt calls by outcome", ("result",))
EARLY_CANCELS = REGISTRY.counter(
    "crypto_decrypt_early_cancel_total",
    "Queued trial-decrypt attempts skipped because another key "
    "already matched (first-match early-cancel)")

BUSY_SECONDS = REGISTRY.counter(
    "cryptopool_busy_seconds_total",
    "Seconds the pool's worker threads spent inside jobs handed to "
    "run(): over wall time, the share of one thread the pool keeps busy",
    ("pool",))
QUEUE_WAIT = REGISTRY.histogram(
    "cryptopool_queue_wait_seconds",
    "Time a job handed to run() waited for a worker thread, submit to "
    "start", ("pool",))

#: default worker count — crypto is CPU-bound, so more threads than
#: cores only adds contention; capped small because the event loop and
#: the PoW executor share the same cores
DEFAULT_POOL_SIZE = max(1, min(8, (os.cpu_count() or 2)))


class CryptoPool:
    """Bounded thread pool for signature checks and trial decrypts.

    ``decrypt_fn(payload, privkey) -> plaintext`` (raising
    ``ValueError``/``DecryptionError`` on a miss) and
    ``verify_fn(data, sig, pub) -> bool`` default to the real
    ``crypto`` package, resolved lazily so this module imports (and
    its pool mechanics test) without the optional ``cryptography``
    dependency.
    """

    def __init__(self, size: int | None = None, *,
                 decrypt_fn=None, verify_fn=None, batch=None,
                 name: str = "processor"):
        #: 0 = inline synchronous execution (the pre-pool path)
        self.size = DEFAULT_POOL_SIZE if size is None else size
        #: whose pool this is: the ``pool`` label of its two series
        self.name = name
        self._busy = BUSY_SECONDS.labels(pool=name)
        self._queue_wait = QUEUE_WAIT.labels(pool=name)
        self._exec: ThreadPoolExecutor | None = None
        self._lock = threading.Lock()
        self._decrypt = decrypt_fn
        self._verify = verify_fn
        #: optional coalescing batch engine (crypto/batch.py); its
        #: drain task is started/stopped by whoever owns the pool
        #: (ObjectProcessor) — when not running, the per-call paths
        #: below serve
        self.batch = batch
        #: optional negative screen (crypto/screen.py, ISSUE 17):
        #: probed before any trial-decrypt sweep whose caller supplies
        #: an object tag; attached by the owning ObjectProcessor
        self.screen = None

    def _decrypt_fn(self):
        if self._decrypt is None:
            from ..crypto import decrypt
            self._decrypt = decrypt
        return self._decrypt

    def _verify_fn(self):
        if self._verify is None:
            from ..crypto import verify
            self._verify = verify
        return self._verify

    def _batch_active(self) -> bool:
        return self.batch is not None and self.batch.running

    def _executor(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._exec is None:
                self._exec = ThreadPoolExecutor(
                    max_workers=self.size,
                    thread_name_prefix="bmtpu-crypto")
            return self._exec

    def close(self) -> None:
        with self._lock:
            if self._exec is not None:
                self._exec.shutdown(wait=False, cancel_futures=True)
                self._exec = None

    # -- generic off-loop execution ------------------------------------------

    async def run(self, fn, *args):
        """Run ``fn(*args)`` off the event loop (inline when size=0)."""
        if self.size == 0:
            return fn(*args)
        return await asyncio.get_running_loop().run_in_executor(
            self._executor(), self._timed, time.perf_counter(), fn, *args)

    def _timed(self, submitted: float, fn, *args):
        """``fn(*args)`` on a worker thread, its wait for the thread and
        its length counted (a job that raises is counted too)."""
        started = time.perf_counter()
        self._queue_wait.observe(started - submitted)
        try:
            return fn(*args)
        finally:
            self._busy.inc(time.perf_counter() - started)

    # -- signatures ----------------------------------------------------------

    async def verify(self, data: bytes, signature: bytes,
                     pubkey: bytes) -> bool:
        """One ECDSA verification off the loop (never raises)."""
        OPS.labels(op="verify").inc()
        if self._batch_active():
            return await self.batch.verify(data, signature, pubkey)
        return bool(await self.run(self._verify_fn(), data, signature,
                                   pubkey))

    async def verify_many(
            self, items: Sequence[tuple[bytes, bytes, bytes]]
    ) -> list[bool]:
        """Fan ``(data, signature, pubkey)`` checks across the pool."""
        if not items:
            return []
        OPS.labels(op="verify").inc(len(items))
        if self._batch_active():
            return list(await asyncio.gather(
                *[self.batch.verify(*item) for item in items]))
        _verify = self._verify_fn()
        if self.size == 0:
            return [bool(_verify(*item)) for item in items]
        loop = asyncio.get_running_loop()
        ex = self._executor()
        futs = [loop.run_in_executor(ex, _verify, *item) for item in items]
        return [bool(ok) for ok in await asyncio.gather(*futs)]

    # -- trial decrypt -------------------------------------------------------

    async def try_decrypt_many(self, payload: bytes,
                               keys: Iterable[tuple[bytes, object]],
                               *, tag: bytes | None = None,
                               ) -> list[tuple[bytes, object]]:
        """ECIES trial-decrypt ``payload`` against many candidate keys.

        ``keys``: iterable of ``(privkey_bytes, handle)``; the handle
        rides along so callers can map a hit back to its identity or
        subscription.  Returns the (usually 0- or 1-element) list of
        ``(plaintext, handle)`` matches in submission order.

        ``tag`` (the object's inventory hash) opts the sweep into the
        negative screen (ISSUE 17): a cached no-match for the current
        keyring epoch returns ``[]`` without paying a single ECDH, and
        a genuinely completed no-match sweep populates the cache for
        the next gossip re-arrival.  The probe runs BEFORE ``keys`` is
        materialized, so callers may pass a lazy iterable and a
        screened re-arrival stays O(1) in keyring size.

        First-match early-cancel: a hit sets a shared event; queued
        attempts that see it set return immediately without paying the
        ECDH+HMAC.  An object is encrypted to exactly one key, so under
        a wide identity set most attempts are skipped once the right
        key lands.

        With a running batch engine the whole sweep coalesces with
        other objects' sweeps instead (the engine's transposed
        wavefront replaces the event-based cancel).
        """
        screen, epoch = self.screen, 0
        if screen is not None and tag is not None:
            # capture the epoch BEFORE probing: a key added after this
            # read voids any no-match proof this sweep could produce
            epoch = screen.epoch
            if screen.check(tag):
                DECRYPT_RESULTS.labels(result="screened").inc()
                return []
        else:
            tag = None          # no screen attached: record nothing
        keys = list(keys)
        if not keys:
            return []
        DECRYPT_FANOUT.observe(len(keys))
        OPS.labels(op="decrypt").inc(len(keys))
        if self._batch_active():
            matches = await self.batch.try_decrypt(payload, keys,
                                                   tag=tag, epoch=epoch)
            DECRYPT_RESULTS.labels(
                result="hit" if matches else "miss").inc()
            return matches
        _decrypt = self._decrypt_fn()

        found = threading.Event()
        skipped = [0]
        skipped_lock = threading.Lock()

        def attempt(priv: bytes):
            if found.is_set():
                with skipped_lock:
                    skipped[0] += 1
                return None
            try:
                out = _decrypt(payload, priv)
            except ValueError:
                # DecryptionError (a ValueError) — by design the only
                # failure ecies.decrypt raises; a miss, not an error
                return None
            found.set()
            return out

        if self.size == 0:
            matches = []
            for priv, handle in keys:
                out = attempt(priv)
                if out is not None:
                    matches.append((out, handle))
                    break       # inline mode: stop at the first match
        else:
            loop = asyncio.get_running_loop()
            ex = self._executor()
            futs = [loop.run_in_executor(ex, attempt, priv)
                    for priv, _ in keys]
            outs = await asyncio.gather(*futs)
            matches = [(out, handle) for out, (_, handle)
                       in zip(outs, keys) if out is not None]
        if skipped[0]:
            EARLY_CANCELS.inc(skipped[0])
        if tag is not None and not matches:
            # the per-call sweep tried every key (a ValueError is a
            # miss, not an abort) — a genuine no-match proof
            screen.insert(tag, epoch)
        DECRYPT_RESULTS.labels(
            result="hit" if matches else "miss").inc()
        return matches
