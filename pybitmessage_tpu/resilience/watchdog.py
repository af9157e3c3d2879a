"""Slab-stall watchdog: bound the wall time of a blocking launch.

A wedged device launch (driver hang, preempted TPU, remote-relay
stall) would otherwise pin the dispatcher's executor thread forever —
the queue backs up and no fallback tier ever runs.  :class:`StallGuard`
runs the blocking callable on a daemon worker thread and gives up
waiting after ``timeout`` seconds: the call site gets
:class:`SlabStallError`, which the dispatcher ladder treats exactly
like a tier failure (breaker records it, the object requeues to the
next tier).

The abandoned thread cannot be killed — Python has no safe thread
cancellation — so it is left to finish (or hang) in the background as
a daemon; its eventual result is discarded.  That is the standard
trade: one leaked waiter versus a wedged pipeline.  Stall events and
the latency of the recovery that follows are exported through the
metrics registry.
"""

from __future__ import annotations

import logging
import threading
from typing import Callable

from ..observability import REGISTRY
from ..observability.flightrec import FLIGHT_RECORDER

logger = logging.getLogger("pybitmessage_tpu.resilience")

STALLS = REGISTRY.counter(
    "pow_stall_total",
    "Launches abandoned by the stall watchdog", ("site",))
STALL_RECOVERY_SECONDS = REGISTRY.histogram(
    "pow_stall_recovery_seconds",
    "Time from a stall being detected to the rescued solve completing "
    "on a fallback tier")


class SlabStallError(Exception):
    """The guarded launch exceeded its stall deadline."""


class StallGuard:
    """Run a blocking callable with a stall deadline.

    ``timeout <= 0`` disables the guard (the callable runs inline with
    zero overhead).  One worker thread per ``run()`` — fine for
    one-shot guards; the pipeline's per-harvest hot path instead keeps
    a reusable worker (``_PipelineDriver._first_in``).  Recovery latency
    is tracked by the caller (the dispatcher observes
    :data:`STALL_RECOVERY_SECONDS` when a fallback tier completes the
    rescued work) — the guard only detects and counts the stall.
    """

    def __init__(self, *, timeout: float, site: str = "pow.slab"):
        self.timeout = timeout
        self.site = site

    def run(self, fn: Callable):
        if self.timeout <= 0:
            return fn()
        done = threading.Event()
        box: dict = {}

        def worker():
            try:
                box["result"] = fn()
            except BaseException as exc:   # noqa: BLE001 — relayed below
                box["error"] = exc
            finally:
                done.set()

        t = threading.Thread(target=worker, daemon=True,
                             name="bmtpu-stall-%s" % self.site)
        t.start()
        if not done.wait(self.timeout):
            STALLS.labels(site=self.site).inc()
            # black box: the ring holds the breaker flips / chaos
            # fires / slab traffic of the seconds leading up to this —
            # dump it NOW, while the context is still in the ring
            FLIGHT_RECORDER.record("stall", site=self.site,
                                   timeout=self.timeout)
            FLIGHT_RECORDER.dump("stall")
            logger.error("%s stalled: launch exceeded %.1fs; abandoning "
                         "it and falling back", self.site, self.timeout)
            raise SlabStallError(
                "%s exceeded %.1fs stall deadline" % (self.site,
                                                      self.timeout))
        if "error" in box:
            raise box["error"]
        return box["result"]
