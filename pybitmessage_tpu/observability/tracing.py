"""Structured span tracer that follows the JAX profiler.

``trace("pow.solve", backend="tpu-pallas")`` works as a context
manager or a decorator.  Each span records a monotonic start, its
duration, free-form attributes, and its parent span, linked through a
``contextvars.ContextVar``.  Nesting therefore survives ``await``
boundaries and ``asyncio`` tasks (a task copies its creator's
context); it does NOT survive ``loop.run_in_executor`` by itself,
which runs the callable in the worker thread's own context.  The two
executor hops of the send path (``workers/sender.py`` ``_run_crypto``,
``pow/service.py`` ``_run``) carry it explicitly with
``contextvars.copy_context().run``.  Finished spans land in a
fixed-size ring buffer for post-hoc inspection (tests, debugging) —
there is no background exporter to pay for.

Every span is mirrored into a ``jax.profiler.TraceAnnotation`` once
``jax`` has been imported by something else (a span never imports it;
the class is looked up once).  With no profiler session open that
costs well under a microsecond; with one open — the benchmark's
``--trace 1``, an operator's ``profileDevice`` /
``GET /debug/device?seconds=N`` — the program's spans are in the
trace, on the same clock as the device's operations, with nobody
switching anything on.  The profiler records whole intervals, so a
span that crosses an ``await`` and leaves out of order on its thread
(``worker.pow``, hundreds open at once on the loop thread) is sound
for a reader that treats host events as intervals and never as a
stack (``benchmarks/spanreduce.py``).

One ``PowService`` batch is tied together by an identifier, not by
parentage (the service's loop is a task of its own):
:func:`set_batch` puts a sequence number into the context and every
span entered under it carries it as the attribute ``batch``.

``interval("pow.lane.starved", device=2)`` is a STATE rather than a
step: ``open()`` in one turn of a loop and ``close()`` in a later one,
several open at once on one thread (one a lane of the pipeline
driver), closed in any order.  It is recorded and mirrored exactly as
a span is, but it is nobody's parent: it never touches the current
span, so what is opened meanwhile keeps the parent it would have had.

A span may be given ``histogram=<Histogram child or family>`` — its
duration is observed on exit, which is how the solve-latency
histograms are fed without a second ``time.monotonic()`` pair at the
call sites.  Call sites that need the pair themselves read it from
the span (``span.start``, ``span.end``) instead of taking it again.
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import logging
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

logger = logging.getLogger("pybitmessage_tpu.observability")

_current_span: contextvars.ContextVar["Span | None"] = \
    contextvars.ContextVar("pybitmessage_tpu_current_span", default=None)

_span_ids = itertools.count(1)

#: sequence number of the PowService batch this context works for
_batch: contextvars.ContextVar["int | None"] = \
    contextvars.ContextVar("pybitmessage_tpu_span_batch", default=None)

#: jax.profiler.TraceAnnotation, once jax has been imported
_annotation = None


def _find_annotation():
    """The profiler's annotation class if ``jax.profiler`` is already
    imported, else None.  Never imports JAX for a span."""
    global _annotation
    profiler = sys.modules.get("jax.profiler")
    # a module still being imported by another thread has no class yet
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def set_batch(seq: "int | None") -> None:
    """Every span entered in this context from now on carries
    ``batch=seq`` (``None`` stops that)."""
    _batch.set(seq)


@dataclass
class Span:
    name: str
    span_id: int
    parent_id: int | None
    start: float                      # time.monotonic()
    attrs: dict = field(default_factory=dict)
    duration: float | None = None     # filled on exit

    @property
    def end(self) -> float:
        """``time.monotonic()`` at exit (of a finished span)."""
        return self.start + self.duration

    def as_dict(self) -> dict:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "start": self.start,
                "duration": self.duration, "attrs": dict(self.attrs)}


class Tracer:
    """Ring buffer of finished spans + the trace() factory."""

    def __init__(self, maxlen: int = 2048):
        self._lock = threading.Lock()
        self.spans: deque[Span] = deque(maxlen=maxlen)

    def record(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)

    def recent(self, n: int = 50, name: str | None = None) -> list[Span]:
        with self._lock:
            out = list(self.spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out[-n:]

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()


#: process-wide default tracer
TRACER = Tracer()


def _begin(name: str, attrs: dict):
    """A started :class:`Span` under the current one, and its mirror in
    the profiler's trace (None before ``jax`` is imported)."""
    parent = _current_span.get()
    batch = _batch.get()
    if batch is not None:
        attrs.setdefault("batch", batch)
    annotation = _annotation or _find_annotation()
    jax_ctx = None
    if annotation is not None:
        # the interval starts at construction; attributes known
        # now ride along as the event's stats
        try:
            jax_ctx = annotation(name, **attrs)
        except Exception:
            jax_ctx = None
    return Span(name=name, span_id=next(_span_ids),
                parent_id=parent.span_id if parent is not None else None,
                start=time.monotonic(), attrs=attrs), jax_ctx


def _end(span: Span, jax_ctx, tracer: Tracer, exc_type=None, exc=None,
         tb=None) -> None:
    """Stamp the duration, end the mirror, put the span into the ring."""
    span.duration = time.monotonic() - span.start
    if jax_ctx is not None:
        try:
            jax_ctx.__exit__(exc_type, exc, tb)
        except Exception:
            logger.debug("jax trace annotation exit failed",
                         exc_info=True)
    tracer.record(span)


class trace:
    """Span context manager / decorator.

    >>> with trace("pow.solve", backend="cpp") as span:
    ...     ...
    >>> @trace("inventory.flush")
    ... def flush(): ...
    """

    __slots__ = ("name", "attrs", "histogram", "tracer", "span",
                 "_token", "_jax_ctx")

    def __init__(self, name: str, *, histogram=None, tracer: Tracer = None,
                 **attrs):
        self.name = name
        self.attrs = attrs
        self.histogram = histogram
        self.tracer = tracer or TRACER
        self.span = None
        self._token = None
        self._jax_ctx = None

    def __enter__(self) -> Span:
        self.span, self._jax_ctx = _begin(self.name, self.attrs)
        self._token = _current_span.set(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        _current_span.reset(self._token)
        if exc_type is not None:
            self.span.attrs["error"] = exc_type.__name__
        _end(self.span, self._jax_ctx, self.tracer, exc_type, exc, tb)
        self._jax_ctx = None
        if self.histogram is not None:
            self.histogram.observe(self.span.duration)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            # fresh instance per call — `self` holds per-entry state
            with trace(self.name, histogram=self.histogram,
                       tracer=self.tracer, **self.attrs):
                return fn(*args, **kwargs)
        return wrapper


class interval:
    """A state of a loop, as an interval: ``open()`` now, ``close()``
    in some later turn.

    >>> starved = interval("pow.lane.starved", device=2, lane=2)
    >>> starved.open()
    >>> ...                     # spans come and go on this thread
    >>> starved.close()

    Recorded into the ring and mirrored into the profiler's trace as a
    span is (parent: the span current at ``open``; ``batch`` as spans
    carry it; the attributes known at ``open`` ride as the event's
    stats), but never the current span itself: intervals overlap, close
    out of order and stay open across the spans of their thread, and a
    span opened meanwhile is not their child.
    """

    __slots__ = ("name", "attrs", "tracer", "span", "_jax_ctx")

    def __init__(self, name: str, *, tracer: Tracer = None, **attrs):
        self.name = name
        self.attrs = attrs
        self.tracer = tracer or TRACER
        self.span = None
        self._jax_ctx = None

    def open(self) -> Span:
        self.span, self._jax_ctx = _begin(self.name, self.attrs)
        return self.span

    def close(self) -> Span:
        _end(self.span, self._jax_ctx, self.tracer)
        self._jax_ctx = None
        return self.span


def current_span() -> Span | None:
    return _current_span.get()


# -- wire trace context (distributed observability plane) --------------------
#
# A compact context that crosses the wire on object pushes, sync
# rounds and PoW job hops so LifecycleTracer timelines stitch across
# nodes: 16-byte trace id + 8-byte parent span id + 8-byte wall-clock
# send time (microseconds).  Carried only to peers that negotiated the
# NODE_TRACE service bit — legacy peers see nothing.

import os
import struct

from .metrics import REGISTRY

#: encoded size on the wire: trace_id(16) + parent_span(8) + sent_at(8)
TRACE_CTX_LEN = 32

TRACE_CTX_SENT = REGISTRY.counter(
    "trace_ctx_sent_total",
    "Wire trace contexts attached to outgoing packets, by command",
    ("command",))
TRACE_CTX_RECEIVED = REGISTRY.counter(
    "trace_ctx_received_total",
    "Wire trace contexts parsed from incoming packets, by command",
    ("command",))
TRACE_CTX_INVALID = REGISTRY.counter(
    "trace_ctx_invalid_total",
    "Trace trailers that failed to parse (dropped; the carrying packet "
    "is still processed)")
TRACE_CLOCK_SKEW = REGISTRY.gauge(
    "trace_clock_skew_seconds",
    "Most recent per-connection clock-offset estimate fed by incoming "
    "trace contexts (remote clock minus local, bounded)")


def new_trace_id() -> bytes:
    return os.urandom(16)


def new_span_id() -> int:
    return int.from_bytes(os.urandom(8), "big") or 1


class TraceContext:
    """One hop's wire trace context (16B trace id + 8B parent span +
    8B send time)."""

    __slots__ = ("trace_id", "parent_span", "sent_at")

    def __init__(self, trace_id: bytes, parent_span: int,
                 sent_at: float | None = None):
        self.trace_id = bytes(trace_id[:16]).ljust(16, b"\x00")
        self.parent_span = parent_span & (2 ** 64 - 1)
        self.sent_at = time.time() if sent_at is None else float(sent_at)

    def encode(self) -> bytes:
        return self.trace_id + struct.pack(
            ">Qq", self.parent_span, int(self.sent_at * 1e6))

    @classmethod
    def decode(cls, data: bytes) -> "TraceContext":
        if len(data) < TRACE_CTX_LEN:
            raise ValueError("trace context too short")
        parent, micros = struct.unpack_from(">Qq", data, 16)
        return cls(data[:16], parent, micros / 1e6)

    def as_dict(self) -> dict:
        return {"traceId": self.trace_id.hex(),
                "parentSpan": self.parent_span,
                "sentAt": self.sent_at}

    def __repr__(self) -> str:  # debug/flightrec friendliness
        return "TraceContext(%s, parent=%x)" % (self.trace_id.hex()[:8],
                                                self.parent_span)


class SkewEstimator:
    """Bounded per-connection clock-offset estimator.

    Each incoming trace context carries the sender's wall-clock send
    time; ``observe()`` feeds ``remote_sent_at - local_recv_at`` into
    an EWMA (the one-way network delay biases the estimate negative by
    up to the path latency — acceptable for stage-latency stitching,
    where millisecond-scale bias is dwarfed by the second-scale skews
    the estimator exists to remove).  Samples beyond ``max_abs``
    seconds are clamped, so one insane peer clock cannot poison the
    estimate unboundedly, and the estimate itself is bounded by
    construction.  ``offset()`` is remote-minus-local: subtract it
    from a remote timestamp to express it on the local clock.
    """

    __slots__ = ("alpha", "max_abs", "samples", "_offset", "_dev")

    def __init__(self, *, alpha: float = 0.25, max_abs: float = 3600.0):
        self.alpha = alpha
        self.max_abs = max_abs
        self.samples = 0
        self._offset: float | None = None
        self._dev = 0.0

    def observe(self, remote_sent_at: float,
                local_recv_at: float | None = None) -> float:
        if local_recv_at is None:
            local_recv_at = time.time()
        sample = remote_sent_at - local_recv_at
        sample = max(-self.max_abs, min(self.max_abs, sample))
        if self._offset is None:
            self._offset = sample
        else:
            self._dev = (1 - self.alpha) * self._dev + \
                self.alpha * abs(sample - self._offset)
            self._offset = (1 - self.alpha) * self._offset + \
                self.alpha * sample
        self.samples += 1
        TRACE_CLOCK_SKEW.set(self._offset)
        return self._offset

    def offset(self) -> float:
        """Estimated remote-minus-local clock offset (0.0 unsampled)."""
        return self._offset if self._offset is not None else 0.0

    def deviation(self) -> float:
        return self._dev

    def normalize(self, remote_t: float) -> float:
        """A remote wall-clock timestamp expressed on the local clock."""
        return remote_t - self.offset()

    def snapshot(self) -> dict:
        return {"offsetSeconds": round(self.offset(), 6),
                "deviationSeconds": round(self._dev, 6),
                "samples": self.samples}
